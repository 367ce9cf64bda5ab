"""Continuous-batching scheduler invariant harness (docs/serving.md).

The contract the scheduler (:mod:`repro.serve.scheduler`) must hold on any
seeded trace:

* **No slot double-allocation or leak** — every slot in the per-step
  snapshots is owned by at most one request, occupancy never exceeds
  ``max_slots``, and the trace ends with every slot free.
* **FIFO admission fairness** — requests enter slots in arrival order; a
  later arrival never overtakes an earlier one into a lane.
* **Conservation** — after every step, submitted == not-yet-arrived +
  queued + in-flight + completed (also enforced inside ``run_step``).
* **Per-request parity** — every streamed request's tokens are identical
  to running it alone through ``Engine.generate()`` and its sampled-from
  logits agree to ≤5e-6 — the mixed ragged in-flight batch must be
  indistinguishable from solo serving.
* **Throughput** — the point of the exercise: the stream sustains ≥1.3×
  the tokens/s of draining the same trace sequentially per-request.

All workloads come from :func:`scheduler.synthetic_workload` (seeded
arrivals + length distributions), so every failure replays exactly.
"""
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs.base import load_arch
from repro.models import model as model_mod
from repro.serve import scheduler as sched
from repro.serve.engine import Engine, ServeConfig

ARCH = "qwen3-0.6b"
PARITY = 5e-6


def _direct_engine(batch=4, max_len=32):
    """Compiler-free engine (plain-jnp paths): fast to build, the right
    harness for scheduler-logic tests — plan-registry routing has its own
    test below."""
    cfg = dataclasses.replace(load_arch(ARCH, smoke=True),
                              attention_impl="xla_chunked",
                              kernel_plan="direct")
    params = model_mod.init_params(cfg, jax.random.PRNGKey(0))
    return Engine(cfg, params, ServeConfig(batch=batch, max_len=max_len,
                                           warmup=False))


@pytest.fixture(scope="module")
def engine():
    return _direct_engine()


# ----------------------------------------------------------- workload gen ---
def test_synthetic_workload_is_deterministic():
    a = sched.synthetic_workload(12, seed=7, arrival_rate=0.4)
    b = sched.synthetic_workload(12, seed=7, arrival_rate=0.4)
    assert [r.arrival for r in a] == [r.arrival for r in b]
    assert all(np.array_equal(x.tokens, y.tokens) for x, y in zip(a, b))
    assert [r.n_new for r in a] == [r.n_new for r in b]
    # arrivals are nondecreasing and lengths come from the given sets
    assert all(x.arrival <= y.arrival for x, y in zip(a, a[1:]))
    assert {r.prompt_len for r in a} <= {4, 8}
    assert {r.n_new for r in a} <= {2, 4}
    c = sched.synthetic_workload(12, seed=8, arrival_rate=0.4)
    assert any(not np.array_equal(x.tokens, y.tokens) for x, y in zip(a, c))


def test_workload_validation():
    with pytest.raises(ValueError):
        sched.synthetic_workload(2, arrival_rate=0.0)
    eng_like = sched.SlotManager
    with pytest.raises(ValueError):
        eng_like(0)


# ------------------------------------------------------------ slot manager --
def test_slot_manager_guards():
    sm = sched.SlotManager(2)
    s0 = sm.alloc(10)
    s1 = sm.alloc(11)
    assert {s0, s1} == {0, 1} and sm.free_count == 0 and sm.occupancy == 2
    with pytest.raises(RuntimeError, match="no free slots"):
        sm.alloc(12)
    sm.free(s0)
    with pytest.raises(RuntimeError, match="double-freed"):
        sm.free(s0)
    assert sm.alloc(12) == s0          # freed lane is reused
    # a corrupted free list (the seam double-alloc guards) is caught
    sm._free.append(s1)
    with pytest.raises(RuntimeError, match="double-allocated"):
        sm.alloc(13)


# -------------------------------------------------------------- invariants --
class InvariantChecker:
    """step_hook that re-derives every scheduler invariant per step."""

    def __init__(self, n_requests: int, max_slots: int):
        self.n, self.max_slots = n_requests, max_slots
        self.steps = 0
        self.admitted_order = []
        self.ever_active = set()
        self.max_occupancy = 0

    def __call__(self, snap):
        self.steps += 1
        occ = snap["occupancy"]
        assert 0 <= occ <= self.max_slots, snap
        assert occ == len(snap["active"]), "occupancy vs active desync"
        assert occ + snap["free"] == self.max_slots, "slot leak"
        rids = list(snap["active"].values())
        assert len(rids) == len(set(rids)), \
            f"request in two slots at step {snap['step']}: {snap['active']}"
        self.admitted_order.extend(snap["admitted"])
        self.ever_active.update(rids)   # lanes still in flight at step end
        self.max_occupancy = max(self.max_occupancy, occ)
        # conservation (the scheduler asserts it too; re-derive from the
        # snapshot so a broken internal assert can't hide it)
        assert (snap["pending"] + len(snap["queue"]) + occ
                + snap["completed"]) == self.n, snap

    def finish(self, results, requests):
        assert len(results) == self.n, "not every request completed"
        assert self.admitted_order == sorted(self.admitted_order), \
            f"FIFO admission violated: {self.admitted_order}"
        # every request was admitted exactly once (fast finishers may
        # complete inside their admission step, so ever_active is a subset)
        assert set(self.admitted_order) == {r.rid for r in requests}
        assert len(self.admitted_order) == self.n
        assert self.ever_active <= {r.rid for r in requests}
        for r in results:
            assert r.queue_wait_steps >= 0
            assert r.admitted_step >= 0 and r.done_step >= r.admitted_step


def test_invariants_over_200_step_trace(engine):
    """The acceptance-criteria trace: ≥200 seeded scheduler steps with
    queueing pressure (more requests than slots, bursty arrivals)."""
    reqs = sched.synthetic_workload(70, seed=3, prompt_lens=(2, 4),
                                    new_tokens=(2, 4, 6),
                                    arrival_rate=0.28,
                                    vocab=engine.cfg.vocab_size)
    chk = InvariantChecker(len(reqs), max_slots=4)
    res = engine.serve_stream(reqs, step_hook=chk)
    chk.finish(res, reqs)
    assert chk.steps >= 200, f"trace too short: {chk.steps} steps"
    assert chk.max_occupancy == 4, "the trace never filled the slots"
    assert any(r.queue_wait_steps > 0 for r in res), \
        "the trace never exercised the queue"
    # finished clean: all lanes free, nothing in flight
    s = sched.Scheduler(engine)  # fresh — engine holds no scheduler state
    assert s.slots.free_count == s.max_slots


def test_conservation_violation_fails_loud(engine):
    """A scheduler bug that loses a request must raise, not hang."""
    reqs = sched.synthetic_workload(4, seed=0, prompt_lens=(2,),
                                    new_tokens=(2,), arrival_rate=1.0,
                                    vocab=engine.cfg.vocab_size)
    s = sched.Scheduler(engine)
    s.submit(reqs)
    s._total += 1  # simulate a lost request
    with pytest.raises(RuntimeError, match="conservation"):
        while s.pending or s.queue or s.active:
            s.run_step()


def test_request_validation(engine):
    with pytest.raises(ValueError, match="exceeds max_len"):
        engine.serve_stream([sched.Request(0, np.zeros(40, np.int32), 8)])
    with pytest.raises(ValueError, match="n_new"):
        engine.serve_stream([sched.Request(0, np.zeros(4, np.int32), 0)])


def test_encdec_family_rejected():
    """Cross-attention caches are per-request; continuous batching refuses
    the family up front (both at the scheduler and at init_cache)."""
    cfg = load_arch("whisper-base", smoke=True)
    shell = object.__new__(Engine)      # cfg/scfg are all Scheduler reads
    shell.cfg, shell.scfg = cfg, ServeConfig(batch=2, max_len=16)
    with pytest.raises(ValueError, match="encdec"):
        sched.Scheduler(shell)
    with pytest.raises(ValueError, match="encdec"):
        model_mod.init_cache(cfg, 2, 16, jnp.float32, per_slot_pos=True)


# ------------------------------------------------------------------ parity --
def test_stream_token_parity_vs_solo(engine):
    """Every streamed request reproduces its solo run exactly: same tokens,
    sampled-from logits within 5e-6 — the ragged mixed batch is
    indistinguishable from serving each request alone."""
    reqs = sched.synthetic_workload(8, seed=11, prompt_lens=(3, 5, 8),
                                    new_tokens=(1, 3, 5),
                                    arrival_rate=0.5,
                                    vocab=engine.cfg.vocab_size)
    res = {r.rid: r for r in engine.serve_stream(reqs, collect_logits=True)}
    for r in reqs:
        got = res[r.rid]
        assert got.tokens.shape == (r.n_new,)
        assert got.logits.shape[0] == r.n_new
        solo_t, solo_l = engine.generate(
            jnp.asarray(np.asarray(r.tokens))[None], r.n_new,
            return_logits=True)
        np.testing.assert_array_equal(got.tokens, np.asarray(solo_t)[0],
                                      err_msg=f"rid {r.rid}")
        err = float(np.max(np.abs(got.logits - np.asarray(solo_l)[:, 0])))
        assert err <= PARITY, f"rid {r.rid}: logit drift {err:.2e}"


def test_stream_parity_registry_route(tmp_path, monkeypatch):
    """The plan-registry serving config (pallas + measured plans): parity
    still holds and the stream runs on 100% warm plans — zero post-warmup
    misses in either phase, with the ragged per-slot decode counted."""
    from repro import compiler
    from repro.compiler.registry import PlanRegistry, set_default_registry
    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path / "cache"))
    compiler.clear_memo()
    old = set_default_registry(PlanRegistry())
    try:
        _run_registry_route_case()
    finally:
        set_default_registry(old)


def _run_registry_route_case():
    cfg = dataclasses.replace(load_arch(ARCH, smoke=True),
                              attention_impl="pallas")
    params = model_mod.init_params(cfg, jax.random.PRNGKey(0))
    eng = Engine(cfg, params, ServeConfig(batch=2, max_len=16))
    warm = eng.stats()["registry"]          # warmup's own cold measures
    ragged = obs.snapshot(include_views=False)["counters"].get(
        "registry.decode.ragged_pos", 0)
    reqs = sched.synthetic_workload(3, seed=2, prompt_lens=(4, 8),
                                    new_tokens=(2, 3), arrival_rate=0.8,
                                    vocab=cfg.vocab_size)
    res = {r.rid: r for r in eng.serve_stream(reqs, collect_logits=True)}
    st = eng.stats()["registry"]            # before the batch-1 solo runs
    assert st["decode"]["misses"] == warm["decode"]["misses"], \
        "the stream's decode went cold post-warmup"
    assert st["prefill"]["misses"] == warm["prefill"]["misses"], \
        "the stream's prefill went cold post-warmup"
    assert st["decode"]["hits"] > warm["decode"]["hits"]
    assert st["prefill"]["hits"] > warm["prefill"]["hits"]
    assert st["fallbacks"] == warm["fallbacks"]
    assert obs.snapshot(include_views=False)["counters"].get(
        "registry.decode.ragged_pos", 0) > ragged
    for r in reqs:
        solo_t, solo_l = eng.generate(
            jnp.asarray(np.asarray(r.tokens))[None], r.n_new,
            return_logits=True)
        np.testing.assert_array_equal(res[r.rid].tokens,
                                      np.asarray(solo_t)[0])
        err = float(np.max(np.abs(res[r.rid].logits
                                  - np.asarray(solo_l)[:, 0])))
        assert err <= PARITY, f"rid {r.rid}: logit drift {err:.2e}"


# -------------------------------------------------------------- throughput --
def test_stream_throughput_beats_sequential(engine):
    """≥1.3× tokens/s over draining the trace sequentially per-request.
    Both paths are pre-warmed (traced + compiled) before timing."""
    reqs = sched.synthetic_workload(10, seed=5, prompt_lens=(4, 8),
                                    new_tokens=(6, 8), arrival_rate=1.0,
                                    vocab=engine.cfg.vocab_size)
    total_tokens = sum(r.n_new for r in reqs)

    def run_stream():
        return engine.serve_stream(reqs)

    def run_sequential():
        for r in reqs:
            engine.generate(jnp.asarray(np.asarray(r.tokens))[None], r.n_new)

    run_stream(); run_sequential()          # warm both paths
    best_stream = min(_timed(run_stream) for _ in range(2))
    best_seq = min(_timed(run_sequential) for _ in range(2))
    tps_stream = total_tokens / best_stream
    tps_seq = total_tokens / best_seq
    speedup = tps_stream / tps_seq
    assert speedup >= 1.3, \
        (f"stream {tps_stream:.1f} tok/s vs sequential {tps_seq:.1f} tok/s "
         f"— only {speedup:.2f}x")


def _timed(fn):
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


# ----------------------------------------------------------------- overload --
class OverloadChecker:
    """step_hook for overload traces: the InvariantChecker contract extended
    with shed accounting, preemption re-admission, queue bounds, and the
    chunked-prefill snapshot keys."""

    def __init__(self, n_requests: int, max_slots: int,
                 max_queue=None):
        self.n, self.max_slots, self.max_queue = \
            n_requests, max_slots, max_queue
        self.steps = 0
        self.admissions = {}            # rid -> times admitted into a slot
        self.preemptions = {}           # rid -> times preempted
        self.max_occupancy = 0
        self.saw_prefilling = False

    def __call__(self, snap):
        self.steps += 1
        occ = snap["occupancy"]
        assert 0 <= occ <= self.max_slots, snap
        assert occ == len(snap["active"]), "occupancy vs active desync"
        assert occ + snap["free"] == self.max_slots, "slot leak"
        rids = list(snap["active"].values())
        assert len(rids) == len(set(rids)), \
            f"request in two slots at step {snap['step']}: {snap['active']}"
        if self.max_queue is not None:
            assert len(snap["queue"]) <= self.max_queue, \
                f"admission queue bound exceeded: {snap}"
        assert set(snap["prefilling"]) <= set(snap["active"]), snap
        self.saw_prefilling |= bool(snap["prefilling"])
        for rid in snap["admitted"]:
            self.admissions[rid] = self.admissions.get(rid, 0) + 1
        for rid in snap["preempted"]:
            self.preemptions[rid] = self.preemptions.get(rid, 0) + 1
        self.max_occupancy = max(self.max_occupancy, occ)
        # conservation, now including sheds
        assert (snap["pending"] + len(snap["queue"]) + occ
                + snap["completed"] + snap["shed"]) == self.n, snap

    def finish(self, completed, shed, requests):
        done = {r.rid for r in completed}
        dropped = {s.rid for s in shed}
        # every submitted request completed or was shed, exactly once each
        assert done | dropped == {r.rid for r in requests}
        assert not (done & dropped), "request both completed and shed"
        assert len(completed) + len(shed) == self.n
        # shed requests never touched a slot; completed ones were admitted
        # exactly (1 + preemptions) times
        assert not (dropped & set(self.admissions)), \
            "a shed request was admitted into a slot"
        for r in completed:
            assert self.admissions.get(r.rid) == 1 + r.preemptions, \
                (r.rid, self.admissions.get(r.rid), r.preemptions)
            assert self.preemptions.get(r.rid, 0) == r.preemptions
        for s in shed:
            assert s.reason in ("queue_full", "deadline_unmeetable"), s


def test_overload_invariants_200_steps_with_preemption(engine):
    """The acceptance trace: 200+ steps at 2x the service rate with chunked
    prefill, preemption, deadlines and a bounded queue — every invariant
    holds, every request completes or is shed with a named reason."""
    reqs = sched.synthetic_workload(
        130, seed=13, prompt_lens=(2, 4, 8, 16), new_tokens=(2, 4, 6),
        arrival_rate=0.35, vocab=engine.cfg.vocab_size,
        prompt_len_weights=(0.35, 0.3, 0.2, 0.15),
        deadlines_ms=(10, 20, None), priorities=(0, 1, 2))
    chk = OverloadChecker(len(reqs), max_slots=2, max_queue=8)
    completed, shed = engine.serve_stream(
        reqs, max_slots=2, step_hook=chk, prefill_chunk_tokens=4,
        preempt_policy="lowest_priority", max_queue=8,
        deadline_aware=True, return_shed=True)
    chk.finish(completed, shed, reqs)
    assert chk.steps >= 200, f"trace too short: {chk.steps} steps"
    assert chk.max_occupancy == 2
    assert chk.saw_prefilling, "chunked prefill never engaged"
    assert sum(chk.preemptions.values()) >= 1, \
        "the trace never exercised preemption"
    assert shed, "the trace never exercised shedding"
    # preempted requests are never shed: they were admitted and must finish
    assert set(chk.preemptions) <= {r.rid for r in completed}


def test_chunked_prefill_token_parity(engine):
    """Chunked prefill is a pure scheduling change: the same trace served
    with and without a chunk budget yields identical tokens, and both match
    solo generation."""
    reqs = sched.synthetic_workload(6, seed=21, prompt_lens=(3, 9, 17),
                                    new_tokens=(2, 4), arrival_rate=0.6,
                                    vocab=engine.cfg.vocab_size)
    plain = {r.rid: r.tokens for r in engine.serve_stream(reqs)}
    for chunk in (4, 5):                    # aligned and ragged boundaries
        chunked = {r.rid: r for r in engine.serve_stream(
            reqs, prefill_chunk_tokens=chunk)}
        for r in reqs:
            np.testing.assert_array_equal(
                chunked[r.rid].tokens, plain[r.rid],
                err_msg=f"rid {r.rid} chunk={chunk}")
    long_req = max(reqs, key=lambda r: r.prompt_len)
    solo = engine.generate(jnp.asarray(np.asarray(long_req.tokens))[None],
                           long_req.n_new)
    np.testing.assert_array_equal(plain[long_req.rid], np.asarray(solo)[0])


def test_preempted_request_resumes_bit_exact(engine):
    """A preempted lane (evicted mid-decode, requeued, re-prefilled with
    its emitted tokens) finishes with exactly the tokens of its solo run."""
    rng = np.random.default_rng(0)
    toks = lambda n: rng.integers(0, engine.cfg.vocab_size, n,
                                  dtype=np.int64)
    reqs = [
        # two low-priority long decodes fill both slots at step 0 ...
        sched.Request(0, toks(4), 10, arrival=0, priority=0),
        sched.Request(1, toks(4), 10, arrival=0, priority=0),
        # ... then a high-priority arrival forces a preemption
        sched.Request(2, toks(4), 2, arrival=2, priority=5),
    ]
    completed, shed = engine.serve_stream(
        reqs, max_slots=2, preempt_policy="lowest_priority",
        return_shed=True)
    assert not shed
    res = {r.rid: r for r in completed}
    assert sum(r.preemptions for r in completed) >= 1, \
        "no preemption happened"
    for r in reqs:
        solo = engine.generate(
            jnp.asarray(np.asarray(r.tokens))[None], r.n_new)
        np.testing.assert_array_equal(res[r.rid].tokens,
                                      np.asarray(solo)[0],
                                      err_msg=f"rid {r.rid}")


@pytest.mark.parametrize("preempt", [False, True])
def test_request_wall_records(engine, preempt):
    """Each completed request carries one non-decreasing wall stamp per
    token, and its TTFT runs from its arrival: stamped in the step it
    arrived in, before its first token.  A preempted request keeps the
    stamps of the tokens it emitted before its eviction."""
    rng = np.random.default_rng(1)
    reqs = [sched.Request(i, rng.integers(0, engine.cfg.vocab_size, 4),
                          6 if i < 2 else 3, arrival=i // 2,
                          priority=5 if preempt and i == 2 else 0)
            for i in range(4)]
    s = sched.Scheduler(
        engine, max_slots=2,
        preempt_policy="lowest_priority" if preempt else None)
    s.submit(reqs)
    starts = []                     # wall clock as each step begins
    while s.pending or s.queue or s.active:
        starts.append(time.perf_counter())
        s.run_step()
    starts.append(time.perf_counter())
    assert (s.preempt_count > 0) == preempt
    for r in reqs:
        c = s.completed[r.rid]
        assert c.token_wall.dtype == np.float64
        assert c.token_wall.shape == (r.n_new,) == c.tokens.shape
        assert np.all(np.diff(c.token_wall) >= 0)
        assert starts[r.arrival] <= c.arrival_wall < starts[r.arrival + 1]
        assert c.arrival_wall <= c.token_wall[0]
        assert c.ttft_s == c.token_wall[0] - c.arrival_wall


def test_ttft_runs_from_arrival_not_submission(engine):
    """A request submitted long before its virtual arrival step counts
    none of the steps before it in its TTFT: it is no longer than its
    first token minus the start of the step it arrived in."""
    rng = np.random.default_rng(2)
    late = 6
    reqs = [sched.Request(0, rng.integers(0, engine.cfg.vocab_size, 4), 8,
                          arrival=0),
            sched.Request(1, rng.integers(0, engine.cfg.vocab_size, 4), 2,
                          arrival=late)]
    s = sched.Scheduler(engine, max_slots=2)
    submitted = time.perf_counter()
    s.submit(reqs)
    starts = []
    while s.pending or s.queue or s.active:
        starts.append(time.perf_counter())
        s.run_step()
    c = s.completed[1]
    assert c.ttft_steps == 0 and c.admitted_step == late
    assert c.ttft_s <= c.token_wall[0] - starts[late]
    assert c.ttft_s < c.token_wall[0] - submitted
    # the early request's TTFT does not run from the late one's arrival
    assert 0 < s.completed[0].ttft_s <= \
        s.completed[0].token_wall[0] - starts[0]


def test_admission_control_sheds_with_named_reasons(engine):
    """queue_full fires on a bounded queue under burst arrivals;
    deadline_unmeetable fires on a deadline no admission could meet.
    Reason-named counters in the obs snapshot move for both."""
    def ctr(name):
        return obs.snapshot(include_views=False)["counters"].get(name, 0)
    before_qf = ctr("sched.shed.queue_full")
    before_dl = ctr("sched.shed.deadline_unmeetable")
    rng = np.random.default_rng(1)
    toks = lambda n: rng.integers(0, engine.cfg.vocab_size, n,
                                  dtype=np.int64)
    reqs = [sched.Request(i, toks(4), 6, arrival=0) for i in range(8)]
    # rid 8: a deadline even immediate admission cannot meet — it arrives
    # after the step-0 burst so the bounded queue has room and the shed
    # reason is the deadline, not the overflow
    reqs.append(sched.Request(8, toks(8), 8, arrival=2, deadline_ms=1.0))
    completed, shed = engine.serve_stream(
        reqs, max_slots=2, max_queue=3, deadline_aware=True,
        return_shed=True)
    reasons = {s.rid: s.reason for s in shed}
    assert reasons.get(8) == "deadline_unmeetable"
    assert "queue_full" in set(reasons.values())
    assert len(completed) + len(shed) == len(reqs)
    assert ctr("sched.shed.queue_full") > before_qf
    assert ctr("sched.shed.deadline_unmeetable") > before_dl


def test_overload_workload_shapes():
    """synthetic_workload's overload extensions: rate > 1 packs arrivals
    tighter than service, weights skew lengths, deadlines/priorities attach
    — all under the same seed contract (old signature bit-identical)."""
    old = sched.synthetic_workload(16, seed=4, arrival_rate=0.5)
    again = sched.synthetic_workload(16, seed=4, arrival_rate=0.5)
    assert [r.arrival for r in old] == [r.arrival for r in again]
    assert all(r.priority == 0 and r.deadline_ms is None for r in old)
    hot = sched.synthetic_workload(
        64, seed=4, arrival_rate=3.0, prompt_lens=(2, 16),
        prompt_len_weights=(0.9, 0.1), deadlines_ms=(5, None),
        priorities=(0, 1))
    hot2 = sched.synthetic_workload(
        64, seed=4, arrival_rate=3.0, prompt_lens=(2, 16),
        prompt_len_weights=(0.9, 0.1), deadlines_ms=(5, None),
        priorities=(0, 1))
    assert [r.arrival for r in hot] == [r.arrival for r in hot2]
    assert [r.priority for r in hot] == [r.priority for r in hot2]
    assert [r.deadline_ms for r in hot] == [r.deadline_ms for r in hot2]
    # rate 3.0 packs ~3 arrivals per step; span well under n_requests
    assert hot[-1].arrival < 40
    assert sum(r.prompt_len == 2 for r in hot) > sum(
        r.prompt_len == 16 for r in hot)
    assert {r.priority for r in hot} == {0, 1}
    assert {r.deadline_ms for r in hot} <= {5.0, None}
    with pytest.raises(ValueError):
        sched.synthetic_workload(2, prompt_len_weights=(1.0,))
    with pytest.raises(ValueError):
        sched.synthetic_workload(2, priorities=())


def test_preempt_policy_validation(engine):
    with pytest.raises(ValueError, match="preempt_policy"):
        sched.Scheduler(engine, preempt_policy="steal_everything")
    with pytest.raises(ValueError, match="prefill_chunk_tokens"):
        sched.Scheduler(engine, prefill_chunk_tokens=0)
    with pytest.raises(ValueError, match="max_queue"):
        sched.Scheduler(engine, max_queue=0)


# ------------------------------------------------------------- degradation --
def test_stream_decode_fault_degrades_not_drops(engine):
    """A decode-step fault mid-stream re-runs on the plain-jnp rung: every
    request still completes with parity and the in-flight ones are counted
    degraded (the chaos suite covers the full matrix)."""
    from repro.testing import faults
    reqs = sched.synthetic_workload(4, seed=9, prompt_lens=(4,),
                                    new_tokens=(4,), arrival_rate=1.0,
                                    vocab=engine.cfg.vocab_size)
    clean = {r.rid: r.tokens for r in engine.serve_stream(reqs)}
    before = engine.degraded_requests
    rule = faults.FaultRule("engine.decode", "error", after=1, times=1)
    try:
        with faults.inject(rule):
            res = engine.serve_stream(reqs)
    finally:
        faults.clear()
    assert rule.fired == 1
    assert len(res) == len(reqs)
    for r in res:
        np.testing.assert_array_equal(r.tokens, clean[r.rid])
    n_deg = sum(1 for r in res if r.degraded)
    assert n_deg >= 1
    assert engine.degraded_requests == before + n_deg
