"""Continuous-batching scheduler over the :class:`repro.serve.Engine`.

The serving analogue of the paper's multi-pumping: a fixed resource budget
(``max_slots`` preallocated KV-cache lanes + the warmed decode-plan bucket)
is kept busy at a higher effective rate by interleaving *independent*
requests through it, instead of draining one batch at a time.  The step
loop runs mixed-phase iterations:

    arrivals -> shed sweep -> preemption -> admission -> prefill chunks
             -> batched decode

* **Slot manager** — ``max_slots`` decode lanes over one per-slot-pos cache
  (``models.model.init_cache(per_slot_pos=True)``: the ``pos`` leaf is a
  ``(B,)`` vector, so each cache row advances at its own depth).  Free-list
  allocation with double-alloc/double-free guards; a freed lane keeps
  masked-out garbage until re-admission overwrites it.
* **Admission** — waiting requests are admitted into freed slots between
  decode steps, ordered by ``(-priority, [deadline,] arrival, rid)`` — pure
  FIFO when no request carries a priority or deadline.  Short prompts are
  grouped by *exact* prompt length and prefilled on a fresh scalar-pos
  cache (token-level padding would corrupt SSM state / the conv tail — the
  plan registry does its own construction-exact padding internally), then
  scattered into their lanes with :func:`insert_rows`.  The prefill batch
  pads up to the engine's warmed batch size so the grouped prefill still
  hits the warm plan bucket.
* **Chunked prefill** — with ``prefill_chunk_tokens`` set, a prompt longer
  than the budget is admitted immediately but prefilled over several steps
  on a private scalar-pos side cache (``Engine.prefill_chunk`` — the
  continuation path attends over the whole written prefix and seeds the
  SSM scan from cached state), at most ``prefill_chunk_tokens`` prefill
  tokens per scheduler step across all lanes.  Decode lanes keep stepping
  between chunks, so one long prompt no longer head-of-line-blocks every
  in-flight request.  The finished side cache is scattered into the lane
  in one :func:`insert_rows`, after which the lane decodes normally.
* **Preemption** — with ``preempt_policy`` set, a queued request that
  strictly beats an active lane (higher priority, or — deadline-aware —
  strictly earlier absolute deadline) may evict it: the lane's cache rows
  are zeroed (``sched.evict_rows``), its generated-so-far tokens and PRNG
  chain are parked, and the request is requeued for bit-exact resume by
  recompute (prefill of ``prompt ++ emitted[:-1]`` restores the exact
  cache the next decode step needs — same content, same pos, same key
  chain, so the resumed tokens match the uninterrupted run).  Strictness
  plus a per-request preemption cap makes the policy livelock-free; at
  most one preemption per step keeps traces easy to reason about.
* **Admission control** — ``max_queue`` bounds the queue: an arrival that
  would overflow it is *shed* with reason ``queue_full`` (counted in
  ``sched.shed``, surfaced in :attr:`Scheduler.shed` — never silently
  dropped).  ``deadline_aware=True`` additionally sheds queued requests
  whose ``deadline_ms`` is provably unmeetable even if admitted this very
  step (reason ``deadline_unmeetable``).  Preempted requests were already
  admitted and are never shed — they always complete.
* **Decode** — one jitted ``decode_step`` over the whole slot cache per
  scheduler step.  Free and still-prefilling lanes decode garbage
  harmlessly (their outputs are never read and admission/insert overwrites
  the whole row, ``pos`` included).  Per-request sampling uses per-request
  PRNG key chains, so every request's tokens are bit-identical to running
  it alone through :meth:`Engine.generate`.

Each step is an ``obs`` step span (``sched.step``) holding its admissions
(``sched.admit``: ``serve.prefill`` then ``sched.insert_rows``, with the
admitted rids) and its decode (``sched.decode``: ``serve.decode``, then
``sched.logits_to_host``, then ``sched.sample``), and the ``sched.*``
counters count decode steps, lanes, prefill and padding rows and the logits
bytes copied to the host.  Every request is stamped on the wall clock when
it arrives (leaves ``pending`` at its arrival step) and when each of its
tokens' logits reach the host (``CompletedRequest.arrival_wall``,
``token_wall``).

Time is *virtual*: arrivals are measured in scheduler steps, so a seeded
:func:`synthetic_workload` replays deterministically — the property the
invariant harness in ``tests/test_scheduler.py`` is built on (no slot
leak/double-allocation, admission order, request conservation after every
step including sheds and preemptions, per-request token parity vs solo
generation).  ``deadline_ms`` maps onto virtual steps through
``step_time_ms`` (default 1.0: one step per millisecond).

Failure behaviour rides the engine's degradation ladder for free: prefill,
prefill chunks and decode route through :meth:`Engine._run_step`, so an
injected fault or a non-finite step re-runs on the plain-jnp rung and the
affected in-flight requests are marked degraded rather than dropped.
``sched.slot_free``, ``sched.preempt`` and ``sched.evict_rows`` are this
module's own fault sites: a fault in any of them marks the request
degraded but the slot bookkeeping still completes — a lane is never
leaked, a preempted request is never lost.
"""
from __future__ import annotations

import bisect
import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.testing import faults

PREEMPT_POLICIES = ("longest_remaining", "lowest_priority")


@dataclasses.dataclass(frozen=True)
class Request:
    """One generation request in a stream.

    ``arrival`` is in virtual scheduler steps (deterministic replay);
    ``tokens`` is the (S,) prompt.  ``priority`` orders admission and
    preemption (higher wins; default 0 keeps pure FIFO).  ``deadline_ms``
    is a completion deadline relative to arrival, interpreted through the
    scheduler's ``step_time_ms``; ``None`` = best-effort.
    """
    rid: int
    tokens: np.ndarray
    n_new: int
    arrival: int = 0
    priority: int = 0
    deadline_ms: Optional[float] = None

    @property
    def prompt_len(self) -> int:
        return int(np.asarray(self.tokens).shape[-1])


@dataclasses.dataclass
class CompletedRequest:
    """Per-request result + latency accounting for one streamed request.

    Wall times are ``time.perf_counter()`` readings: ``arrival_wall`` at
    the start of the step the request arrived in, ``token_wall[i]`` when
    the logits token ``i`` was sampled from reached the host."""
    rid: int
    tokens: np.ndarray                      # (n_new,) generated tokens
    arrival: int
    admitted_step: int
    done_step: int
    queue_wait_steps: int                   # admitted_step - arrival
    ttft_s: float                           # arrival -> first token (wall)
    tpot_s: float                           # mean inter-token wall time
    degraded: bool = False
    logits: Optional[np.ndarray] = None     # (n_new, V) fp32 when collected
    preemptions: int = 0                    # times evicted + resumed
    ttft_steps: int = 0                     # arrival -> first token (virtual)
    arrival_wall: float = 0.0
    token_wall: Optional[np.ndarray] = None  # (n_new,) float64


@dataclasses.dataclass(frozen=True)
class ShedRequest:
    """A request rejected by admission control — counted, never silent.

    ``reason`` is one of ``queue_full`` (bounded admission queue overflow)
    or ``deadline_unmeetable`` (even immediate admission could not finish
    before the deadline).  Shed requests never occupied a slot and emitted
    no tokens.
    """
    rid: int
    arrival: int
    shed_step: int
    reason: str
    prompt_len: int
    n_new: int


class SlotManager:
    """Free-list allocator over ``n`` decode lanes with leak guards.

    Double allocation and double free raise immediately — the invariant
    harness runs with these guards live, so a scheduler bug surfaces as a
    hard error inside the trace rather than as silent cache corruption.
    """

    def __init__(self, n: int):
        if n <= 0:
            raise ValueError(f"max_slots must be positive, got {n}")
        self.n = n
        self._free: List[int] = list(range(n - 1, -1, -1))  # pop() -> slot 0
        self.owner: Dict[int, int] = {}

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def occupancy(self) -> int:
        return self.n - len(self._free)

    def alloc(self, rid: int) -> int:
        if not self._free:
            raise RuntimeError("slot allocation with no free slots")
        slot = self._free.pop()
        if slot in self.owner:
            raise RuntimeError(
                f"slot {slot} double-allocated (owned by request "
                f"{self.owner[slot]}, requested by {rid})")
        self.owner[slot] = rid
        return slot

    def free(self, slot: int) -> None:
        if slot not in self.owner:
            raise RuntimeError(f"slot {slot} double-freed (no owner)")
        del self.owner[slot]
        self._free.append(slot)


def synthetic_workload(n_requests: int, *, seed: int = 0,
                       prompt_lens: Sequence[int] = (4, 8),
                       new_tokens: Sequence[int] = (2, 4),
                       arrival_rate: float = 0.5,
                       vocab: int = 100,
                       prompt_len_weights: Optional[Sequence[float]] = None,
                       deadlines_ms: Optional[Sequence] = None,
                       priorities: Optional[Sequence[int]] = None
                       ) -> List[Request]:
    """Deterministic synthetic request trace.

    Seeded inter-arrival gaps and prompt/completion lengths drawn from the
    given sets — lengths come from a *set* rather than a continuous range
    so a trace touches a bounded number of prefill shapes (one jit trace
    per distinct prompt length).  Same seed, same trace: the test harness
    replays it through both the scheduler and solo generation.

    ``arrival_rate <= 1`` keeps the original geometric-gap process (mean
    gap ``1/rate - 1`` steps) bit-identical across releases.  Overload
    shapes use ``arrival_rate > 1``: per-request Bernoulli gaps of mean
    ``1/rate`` steps, i.e. ~``rate`` arrivals per scheduler step — more
    work per step than ``max_slots`` lanes can serve, the regime the
    admission-control machinery is built for.

    The optional knobs draw extra per-request attributes *after* the base
    draws, so a trace generated without them is bit-identical to older
    releases: ``prompt_len_weights`` skews prompt lengths (heavy-tailed
    mixes), ``deadlines_ms`` assigns each request a deadline drawn from
    the given choices (``None`` entries = best-effort), ``priorities``
    likewise.
    """
    if arrival_rate <= 0.0:
        raise ValueError(f"arrival_rate must be positive, got {arrival_rate}")
    if prompt_len_weights is not None \
            and len(prompt_len_weights) != len(prompt_lens):
        raise ValueError("prompt_len_weights must match prompt_lens")
    rng = np.random.default_rng(seed)
    reqs, t = [], 0
    for rid in range(n_requests):
        if rid and arrival_rate < 1.0:
            t += int(rng.geometric(arrival_rate)) - 1
        elif rid and arrival_rate > 1.0:
            t += int(rng.random() < 1.0 / arrival_rate)
        if prompt_len_weights is None:
            plen = int(rng.choice(prompt_lens))
        else:
            plen = int(rng.choice(prompt_lens,
                                  p=np.asarray(prompt_len_weights, float)
                                  / float(np.sum(prompt_len_weights))))
        tokens = rng.integers(0, vocab, size=plen, dtype=np.int32)
        n_new = int(rng.choice(new_tokens))
        deadline = None
        if deadlines_ms is not None:
            pick = deadlines_ms[int(rng.integers(len(deadlines_ms)))]
            deadline = None if pick is None else float(pick)
        priority = 0
        if priorities is not None:
            priority = int(priorities[int(rng.integers(len(priorities)))])
        reqs.append(Request(rid=rid, tokens=tokens, n_new=n_new, arrival=t,
                            priority=priority, deadline_ms=deadline))
    return reqs


def insert_rows(big_cache, small_cache, slots, n_rows: int):
    """Scatter ``n_rows`` prefilled rows of ``small_cache`` into the
    per-slot lanes ``slots`` of ``big_cache``.

    Cache leaves are stacked over layers — ``(n_layers, B, ...)`` (the
    hybrid family adds an ``(n_groups, B, ...)`` ``shared_attn`` group,
    which the same rule covers).  The ``pos`` leaf is the one asymmetric
    case: scalar-per-layer ``(n_layers,)`` in the fresh prefill cache vs
    per-slot ``(n_layers, B)`` in the big cache — each admitted lane's pos
    is set to its prompt length.  ``small_cache`` may carry padding rows
    beyond ``n_rows`` (prefill pads the batch up to the warm plan bucket);
    they are dropped here.
    """
    slots = jnp.asarray(slots, jnp.int32)

    def one(path, big, small):
        last = path[-1]
        key = getattr(last, "key", getattr(last, "name", None))
        if key == "pos":
            return big.at[:, slots].set(small[:, None])
        return big.at[:, slots].set(small[:, :n_rows])

    return jax.tree_util.tree_map_with_path(one, big_cache, small_cache)


@dataclasses.dataclass
class _Lane:
    """In-flight per-slot decode state."""
    req: Request
    key: jax.Array                  # per-request PRNG chain (parity w/ solo)
    cur: int = 0                    # last sampled token (next decode input)
    emitted: List[int] = dataclasses.field(default_factory=list)
    logits: List[np.ndarray] = dataclasses.field(default_factory=list)
    admitted_step: int = 0
    arrival_wall: float = 0.0
    token_wall: List[float] = dataclasses.field(default_factory=list)
    first_tok_step: int = -1
    degraded: bool = False
    preemptions: int = 0
    # chunked-prefill state: tokens still being written into the private
    # scalar-pos side cache; the lane holds a slot but does not decode
    # until the side cache is complete and scattered in
    prefilling: bool = False
    prefill_toks: Optional[np.ndarray] = None
    prefill_done: int = 0
    side: Any = None


@dataclasses.dataclass
class _QueueItem:
    """One admission-queue entry: a fresh request, or a preempted lane
    parked for resume-by-recompute (``resume`` carries its emitted tokens,
    PRNG chain and latency accounting)."""
    req: Request
    resume: Optional[_Lane] = None
    arrival_wall: float = 0.0               # when it left ``pending``


class Scheduler:
    """The continuous-batching step loop.  Built by
    :meth:`Engine.serve_stream`; usable directly when a test needs to drive
    steps one at a time.

    ``step_hook(state_dict)`` (if given) runs after every scheduler step
    with a snapshot: ``step, occupancy, queue, pending, active, completed,
    admitted`` (rids admitted this step), plus the overload surface —
    ``shed`` (total shed so far), ``preempted`` (rids preempted this
    step), ``prefilling`` (slots still mid-chunked-prefill) — the surface
    the invariant harness asserts on.
    """

    def __init__(self, engine, *, max_slots: Optional[int] = None,
                 collect_logits: bool = False,
                 step_hook: Optional[Callable[[Dict[str, Any]], None]] = None,
                 prefill_chunk_tokens: Optional[int] = None,
                 preempt_policy: Optional[str] = None,
                 max_queue: Optional[int] = None,
                 deadline_aware: bool = False,
                 step_time_ms: float = 1.0,
                 max_preemptions: int = 2):
        from repro.models import model as model_mod
        cfg = engine.cfg
        if cfg.family == "encdec":
            raise ValueError(
                "continuous batching is not supported for the encdec "
                "family (cross-attention caches are per-request)")
        if preempt_policy is not None and \
                preempt_policy not in PREEMPT_POLICIES:
            raise ValueError(
                f"preempt_policy must be one of {PREEMPT_POLICIES}, "
                f"got {preempt_policy!r}")
        if prefill_chunk_tokens is not None and prefill_chunk_tokens < 1:
            raise ValueError("prefill_chunk_tokens must be >= 1")
        if max_queue is not None and max_queue < 1:
            raise ValueError("max_queue must be >= 1")
        if step_time_ms <= 0.0:
            raise ValueError("step_time_ms must be positive")
        self.engine = engine
        self.max_slots = int(max_slots or engine.scfg.batch)
        self.collect_logits = collect_logits
        self.step_hook = step_hook
        self.prefill_chunk_tokens = prefill_chunk_tokens
        self.preempt_policy = preempt_policy
        self.max_queue = max_queue
        self.deadline_aware = deadline_aware
        self.step_time_ms = float(step_time_ms)
        self.max_preemptions = int(max_preemptions)
        self.slots = SlotManager(self.max_slots)
        cdt = jnp.dtype(engine.scfg.cache_dtype)
        self.cache = model_mod.init_cache(cfg, self.max_slots,
                                          engine.scfg.max_len, cdt,
                                          per_slot_pos=True)
        # fresh scalar-pos side cache for one chunk-prefilling lane
        self._side_factory = lambda: model_mod.init_cache(
            cfg, 1, engine.scfg.max_len, cdt)
        self.active: Dict[int, _Lane] = {}
        self.queue: List[_QueueItem] = []
        self.pending: List[Request] = []
        self.completed: Dict[int, CompletedRequest] = {}
        self.shed: Dict[int, ShedRequest] = {}
        self.preempt_count = 0
        self.step = 0
        self._total = 0

    # ------------------------------------------------------------ helpers --
    def _sample_row(self, logits_row, key) -> int:
        """Sample one token for one lane — same math as
        ``Engine._sample`` on a (1, V) batch, so a streamed request's
        tokens match its solo run exactly (per-request key chain)."""
        eng = self.engine
        if eng.scfg.temperature <= 0.0:
            return int(np.argmax(np.asarray(logits_row)))
        out = jax.random.categorical(
            key, jnp.asarray(logits_row)[None] / eng.scfg.temperature)
        return int(out[0])

    def _prefill_tokens(self, it: _QueueItem) -> np.ndarray:
        """The token sequence admission must prefill for this entry: the
        prompt, plus — for a preempted resume — every already-emitted
        token except the last (resume-by-recompute: the cache then holds
        exactly what the uninterrupted run's cache held before its next
        decode step, at the same pos; the last emitted token becomes the
        next decode input)."""
        base = np.asarray(it.req.tokens, np.int32).reshape(-1)
        if it.resume is not None and it.resume.emitted:
            return np.concatenate(
                [base, np.asarray(it.resume.emitted[:-1], np.int32)])
        return base

    def _lane_for(self, it: _QueueItem) -> _Lane:
        if it.resume is not None:
            return it.resume
        return _Lane(req=it.req,
                     key=jax.random.PRNGKey(self.engine.scfg.seed),
                     arrival_wall=it.arrival_wall)

    def _qkey(self, it: _QueueItem):
        r = it.req
        if self.deadline_aware:
            ds = self._deadline_step(r)
            return (-r.priority, float("inf") if ds is None else ds,
                    r.arrival, r.rid)
        return (-r.priority, r.arrival, r.rid)

    def _enqueue(self, it: _QueueItem) -> None:
        keys = [self._qkey(x) for x in self.queue]
        self.queue.insert(bisect.bisect_right(keys, self._qkey(it)), it)

    def _deadline_step(self, r: Request) -> Optional[int]:
        """Absolute virtual-step deadline, or None for best-effort."""
        if r.deadline_ms is None:
            return None
        return r.arrival + int(np.ceil(r.deadline_ms / self.step_time_ms))

    def _chunks_for(self, n_tokens: int) -> int:
        c = self.prefill_chunk_tokens
        if c is None or n_tokens <= c:
            return 1
        return -(-n_tokens // c)

    def _min_done_step(self, it: _QueueItem) -> int:
        """Earliest possible completion step if admitted *this* step:
        ``chunks`` prefill steps (the last also samples the first token)
        then one decode step per remaining token."""
        chunks = self._chunks_for(len(self._prefill_tokens(it)))
        done = len(it.resume.emitted) if it.resume is not None else 0
        rem = max(it.req.n_new - done, 1)
        return self.step + chunks + rem - 2

    def _remaining_work(self, lane: _Lane) -> int:
        """Tokens of work left in a lane — decode tokens still to emit
        plus prefill tokens still to write (preemption-victim metric)."""
        rem = lane.req.n_new - len(lane.emitted)
        if lane.prefilling:
            rem += len(lane.prefill_toks) - lane.prefill_done
        return rem

    def _shed_request(self, it: _QueueItem, reason: str) -> None:
        r = it.req
        self.shed[r.rid] = ShedRequest(
            rid=r.rid, arrival=r.arrival, shed_step=self.step,
            reason=reason, prompt_len=r.prompt_len, n_new=r.n_new)
        obs.count("sched.shed", reason=reason)
        # counters don't carry attrs (they only reach the tracer), so the
        # reason-named counter is its own metric — the overload report and
        # chaos tests read shed causes from the snapshot by name
        obs.count(f"sched.shed.{reason}")

    def _finish(self, slot: int, lane: _Lane) -> None:
        """Complete the lane's request and reclaim its slot.  A fault at
        the ``sched.slot_free`` site marks the request degraded but the
        slot is reclaimed regardless — a lane is never leaked."""
        try:
            faults.check("sched.slot_free", slot=slot, rid=lane.req.rid)
        except Exception as e:  # noqa: BLE001 — serving must not die
            obs.count("sched.slot_free_fault", reason=type(e).__name__)
            lane.degraded = True
        self.slots.free(slot)
        del self.active[slot]
        r = lane.req
        n = len(lane.emitted)
        walls = np.asarray(lane.token_wall, np.float64)
        tpot = ((walls[-1] - walls[0]) / (n - 1)) if n > 1 else 0.0
        ttft = float(walls[0] - lane.arrival_wall)
        if lane.degraded:
            self.engine.degraded_requests += 1
            obs.count("serve.degraded_request")
        obs.observe("serve.request_ttft_s", ttft)
        obs.observe("serve.request_tpot_s", tpot)
        self.completed[r.rid] = CompletedRequest(
            rid=r.rid, tokens=np.asarray(lane.emitted, np.int32),
            arrival=r.arrival, admitted_step=lane.admitted_step,
            done_step=self.step,
            queue_wait_steps=lane.admitted_step - r.arrival,
            ttft_s=ttft, tpot_s=tpot,
            degraded=lane.degraded,
            logits=(np.stack(lane.logits).astype(np.float32)
                    if self.collect_logits else None),
            preemptions=lane.preemptions,
            ttft_steps=lane.first_tok_step - r.arrival,
            arrival_wall=lane.arrival_wall, token_wall=walls)

    def _first_token(self, slot: int, lane: _Lane, last_row: np.ndarray,
                     now: float) -> None:
        """Prefill finished for this lane: sample the first token (fresh
        admission) or restore the parked decode input (resume — the
        prefill logits predict a token that was already emitted before
        preemption, so they are discarded).  ``now`` is when the prefill
        logits reached the host."""
        if lane.emitted:
            lane.cur = lane.emitted[-1]
            return
        tok0 = self._sample_row(last_row, lane.key)
        lane.emitted.append(tok0)
        lane.cur = tok0
        lane.token_wall.append(now)
        lane.first_tok_step = self.step
        if self.collect_logits:
            lane.logits.append(last_row)
        obs.observe("sched.queue_wait_steps",
                    lane.admitted_step - lane.req.arrival)
        if lane.req.n_new <= 1:
            self._finish(slot, lane)

    # ---------------------------------------------------------- admission --
    def _admit(self, admitted: List[_QueueItem]) -> None:
        """Prefill + insert for this step's admissions: grouped whole-
        prompt prefill for entries within the chunk budget, slot + side-
        cache setup for the rest (their chunks advance in
        :meth:`_advance_chunks`, starting this same step)."""
        eng = self.engine
        budget = self.prefill_chunk_tokens
        direct: List[_QueueItem] = []
        for it in admitted:
            n_tok = len(self._prefill_tokens(it))
            if budget is not None and n_tok > budget:
                slot = self.slots.alloc(it.req.rid)
                lane = self._lane_for(it)
                if it.resume is None:
                    lane.admitted_step = self.step
                lane.prefilling = True
                lane.prefill_toks = self._prefill_tokens(it)
                lane.prefill_done = 0
                lane.side = self._side_factory()
                self.active[slot] = lane
            else:
                direct.append(it)
        groups: Dict[int, List[_QueueItem]] = {}
        for it in direct:
            groups.setdefault(len(self._prefill_tokens(it)), []).append(it)
        for plen, grp in groups.items():
            toks = np.stack([self._prefill_tokens(it) for it in grp])
            g = len(grp)
            # pad the prefill batch up to the engine's warmed batch size so
            # the grouped prefill hits the warm plan bucket (rows are
            # independent through attention/SSM/dropless-MoE; the padding
            # rows are dropped before insert)
            pad_to = eng.scfg.batch if g <= eng.scfg.batch else g
            if pad_to > g:
                toks = np.concatenate(
                    [toks, np.repeat(toks[-1:], pad_to - g, axis=0)])
            rids = [it.req.rid for it in grp]
            # a profiler annotation's stats are comma-separated text
            rid_text = " ".join(map(str, rids))
            obs.count("sched.prefill_rows", pad_to)
            obs.count("sched.prefill_pad_rows", pad_to - g)
            with obs.span("sched.admit", cat="sched", rids=rid_text,
                          plen=plen, rows=pad_to, pad_rows=pad_to - g):
                eng._req_degraded = False
                small, last = eng.prefill(jnp.asarray(toks))
                degraded = eng._req_degraded
                slot_ids = [self.slots.alloc(rid) for rid in rids]
                with obs.span("sched.insert_rows", cat="sched",
                              rids=rid_text):
                    self.cache = insert_rows(self.cache, small, slot_ids, g)
                last_h = np.asarray(last[:g], np.float32)
                now = time.perf_counter()
                for i, (it, slot) in enumerate(zip(grp, slot_ids)):
                    lane = self._lane_for(it)
                    if it.resume is None:
                        lane.admitted_step = self.step
                    lane.degraded = lane.degraded or degraded
                    self.active[slot] = lane
                    self._first_token(slot, lane, last_h[i], now)

    def _advance_chunks(self) -> None:
        """Advance chunk-prefilling lanes, oldest admission first, within
        the per-step ``prefill_chunk_tokens`` token budget.  A lane's
        chunk is always ``min(budget, remaining)`` — the trace shapes stay
        bounded (one full-chunk shape plus one remainder shape per prompt
        length) — and a younger lane never overtakes an older one."""
        budget = self.prefill_chunk_tokens
        lanes = sorted(
            ((s, ln) for s, ln in self.active.items() if ln.prefilling),
            key=lambda sl: (sl[1].admitted_step, sl[0]))
        eng = self.engine
        left = budget
        for slot, lane in lanes:
            total = len(lane.prefill_toks)
            take = min(budget, total - lane.prefill_done)
            if take > left:
                break
            left -= take
            seg = lane.prefill_toks[lane.prefill_done:
                                    lane.prefill_done + take]
            eng._req_degraded = False
            lane.side, last = eng.prefill_chunk(
                lane.side, jnp.asarray(seg[None]))
            lane.degraded = lane.degraded or eng._req_degraded
            lane.prefill_done += take
            obs.count("sched.prefill_chunk")
            if lane.prefill_done == total:
                with obs.span("sched.insert_rows", cat="sched",
                              rids=str(lane.req.rid)):
                    self.cache = insert_rows(self.cache, lane.side, [slot],
                                             1)
                lane.side = None
                lane.prefilling = False
                lane.prefill_toks = None
                last_h = np.asarray(last[0], np.float32)
                self._first_token(slot, lane, last_h, time.perf_counter())

    # --------------------------------------------------------- preemption --
    def _maybe_preempt(self) -> List[int]:
        """At most one preemption per step: if no slot is free and the
        queue head *strictly* beats an active lane (higher priority, or —
        deadline-aware — a strictly earlier absolute deadline at equal
        priority), evict the policy-chosen victim.  Strict dominance means
        a victim can never bounce its preemptor back, and the per-request
        cap bounds total preemptions, so the policy cannot livelock."""
        if (self.preempt_policy is None or not self.queue
                or self.slots.free_count > 0):
            return []
        c = self.queue[0].req
        cd = self._deadline_step(c)
        victims: List[tuple] = []
        for slot, lane in self.active.items():
            v = lane.req
            if lane.preemptions >= self.max_preemptions:
                continue
            vd = self._deadline_step(v)
            beats = v.priority < c.priority or (
                self.deadline_aware and v.priority == c.priority
                and cd is not None and (vd is None or cd < vd))
            if beats:
                victims.append((slot, lane))
        if not victims:
            return []
        if self.preempt_policy == "lowest_priority":
            slot, lane = min(
                victims,
                key=lambda sl: (sl[1].req.priority,
                                -self._remaining_work(sl[1]), sl[0]))
        else:  # longest_remaining
            slot, lane = max(
                victims,
                key=lambda sl: (self._remaining_work(sl[1]), -sl[0]))
        self._preempt(slot, lane)
        return [lane.req.rid]

    def _preempt(self, slot: int, lane: _Lane) -> None:
        """Evict a lane: zero its cache rows, park its generated-so-far
        state, requeue for resume.  Both fault sites mark the request
        degraded on injection but the bookkeeping always completes — the
        slot is freed exactly once and the request stays in the system."""
        try:
            faults.check("sched.preempt", slot=slot, rid=lane.req.rid)
        except Exception as e:  # noqa: BLE001 — serving must not die
            obs.count("sched.preempt_fault", reason=type(e).__name__)
            lane.degraded = True
        self._evict_rows(slot, lane)
        self.slots.free(slot)
        del self.active[slot]
        lane.prefilling = False
        lane.prefill_toks = None
        lane.prefill_done = 0
        lane.side = None
        lane.preemptions += 1
        self.preempt_count += 1
        obs.count("sched.preempt", policy=self.preempt_policy)
        self._enqueue(_QueueItem(req=lane.req, resume=lane))

    def _evict_rows(self, slot: int, lane: _Lane) -> None:
        """Zero the lane's rows (pos included) across every cache leaf.
        Correctness only needs the pos reset — a garbage row is never
        read and re-admission overwrites it whole — but zeroing is cheap
        hygiene that keeps post-mortem cache dumps honest."""
        try:
            faults.check("sched.evict_rows", slot=slot, rid=lane.req.rid)
        except Exception as e:  # noqa: BLE001 — serving must not die
            obs.count("sched.evict_rows_fault", reason=type(e).__name__)
            lane.degraded = True
        self.cache = jax.tree.map(
            lambda x: x.at[:, slot].set(jnp.zeros_like(x[:, slot])),
            self.cache)

    # --------------------------------------------------------------- loop --
    def _decode(self) -> None:
        """One batched decode step over every decodable lane (chunk-
        prefilling lanes hold their slot but skip decode; their garbage
        rows advance harmlessly and are overwritten by insert)."""
        eng = self.engine
        decodable = {s: ln for s, ln in self.active.items()
                     if not ln.prefilling}
        if not decodable:
            return
        lanes = len(decodable)
        obs.count("sched.decode_steps")
        obs.count("sched.decode_lanes", lanes)
        with obs.span("sched.decode", cat="sched", lanes=lanes):
            toks = np.zeros((self.max_slots, 1), np.int32)
            for slot, lane in decodable.items():
                toks[slot, 0] = lane.cur
            eng._req_degraded = False
            logits, self.cache = eng._decode_token(
                self.cache, {"tokens": jnp.asarray(toks)})
            degraded = eng._req_degraded
            with obs.span("sched.logits_to_host", cat="sched"):
                last = logits[:, -1]
                rows = np.asarray(last, np.float32)
            now = time.perf_counter()
            obs.count("sched.logits_host_bytes", last.nbytes)
            with obs.span("sched.sample", cat="sched", lanes=lanes):
                for slot, lane in list(decodable.items()):
                    if degraded:
                        lane.degraded = True
                    lane.key, sub = jax.random.split(lane.key)
                    tok = self._sample_row(rows[slot], sub)
                    lane.emitted.append(tok)
                    lane.token_wall.append(now)
                    if self.collect_logits:
                        lane.logits.append(rows[slot])
                    if len(lane.emitted) >= lane.req.n_new:
                        self._finish(slot, lane)
                    else:
                        lane.cur = tok

    def submit(self, requests: Sequence[Request]) -> None:
        max_len = self.engine.scfg.max_len
        for r in requests:
            if r.prompt_len + r.n_new > max_len:
                raise ValueError(
                    f"request {r.rid}: prompt_len {r.prompt_len} + n_new "
                    f"{r.n_new} exceeds max_len {max_len}")
            if r.n_new < 1:
                raise ValueError(f"request {r.rid}: n_new must be >= 1")
        self.pending.extend(sorted(requests, key=lambda r: (r.arrival, r.rid)))
        self._total += len(requests)

    def run_step(self) -> None:
        """One scheduler step: arrivals -> shed sweep -> preemption ->
        admission -> prefill chunks -> batched decode."""
        with obs.step_span("sched.step", self.step, cat="sched"):
            now = time.perf_counter()
            while self.pending and self.pending[0].arrival <= self.step:
                it = _QueueItem(req=self.pending.pop(0), arrival_wall=now)
                if self.max_queue is not None \
                        and len(self.queue) >= self.max_queue:
                    self._shed_request(it, "queue_full")
                else:
                    self._enqueue(it)
            if self.deadline_aware:
                # shed sweep: a queued request whose deadline cannot be met
                # even by admitting it right now will never be met — count it
                # out instead of burning slot time on it.  Preempted requests
                # were admitted and are exempt: they always complete.
                keep: List[_QueueItem] = []
                for it in self.queue:
                    ds = self._deadline_step(it.req)
                    if it.resume is None and ds is not None \
                            and self._min_done_step(it) > ds:
                        self._shed_request(it, "deadline_unmeetable")
                    else:
                        keep.append(it)
                self.queue = keep
            preempted = self._maybe_preempt()
            admitted: List[_QueueItem] = []
            while self.queue and len(admitted) < self.slots.free_count:
                # always the queue head: a request never overtakes a
                # better-ranked one into a slot (pure FIFO at equal rank)
                admitted.append(self.queue.pop(0))
            if admitted:
                self._admit(admitted)
            if self.prefill_chunk_tokens is not None:
                self._advance_chunks()
            self._decode()
            obs.gauge("sched.slot_occupancy", self.slots.occupancy)
            obs.gauge("sched.queue_depth", len(self.queue))
            # conservation: every submitted request is exactly one of
            # not-yet-arrived / queued / in-flight / completed / shed
            accounted = (len(self.pending) + len(self.queue) + len(self.active)
                         + len(self.completed) + len(self.shed))
            if accounted != self._total:
                raise RuntimeError(
                    f"request conservation violated at step {self.step}: "
                    f"{accounted} accounted vs {self._total} submitted")
            if self.step_hook is not None:
                self.step_hook({
                    "step": self.step,
                    "occupancy": self.slots.occupancy,
                    "free": self.slots.free_count,
                    "queue": [it.req.rid for it in self.queue],
                    "pending": len(self.pending),
                    "active": {s: ln.req.rid for s, ln in self.active.items()},
                    "admitted": [it.req.rid for it in admitted],
                    "completed": len(self.completed),
                    "shed": len(self.shed),
                    "preempted": preempted,
                    "prefilling": sorted(s for s, ln in self.active.items()
                                         if ln.prefilling),
                })
            self.step += 1

    def run(self, requests: Sequence[Request]) -> List[CompletedRequest]:
        self.submit(requests)
        if not self.pending:
            return []
        # stall guard: every step makes progress (a token decodes, a chunk
        # advances, or an admission/shed happens), so total steps are
        # bounded by the arrivals span + per-request work — each request
        # costs up to n_new decode steps plus its prefill chunks, and a
        # preempted request repays its (longer) prefill up to
        # max_preemptions more times
        reqs = self.pending
        work = sum(
            r.n_new
            + self._chunks_for(r.prompt_len + r.n_new)
            * (1 + (self.max_preemptions
                    if self.preempt_policy is not None else 0))
            for r in reqs)
        bound = (max(r.arrival for r in reqs) + work
                 + len(reqs) + self.max_slots + 8)
        with obs.span("serve.stream", cat="serve", requests=self._total,
                      max_slots=self.max_slots) as sp:
            while self.pending or self.queue or self.active:
                if self.step > bound:
                    raise RuntimeError(
                        f"scheduler stalled: step {self.step} exceeded "
                        f"bound {bound} with {len(self.completed)}/"
                        f"{self._total} completed")
                self.run_step()
            sp.set(steps=self.step, completed=len(self.completed),
                   shed=len(self.shed), preemptions=self.preempt_count)
        return [self.completed[rid] for rid in sorted(self.completed)]
