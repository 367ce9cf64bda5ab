"""Drives the program's scheduler on the wall clock and records what a
client sees.

The harness steps ``repro.serve.scheduler.Scheduler.run_step()`` itself:
it submits each request when it is due (``arrival = sched.step``, so the
scheduler takes it at its next step), sleeps when nothing is due or in
flight, and stamps each output token after the step whose logits reached
the host.  A request's first token is stamped when its prefill logits have
been sampled, which is when the step's decode starts; every other token
when ``run_step`` returns.

Around the calls into the program it records its own spans, as
``jax.profiler.TraceAnnotation`` (``bench.submit``, ``bench.sleep``,
``bench.run_step``, ``bench.prefill``, ``bench.decode``,
``bench.host_sampling``), and keeps per step the work the step needed (the
model family's work functions, ``bench/families``) and its host-clock
duration.

Requests beyond ``queue_cap`` waiting inside the scheduler stay with the
client until there is room, their due time unchanged.  The program's
scheduler prefills every admitted group of one prompt length as one batch,
padded to the engine batch when smaller and unpadded when larger, so a
larger group would be a prefill shape that was never compiled; the cap of
one engine batch keeps every group within the warmed shapes.  Each request
held back is counted (``deferred``).

The hooks it reads are private names of the program: the engine's
``prefill`` and ``_decode_token`` methods, its ``serve.decode_step_s``
histogram and its ``StepTimer``'s prefill phase.  Every step checks that
they still see what the scheduler did, and raises ``ProbeLost`` if not, so
a program that stops calling them fails the run instead of shifting the
stamps or silencing the metrics.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional

import jax


def annotate(name: str):
    return jax.profiler.TraceAnnotation(name)


class ProbeLost(RuntimeError):
    """The program no longer goes through a hook the harness reads."""


def engine_counts(eng) -> Dict[str, tuple]:
    """``(calls, seconds)`` of the engine's decode histogram and of its
    ``StepTimer`` prefill phase (warm calls only)."""
    hist = eng._step_hist
    pre = eng.timer._warm.get("prefill")
    return {"decode": (hist.count, hist.total),
            "prefill": (pre.count, pre.total) if pre is not None
            else (0, 0.0)}


@dataclasses.dataclass
class ReqRecord:
    spec: object
    first: Optional[float] = None
    last: Optional[float] = None
    n_tokens: int = 0
    done: bool = False
    degraded: bool = False


@dataclasses.dataclass
class StepRecord:
    kind: str                       # "prefill" | "decode"
    start: float
    end: float
    rows: int = 0                   # rows the program computed
    # per real row: its prompt length (prefill) or live context (decode)
    real: List[int] = dataclasses.field(default_factory=list)
    work: Dict[str, float] = dataclasses.field(default_factory=dict)
    attn: Dict[str, float] = dataclasses.field(default_factory=dict)


class Probe:
    """Wraps the engine's ``prefill`` and ``_decode_token`` on the instance,
    so the scheduler's own calls go through it: it times each call on the
    host clock, annotates it, and notes its shape."""

    def __init__(self, eng, sched, clock: Callable[[], float]):
        self.eng, self.sched, self.clock = eng, sched, clock
        self.step_prefills: List[StepRecord] = []
        self.step_decode: Optional[StepRecord] = None
        self.sampling = None
        prefill, decode = eng.prefill, eng._decode_token

        def traced_prefill(tokens, enc_out=None):
            t0 = self.clock()
            with annotate("bench.prefill"):
                out = prefill(tokens, enc_out)
            self.step_prefills.append(StepRecord(
                "prefill", t0, self.clock(), rows=int(tokens.shape[0]),
                real=[int(tokens.shape[1])]))
            return out

        def traced_decode(cache, batch):
            lanes = [ln for ln in self.sched.active.values()
                     if not ln.prefilling]
            contexts = [ln.req.prompt_len + len(ln.emitted) for ln in lanes]
            t0 = self.clock()
            with annotate("bench.decode"):
                out = decode(cache, batch)
            self.step_decode = StepRecord(
                "decode", t0, self.clock(),
                rows=int(batch["tokens"].shape[0]),
                real=contexts)
            self.sampling = annotate("bench.host_sampling")
            self.sampling.__enter__()
            return out

        eng.prefill = traced_prefill
        eng._decode_token = traced_decode

    def remove(self) -> None:
        """Put the engine's own methods back."""
        del self.eng.prefill, self.eng._decode_token

    def begin_step(self) -> None:
        self.step_prefills = []
        self.step_decode = None

    def end_step(self) -> None:
        if self.sampling is not None:
            self.sampling.__exit__(None, None, None)
            self.sampling = None


class Client:
    """One run's client side: the traffic, the scheduler, the records."""

    def __init__(self, sched, eng, gen, family, model,
                 queue_cap: int, clock: Callable[[], float]):
        self.sched, self.eng, self.gen = sched, eng, gen
        self.family, self.model = family, model
        self.queue_cap = queue_cap
        self.clock = clock                      # seconds, window opens at 0
        self.probe = Probe(eng, sched, clock)
        self.reqs: Dict[int, ReqRecord] = {}
        self.inflight: Dict[int, ReqRecord] = {}
        self.backlog: List = []
        self.steps: List[StepRecord] = []
        self.stamps: List[float] = []           # every output token
        self.gaps: List[tuple] = []             # (t_prev, t_next)
        self.deferred = set()                   # rids held back by the cap
        self.lateness: List[float] = []         # submit - due

    # ----------------------------------------------------------- submit --
    def submit_due(self, now: float, due_before: float = float("inf")):
        from repro.serve.scheduler import Request
        self.backlog.extend(self.gen.due_until(min(now, due_before)))
        if not self.backlog:
            return
        with annotate("bench.submit"):
            room = self.queue_cap - len(self.sched.queue) \
                - len(self.sched.pending)
            take, self.backlog = self.backlog[:max(room, 0)], \
                self.backlog[max(room, 0):]
            self.deferred.update(sp.rid for sp in self.backlog)
            if not take:
                return
            t = self.clock()
            reqs = []
            for s in take:
                rec = ReqRecord(spec=s)
                self.reqs[s.rid] = self.inflight[s.rid] = rec
                self.lateness.append(t - s.due)
                reqs.append(Request(rid=s.rid, tokens=s.prompt,
                                    n_new=s.n_new, arrival=self.sched.step))
            self.sched.submit(reqs)

    def idle(self) -> bool:
        s = self.sched
        return not (s.pending or s.queue or s.active)

    def next_due(self) -> Optional[float]:
        if self.backlog:
            return self.backlog[0].due
        return self.gen.next_due()

    def sleep_until(self, t: float) -> None:
        with annotate("bench.sleep"):
            dt = t - self.clock()
            if dt > 0:
                time.sleep(dt)

    # ------------------------------------------------------------- step --
    def step(self) -> None:
        sched, probe = self.sched, self.probe
        before = {rid: rec.n_tokens for rid, rec in self.inflight.items()}
        counts_before = engine_counts(self.eng)
        probe.begin_step()
        with annotate("bench.run_step"):
            sched.run_step()
            probe.end_step()
        t_end = self.clock()
        counts_after = engine_counts(self.eng)
        dec = probe.step_decode
        t_first = dec.start if dec is not None else t_end
        lanes = {ln.req.rid: ln for ln in sched.active.values()}
        admitted: Dict[int, int] = {}
        n_later = 0
        for rid, rec in list(self.inflight.items()):
            ln = lanes.get(rid)
            if ln is not None:
                n, degraded = len(ln.emitted), ln.degraded
            elif rid in sched.completed:
                c = sched.completed[rid]
                n, degraded = len(c.tokens), c.degraded
            else:
                continue
            prev = before.get(rid, 0)
            for k in range(prev, n):
                t = t_first if k == 0 else t_end
                if k == 0:
                    rec.first = t
                    plen = int(rec.spec.prompt.shape[0])
                    admitted[plen] = admitted.get(plen, 0) + 1
                else:
                    self.gaps.append((rec.last, t))
                    n_later += 1
                self.stamps.append(t)
                rec.last = t
            rec.n_tokens = n
            rec.degraded = rec.degraded or degraded
            if ln is None:
                rec.done = True
                del self.inflight[rid]
                self.gen.complete(rec.spec, t_end)
        self._check_hooks(sum(admitted.values()), n_later,
                          counts_before, counts_after)
        fam, m = self.family, self.model
        for p in probe.step_prefills:
            s = p.real[0]
            p.real = [s] * admitted.get(s, 0)
            p.work = fam.prefill_step(m, p.real)
            p.attn = fam.prefill_attention(m, p.real)
            self.steps.append(p)
        if dec is not None:
            dec.work = fam.decode_step(m, dec.real)
            dec.attn = fam.decode_attention(m, dec.real)
            self.steps.append(dec)

    def _check_hooks(self, n_first: int, n_later: int, before: Dict,
                     after: Dict) -> None:
        """Raise ``ProbeLost`` where the program's hooks missed this step's
        work: tokens with no wrapped call behind them, or a wrapped call
        that its counter did not count."""
        probe = self.probe
        n_dec = 0 if probe.step_decode is None else 1
        n_pre = len(probe.step_prefills)
        if n_later and not n_dec:
            raise ProbeLost(f"{n_later} tokens decoded in a step with no "
                            "Engine._decode_token call")
        if n_first and not n_pre:
            raise ProbeLost(f"{n_first} first tokens in a step with no "
                            "Engine.prefill call")
        moved = {k: after[k][0] - before[k][0] for k in after}
        if moved["decode"] != n_dec:
            raise ProbeLost(f"serve.decode_step_s counted {moved['decode']} "
                            f"decode calls, the probe saw {n_dec}")
        if moved["prefill"] != n_pre:
            raise ProbeLost(f"the prefill timer counted {moved['prefill']} "
                            f"prefill calls, the probe saw {n_pre}")

    # -------------------------------------------------------------- run --
    def run(self, until: float, on_open: Optional[Callable] = None) -> None:
        """Serve the traffic until ``until`` on the window clock, calling
        ``on_open`` once when the clock first reads 0 or more."""
        now = self.clock()
        while now < until or on_open is not None:
            if on_open is not None and now >= 0:
                on_open()
                on_open = None
                if now >= until:
                    break
            self.submit_due(now)
            if self.idle():
                nxt = self.next_due()
                wake = until if nxt is None else min(nxt, until)
                if on_open is not None:
                    wake = min(wake, 0.0)
                self.sleep_until(wake)
            else:
                self.step()
            now = self.clock()

    def ttft_s(self, window_s: float, gave_up_at: float) -> List[float]:
        """Due time to first token of every request due in the window.  A
        request that failed (a degraded step) or had no first token when the
        harness stopped waiting counts at the time it stopped waiting."""
        out = []
        for r in self.reqs.values():
            if not 0.0 <= r.spec.due < window_s:
                continue
            end = gave_up_at if r.first is None or r.degraded else r.first
            out.append(end - r.spec.due)
        return out

    def finish(self, window_s: float, limit_s: float) -> float:
        """After the window closes: step on, sending nothing new, until
        every request due in the window has its first token or ``limit_s``
        has passed.  Returns the clock reading when it stopped."""
        stop = window_s + limit_s
        while True:
            now = self.clock()
            self.submit_due(now, due_before=window_s)
            waiting = [r for r in self.reqs.values()
                       if r.spec.due < window_s and r.first is None]
            pending = self.backlog and self.backlog[0].due < window_s
            if (not waiting and not pending) or now >= stop:
                return now
            if self.idle():
                self.sleep_until(min(stop, now + 0.01))
            else:
                self.step()
