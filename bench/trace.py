"""Reduces a JAX profiler trace (``.xplane.pb``) to the numbers the
benchmark reports: device busy and idle time over the traced window, the
time of the Pallas kernels (``tpu_custom_call``), the device operations
that took most time, and the idle gaps named by what the host was doing.

The window is the host span ``bench.window``, which the harness opens when
the measured window opens and closes when it closes.  A device operation is
an event on an ``XLA Ops`` line of a TPU plane.  An idle gap is a stretch of
the window in which no operation runs on the chip; it is named by the
deepest ``bench.*`` host span that covers its midpoint (``none`` where no
span does).  Times are in seconds; busy time is averaged over the chips.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

WINDOW = "bench.window"
SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:TPU:"
CUSTOM_CALL = "tpu_custom_call"
_CONTAINER = re.compile(r"(^|[\s)}])(while|conditional|call)\(")


def find(logdir: str) -> Optional[str]:
    """The newest ``.xplane.pb`` under ``logdir``."""
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def _union(intervals: Iterable[Tuple[float, float]]) -> List[List[float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def op_name(text: str) -> str:
    """The instruction's name (``%fusion.12``) out of an op event's name,
    which on a TPU trace is the whole HLO instruction."""
    return text.split(" = ", 1)[0].strip()


def is_container(text: str) -> bool:
    """A loop or call whose body's ops are events of their own."""
    return _CONTAINER.search(text.split(" = ", 1)[-1]) is not None


def is_custom_call(text: str) -> bool:
    """A Pallas kernel lowers to a custom call whose target is
    ``tpu_custom_call``."""
    return CUSTOM_CALL in text


def reduce(path: str, top: int = 10) -> Dict:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    spans: List[Tuple[float, float, str, int]] = []
    chips: List[List[Tuple[float, float, str, str]]] = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    s = ev.start_ns
                    ops.append((s, s + ev.duration_ns, ev.name))
            if ops:
                chips.append(ops)
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    s = ev.start_ns
                    spans.append((s, s + ev.duration_ns, ev.name, 0))
    windows = [sp for sp in spans if sp[2] == WINDOW]
    if not windows:
        raise ValueError(f"{path}: no {WINDOW!r} span in the trace")
    lo, hi = windows[0][0], windows[0][1]
    if not chips:
        raise ValueError(f"{path}: no TPU plane with {OPS_LINE!r} events")
    busy_ns, custom_ns = [], 0.0
    by_op: Dict[str, float] = {}
    unions = []
    for ops in chips:
        clipped = []
        for s, e, name in ops:
            s, e = _clip(s, e, lo, hi)
            if e <= s:
                continue
            clipped.append((s, e))
            if is_container(name):
                continue
            key = op_name(name)
            if is_custom_call(name):
                key += " (tpu_custom_call)"
                custom_ns += e - s
            by_op[key] = by_op.get(key, 0.0) + (e - s)
        u = _union(clipped)
        unions.append(u)
        busy_ns.append(sum(e - s for s, e in u))
    n = len(chips)
    gaps = _gaps(unions[0], lo, hi)
    named: Dict[str, float] = {}
    inner = [sp for sp in spans if sp[2] != WINDOW]
    for (s, e), label in zip(gaps, _label(inner, [(s + e) / 2
                                                  for s, e in gaps])):
        named[label] = named.get(label, 0.0) + (e - s)
    ops_top = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    gaps_top = sorted(named.items(), key=lambda kv: -kv[1])[:top]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy_ns) / n / 1e9,
        "custom_call_s": custom_ns / n / 1e9,
        "chips": n,
        "device_ops": [[k, v / n / 1e9] for k, v in ops_top],
        "idle_gaps": [[k, v / 1e9] for k, v in gaps_top],
    }


def _gaps(union: List[List[float]], lo: float, hi: float):
    out, t = [], lo
    for s, e in union:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def _label(spans, times: List[float]) -> List[str]:
    """For each of the increasing ``times``, the name of the shortest host
    span covering it (spans nest), or ``none``."""
    spans = sorted(spans)
    out, active, i = [], [], 0
    for t in times:
        while i < len(spans) and spans[i][0] <= t:
            active.append(spans[i])
            i += 1
        active = [sp for sp in active if sp[1] >= t]
        out.append(min(active, key=lambda sp: sp[1] - sp[0])[2]
                   if active else "none")
    return out
