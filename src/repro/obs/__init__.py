"""``repro.obs`` — the observability spine: tracing, metrics, profiling.

Every layer of the reproduction reports through this package:

* the **pass pipeline** emits one span per registered pass
  (``compiler.pass``) under a ``compiler.pipeline`` span with graph
  size/factor/mode attributes;
* **``compiler.compile``** spans each request and counts how it was served
  (``compile.memo_hit`` / ``compile.replay`` / ``compile.measure`` /
  ``compile.build``);
* the **compile cache** counts health events (``cache.corrupt``,
  ``cache.stale_jax_version``) that the old code swallowed silently;
* the **pallas backend** counts the per-region emission-tier mix
  (``emission.tier.*``) and records the degradation reason next to the
  tier in ``report.emission``;
* the **plan registry** counts hits/misses/measure/replay per phase and
  fallbacks (``registry.*``), and publishes its stats as a snapshot view;
* the **serve engine** wraps warmup/prefill/per-token decode in spans and
  records TTFT + per-token latency histograms, so one ``generate()`` call
  under ``--trace`` yields a complete nested timeline;
* the **scheduler** spans each step, admission, decode, logits copy and
  sampling (``sched.*``), and the engine's step timer splits each call
  into ``engine.dispatch`` and ``engine.wait``;
* **JAX compilations** are counted (``jax.compiles``) once
  :func:`watch_compiles` is called, as the engine does.

While tracing, once JAX is loaded, the spans also land in any
``jax.profiler`` capture (:func:`profile` with a ``logdir`` takes one), on
the device trace's clock.

Quick use::

    from repro import obs
    obs.enable()                          # tracing (metrics are always on)
    with obs.span("my.step", n=3):
        ...
    obs.count("my.counter")               # counter + trace instant
    obs.observe("my.latency_s", 0.004)    # histogram sample
    obs.write_trace("trace.json")         # open at ui.perfetto.dev
    obs.snapshot()                        # pure-JSON metrics state

Naming conventions and the Perfetto workflow live in
``docs/observability.md``.
"""
from __future__ import annotations

from typing import Any, Dict

from .metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                      default_metrics, format_phases, format_snapshot,
                      set_default_metrics)
from .profile import profile, watch_compiles
from .trace import (Tracer, disable, enable, get_tracer, instant, set_tracer,
                    span, step_span, tracing_enabled, write_trace)

__all__ = [
    "Tracer", "get_tracer", "set_tracer", "enable", "disable",
    "tracing_enabled", "span", "step_span", "instant", "write_trace",
    "watch_compiles",
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "default_metrics",
    "set_default_metrics", "format_snapshot", "format_phases",
    "count", "observe", "gauge", "snapshot", "register_view", "profile",
]


def count(name: str, n: int = 1, **attrs) -> None:
    """Increment counter ``name`` and, when tracing, drop an instant event
    with ``attrs`` at the same point — the one-call form for the "counter
    events" the cache/registry/backend emit."""
    default_metrics().counter(name).inc(n)
    tr = get_tracer()
    if tr.enabled:
        tr.instant(name, **attrs)


def observe(name: str, value: float) -> None:
    """Record one histogram sample (latency, size, ...)."""
    default_metrics().histogram(name).record(value)


def gauge(name: str, value: float) -> None:
    """Set gauge ``name`` to ``value``."""
    default_metrics().gauge(name).set(value)


def snapshot(include_views: bool = True) -> Dict[str, Any]:
    """Process-wide metrics snapshot (pure JSON — see MetricsRegistry)."""
    return default_metrics().snapshot(include_views=include_views)


def register_view(name: str, fn) -> None:
    """Publish an existing stats object into every future snapshot."""
    default_metrics().register_view(name, fn)
