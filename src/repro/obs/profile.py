"""Profiling hooks: a span with optional ``jax.profiler`` capture, and the
JAX compile listener.

``obs.profile("prefill", logdir="...")`` is the one-command answer to
"where does the time go *inside* one compiled step" — the span lands in the
obs trace (wall-clock attribution across our own layers) and, when a
``logdir`` is given, a ``jax.profiler`` trace capture brackets the same
window with tracing on, so the program's own spans sit beside
XLA/TPU-level cost in TensorBoard/Perfetto.

:func:`watch_compiles` counts JAX's compilations (``jax.compiles``) and,
while tracing, drops a ``jax.compile`` instant with each one's duration.

The jax profiler is strictly optional: import/start/stop failures degrade
to the plain span with a counted ``profile.unavailable`` event — profiling
hooks must never take the serving path down.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Iterator, Optional

from . import metrics as _metrics
from . import trace as _trace


@contextlib.contextmanager
def profile(name: str = "profile", logdir: Optional[str] = None,
            **attrs) -> Iterator[object]:
    """Span (always) + ``jax.profiler`` trace capture (when ``logdir``).

        with obs.profile("serve.prefill", logdir="/tmp/jaxprof"):
            engine.prefill(tokens)

    View the capture with ``tensorboard --logdir /tmp/jaxprof`` or load the
    generated ``.trace.json.gz`` into ui.perfetto.dev.
    """
    started = False
    tracer = _trace.get_tracer()
    if logdir is not None:
        try:
            import jax
            jax.profiler.start_trace(str(logdir))
            started = True
        except Exception as e:  # noqa: BLE001 — profiler absence is not fatal
            _metrics.default_metrics().counter("profile.unavailable").inc()
            _trace.instant("profile.unavailable", error=repr(e))
    if started:
        was_enabled, tracer.enabled = tracer.enabled, True
    span = tracer.span(name, cat="profile", profiled=started, **attrs)
    try:
        with span as sp:
            yield sp
    finally:
        if started:
            tracer.enabled = was_enabled
            try:
                import jax
                jax.profiler.stop_trace()
            except Exception as e:  # noqa: BLE001
                _trace.instant("profile.stop_failed", error=repr(e))


# the two JAX monitoring events that mark one compilation: a backend compile
# or a load from the persistent compilation cache
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")
_watching = False
_watch_lock = threading.Lock()


def _on_jax_event(event: str, secs: float, **_kw) -> None:
    if event in COMPILE_EVENTS:
        _metrics.default_metrics().counter("jax.compiles").inc()
        _trace.instant("jax.compile", event=event, dur_s=secs)


def watch_compiles() -> None:
    """Register the ``jax.monitoring`` listener behind ``jax.compiles``,
    once per process (JAX keeps listeners for the process's life)."""
    global _watching
    with _watch_lock:
        if _watching:
            return
        import jax
        jax.monitoring.register_event_duration_secs_listener(_on_jax_event)
        _watching = True
