"""Mean prefill call time over the window, in milliseconds, from the
engine's ``StepTimer`` prefill phase (warm calls only)."""


def read(run):
    n = run.counters["prefill_count"]
    return run.counters["prefill_total_s"] / n * 1e3 if n else None
