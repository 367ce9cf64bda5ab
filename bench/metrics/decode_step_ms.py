"""Mean decode step time over the window, in milliseconds, from the
engine's own ``serve.decode_step_s`` histogram (host clock around a step
that ends in ``block_until_ready``)."""


def read(run):
    n = run.counters["decode_count"]
    return run.counters["decode_total_s"] / n * 1e3 if n else None
