"""Padding rows over all rows of the window's prefill groups, in percent:
the scheduler pads each group of one prompt length to the engine batch."""


def read(run):
    pre = [s for s in run.window_steps if s.kind == "prefill"]
    rows = sum(s.rows for s in pre)
    if not rows:
        return None
    real = sum(len(s.real) for s in pre)
    return 100.0 * (rows - real) / rows
