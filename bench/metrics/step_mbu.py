"""Bytes the window's steps needed (weights and head table once per step,
live keys and values read, new ones written), over their summed host-clock
time times the chip's peak HBM bandwidth, in percent."""


def read(run):
    if run.peaks is None:
        return None
    steps = [s for s in run.window_steps if s.work]
    t = sum(s.end - s.start for s in steps)
    b = sum(s.work["bytes"] for s in steps)
    return 100.0 * b / (t * run.peaks["hbm_bytes_s"]) if t and b else None
