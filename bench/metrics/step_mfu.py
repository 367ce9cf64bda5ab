"""Model operations the window's steps needed, over their summed host-clock
time times the chip's peak bf16 FLOP/s, in percent.  Real prompt tokens and
live lanes only; the head counts once per sampled token (``bench.counts``)."""


def read(run):
    if run.peaks is None:
        return None
    steps = [s for s in run.window_steps if s.work]
    t = sum(s.end - s.start for s in steps)
    f = sum(s.work["flops"] for s in steps)
    return 100.0 * f / (t * run.peaks["bf16_flops"]) if t and f else None
