"""Share of the traced window in which no operation ran on the chip, in
percent: 1 - union of device op intervals / window."""


def read(run):
    if run.trace is None:
        return None
    tr = run.trace
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
