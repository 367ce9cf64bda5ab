"""Decoding lanes per decode step, averaged over the window's decode
steps (a count the harness takes from the scheduler's lanes)."""


def read(run):
    lanes = [len(s.real) for s in run.window_steps if s.kind == "decode"]
    return sum(lanes) / len(lanes) if lanes else None
