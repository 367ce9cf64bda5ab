"""Output tokens stamped in the window, over the window's seconds."""


def read(run):
    n = sum(1 for t in run.client.stamps if 0.0 <= t < run.seconds)
    return n / run.seconds if n else None
