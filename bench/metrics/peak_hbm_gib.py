"""Peak device memory in use, ``memory_stats()['peak_bytes_in_use']`` read
after the window, in GiB."""


def read(run):
    b = run.memory_peak_bytes
    return b / 2**30 if b else None
