"""95th percentile of all gaps between consecutive output tokens of the
same request, both tokens stamped in the window, in milliseconds."""
import numpy as np


def read(run):
    gaps = [b - a for a, b in run.client.gaps
            if 0.0 <= a and b < run.seconds]
    if not gaps:
        return None
    return float(np.percentile(gaps, 95)) * 1e3
