"""Roofline time of the window's attention work over the device time of all
``tpu_custom_call`` events in the traced window, in percent.  Only the
attention kernels are Pallas calls on this path.  Work counts real rows and
each lane's live context (``bench.counts``); the roofline of each per-layer
kernel call is the larger of its operations over peak bf16 FLOP/s and its
bytes over peak HBM bandwidth."""
from bench import counts


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    dev = run.trace["custom_call_s"]
    if dev <= 0:
        return None
    pk = run.peaks
    t = sum(counts.attention_roofline_s(run.model, s.attn, pk["bf16_flops"],
                                        pk["hbm_bytes_s"])
            for s in run.window_steps if s.attn and s.attn["calls"])
    return 100.0 * t / dev if t else None
