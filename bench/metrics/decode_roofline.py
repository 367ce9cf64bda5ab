"""Roofline time of the window's decode attention over the device time of
the decode attention kernel in the traced window, in percent.  The compiler
names each Pallas kernel after its graph (``decode_attention_m<M><mode>``);
its device time is read from the trace's busiest operations
(``breakdown.device_ops``, the top 10), where the decode kernel leads in
every cell.  A trace whose kernels are unnamed, or where the kernel falls
out of that list, reads nothing.  Work counts each live lane's context
(``bench.counts``), as ``attn_roofline`` does for both attention kernels
together."""
from bench import counts
from bench.program_spans import is_decode_kernel


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    dev = sum(s for op, s in run.trace["device_ops"] if is_decode_kernel(op))
    if dev <= 0:
        return None
    pk = run.peaks
    t = sum(counts.attention_roofline_s(run.model, s.attn, pk["bf16_flops"],
                                        pk["hbm_bytes_s"])
            for s in run.window_steps
            if s.kind == "decode" and s.attn and s.attn["calls"])
    return 100.0 * t / dev if t else None
