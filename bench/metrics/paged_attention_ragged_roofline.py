"""Kernel ``paged_attention_ragged``: its least time on the chip (the larger
of its operations over peak FLOP/s and its bytes over peak HBM bandwidth,
counted from the real tokens and contexts of the traced steps at the pool's
stored width) over its time in the device trace, in percent. Which peak
bounds it is printed beside."""
from costs import attention_bytes, attention_flops, roofline_seconds

KERNEL = "paged_attention_ragged"


def read(run):
    t = run.trace
    if t is None or t.op_s.get(KERNEL, 0.0) <= 0:
        return None
    a, b = run.window.trace_span
    seqs = [q for s in run.window.steps if s.t0 >= a and s.t1 <= b
            for q in s.seqs]
    flops = attention_flops(run.shape, seqs)
    nbytes = attention_bytes(run.shape, seqs, kv_bytes=run.kv_bytes)
    least, bound = roofline_seconds(flops, nbytes, run.peaks)
    print(f"{KERNEL}: {flops:.6g} flops, {nbytes:.6g} bytes, {bound}-bound, "
          f"{t.op_s[KERNEL]:.6g} s in the trace", flush=True)
    return 100.0 * least / t.op_s[KERNEL]
