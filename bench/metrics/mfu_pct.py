"""Whole fused step: model flops of the real (unpadded) tokens of the traced
steps over the traced window times the chip's peak FLOP/s, in percent."""
from costs import step_flops


def read(run):
    t = run.trace
    if t is None or t.window_s <= 0:
        return None
    a, b = run.window.trace_span
    flops = sum(step_flops(run.shape, s.seqs) for s in run.window.steps
                if s.t0 >= a and s.t1 <= b)
    return 100.0 * flops / (t.window_s * run.peaks["flops_per_s"])
