"""Executor, host side: input staging per step, from the program's step
records: the ``extend`` (KV pages), ``stage`` (packing the staging buffers)
and ``h2d`` (host→device copies) spans, mean over the window's steps."""
from steprec import mean, phase_ms, window_records


def read(run):
    recs = window_records(run)
    if recs is None:
        return None
    parts = {p: mean(phase_ms(r, p) for r in recs)
             for p in ("extend", "stage", "h2d")}
    print("stage_ms: " + ", ".join(f"{p} {v:.4f} ms" for p, v in parts.items()),
          flush=True)
    return sum(parts.values())
