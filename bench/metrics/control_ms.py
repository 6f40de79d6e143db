"""Engine and scheduler: control-plane host time per step, from the
program's step records: the ``admit``, ``project``, ``schedule``,
``horizon`` and ``complete`` spans, mean over the window's steps. The
``schedule`` part, the program's own counterpart of ``sched_ms``, is
printed beside it."""
from steprec import mean, phase_ms, window_records

PARTS = ("admit", "project", "schedule", "horizon", "complete")


def read(run):
    recs = window_records(run)
    if recs is None:
        return None
    parts = {p: mean(phase_ms(r, p) for r in recs) for p in PARTS}
    print("control_ms: " + ", ".join(f"{p} {v:.4f} ms"
                                     for p, v in parts.items()), flush=True)
    return sum(parts.values())
