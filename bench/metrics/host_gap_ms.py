"""Engine and executor, host side: the host time during which the chip
waits between two steps. From the program's step records, the mean over
consecutive pairs of window steps that both reached the chip of this step's
``dispatch`` start − the previous step's ``sync`` end. A pair across which
the harness started or stopped the profiler is left out: that time is the
harness's.

Printed beside it, the mean of each span inside that interval (the previous
step's ``complete``; this step's engine phases and staging), the rest of
the ``fb.begin`` and ``fb.execute`` spans there, and the part no ``fb.*``
span covers (the caller's own time)."""
from steprec import mean, phase_ms, window_records

INSIDE = ("admit", "project", "schedule", "horizon", "extend", "stage", "h2d")


def read(run):
    recs = window_records(run)
    if recs is None:
        return None
    steps, cut = run.window.steps, run.window.trace_span or ()
    rows = []
    for i in range(1, len(recs)):
        prev, cur = recs[i - 1], recs[i]
        if prev.t_synced is None or cur.t_dispatch is None:
            continue
        if any(steps[i - 1].t1 <= t <= steps[i].t0 for t in cut):
            continue
        gap = (cur.t_dispatch - prev.t_synced) / 1e6
        spans = {p: phase_ms(cur, p) for p in INSIDE}
        spans["complete"] = phase_ms(prev, "complete")
        # fb.begin/fb.execute inside the gap: the previous step's tail after
        # its sync, and this step's head before its dispatch
        fb = ((prev.t_close - prev.t_synced) + (cur.t_dispatch - cur.t_open)
              ) / 1e6 + spans["complete"]
        spans["other fb"] = fb - sum(spans.values())
        spans["caller"] = gap - fb
        rows.append((gap, spans))
    if not rows:
        return None
    names = list(rows[0][1])
    print("host_gap_ms: " + ", ".join(
        f"{p} {mean(s[p] for _, s in rows):.4f} ms" for p in names)
        + f" over {len(rows)} pairs", flush=True)
    return mean(g for g, _ in rows)
