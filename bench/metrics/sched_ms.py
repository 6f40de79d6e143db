"""Scheduler layer: wall time in batch formation per step, the harness's
span around every ``schedule`` call summed over the window, over calls."""


def read(run):
    w = run.window
    return 1000.0 * w.sched_s / w.n_sched if w.n_sched else None
