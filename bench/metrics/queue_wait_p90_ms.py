"""Engine layer: 90th percentile of due time → the first step that served
the request (unserved ones at their age at close), on the harness clock."""
from stats import percentile, queue_waits


def read(run):
    w = run.window
    p = percentile(queue_waits(w.served, w.close), 90)
    return None if p is None else 1000.0 * p
