"""90th percentile of due time → first token over every request due in the
window; a request without a first token at close counts at its age."""
from stats import percentile, ttfts


def read(run):
    w = run.window
    p = percentile(ttfts(w.served, w.close), 90)
    return None if p is None else 1000.0 * p
