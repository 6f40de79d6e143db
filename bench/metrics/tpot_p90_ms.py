"""90th percentile of (last − first token) / (tokens − 1) over requests
with two tokens or more; unfinished ones run to the window's close."""
from stats import percentile, tpots


def read(run):
    w = run.window
    p = percentile(tpots(w.served, w.close), 90)
    return None if p is None else 1000.0 * p
