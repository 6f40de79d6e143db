"""Step-cost model: 90th percentile over the window's steps of
|predicted − measured| / measured step time, in percent."""
from stats import percentile


def read(run):
    errs = [100.0 * abs(s.predicted_s - s.exec_s) / s.exec_s
            for s in run.window.steps if s.predicted_s > 0 and s.exec_s > 0]
    return percentile(errs, 90)
