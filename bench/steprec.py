"""The program's own step records of the measured window.

The program keeps one record per executor dispatch in a process-wide log,
``repro.engine.trace.steps()``: the durations of its phase spans (``fb.*``)
and the ``perf_counter_ns`` stamps of its dispatch's start and its sync's
end. The harness wraps each ``execute`` call once and nothing runs the
executor after the window, so the window's records are the last
``len(run.window.steps)`` of the log, in the order of ``run.window.steps``.
A program without the log, or a log holding fewer records, gives None.
"""
from __future__ import annotations


def window_records(run) -> list | None:
    try:
        from repro.engine.trace import steps
    except ImportError:
        return None
    n = len(run.window.steps)
    recs = steps()
    if n == 0 or len(recs) < n:
        return None
    return recs[-n:]


def phase_ms(rec, *names: str) -> float:
    return sum(rec.phases.get(p, 0) for p in names) / 1e6


def mean(xs) -> float | None:
    xs = list(xs)
    return sum(xs) / len(xs) if xs else None
