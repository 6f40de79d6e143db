"""Latency arithmetic on the harness's wall-clock stamps.

Every time is in seconds after the window opened, on ``time.perf_counter``.
A request is timed from its due time. At the window's close a request that
has no first token counts at its age then, never at a constant.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Served:
    """One request as the harness saw it."""
    rid: int
    due: float
    prompt: list
    out_len: int
    sent: float | None = None             # when the harness submitted it
    first_launch: float | None = None     # first step that served it began
    stamps: list = dataclasses.field(default_factory=list)  # token times
    tokens: list = dataclasses.field(default_factory=list)  # token ids
    finished: bool = False


def percentile(values, q: float) -> float | None:
    """Linearly interpolated percentile (numpy's default); None if empty."""
    return float(np.percentile(values, q)) if len(values) else None


def ttfts(reqs, close: float) -> list[float]:
    """Due → first token; a request still without one counts at its age."""
    return [(r.stamps[0] if r.stamps else close) - r.due for r in reqs]


def tpots(reqs, close: float) -> list[float]:
    """(last − first) / (tokens − 1) over requests with two tokens or more;
    an unfinished request runs to the close, so a stall at the end shows."""
    out = []
    for r in reqs:
        if len(r.stamps) >= 2:
            end = r.stamps[-1] if r.finished else close
            out.append((end - r.stamps[0]) / (len(r.stamps) - 1))
    return out


def queue_waits(reqs, close: float) -> list[float]:
    """Due → the first step that served it; unserved ones at their age."""
    return [(r.first_launch if r.first_launch is not None else close) - r.due
            for r in reqs]
