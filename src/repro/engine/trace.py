"""Spans and one record per dispatch at the layer boundaries of an engine
step (DESIGN.md §12).

``span(name)`` marks one phase of a step. While a profiler runs it enters
``jax.profiler.TraceAnnotation("fb." + name)``, so the trace shows the
phase on its host plane, on the same clock as the device ops; always, it
adds the phase's ``perf_counter_ns`` duration to the open step record.
With no profiler running a span costs about a microsecond of host time, so
spans and records are always on, like ``jax.monitoring``.

A ``Record`` is opened by ``Engine.begin_step`` (``begin``) for the
dispatch it forms, or by the executor's ``execute`` when no engine called
it. The process keeps the newest ``LOG_SIZE`` records in one log, read
with ``steps()`` after the engine is gone.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Optional

from jax.profiler import TraceAnnotation

from ..core.types import TaskKind

PREFIX = "fb."
LOG_SIZE = 16384
# the measured host phases of a step: engine and scheduler, input staging
HOST = frozenset(("admit", "project", "schedule", "horizon", "complete",
                  "extend", "stage", "h2d"))


@dataclasses.dataclass(slots=True, eq=False)
class Record:
    """One executor dispatch. Stamps are ``time.perf_counter_ns``."""
    rank: Optional[int] = None          # the engine's rank; None when bare
    phases: dict = dataclasses.field(default_factory=dict)  # span -> ns
    t_open: int = 0                     # the opening span's start ...
    t_close: int = 0                    # ... and end
    t_dispatch: Optional[int] = None    # the first ``dispatch`` span's start
    t_synced: Optional[int] = None      # the last ``sync`` span's end
    seqs: int = 0                       # sequences served
    tokens: int = 0                     # tokens the plan grants them
    prefill_tokens: int = 0
    decode_items: int = 0
    deferred: int = 0                   # items the executor deferred
    buckets: Optional[tuple] = None     # fused compile key (t, s, tq, pg)
    new_key: bool = False               # the dispatch grew the compile keys
    predicted_s: Optional[float] = None  # the plan's predicted step time
    calibrated: Optional[bool] = None   # the scheduler's online fit in use
    host_ns: int = 0                    # the ``HOST`` phases summed

    def add(self, name: str, t0: int, t1: int) -> None:
        d = t1 - t0
        self.phases[name] = self.phases.get(name, 0) + d
        if name in HOST:
            self.host_ns += d
        elif name == "dispatch":
            if self.t_dispatch is None:
                self.t_dispatch = t0
        elif name == "sync":
            self.t_synced = t1

    def count(self, plan, deferred) -> None:
        """The plan's items, as served or deferred."""
        n_def = n_dec = pre = 0
        for it in plan.items:
            if it.req_id in deferred:
                n_def += 1
            elif it.kind is TaskKind.PREFILL:
                pre += it.n_tokens
            else:
                n_dec += 1
        self.deferred, self.decode_items, self.prefill_tokens = n_def, n_dec, pre
        self.seqs = len(plan.items) - n_def
        self.tokens = pre + n_dec


_LOG: collections.deque = collections.deque(maxlen=LOG_SIZE)
_open: Optional[Record] = None


def steps() -> list:
    """The logged records, oldest first."""
    return list(_LOG)


def log(rec: Record) -> None:
    _LOG.append(rec)


def current() -> Optional[Record]:
    """The record the phases now running add to (None outside a step)."""
    return _open


_now_ns = time.perf_counter_ns
_profiling = TraceAnnotation.is_enabled


class span:
    """Phase ``name`` of ``rec``, or of the record open when it starts. The
    annotation is made only while a profiler runs. A span may be entered
    again once it has exited, so callers keep one per phase; it is never
    entered while open (phases of one name do not nest)."""
    __slots__ = ("name", "rec", "_rec", "_ann", "_t0")

    def __init__(self, name: str, rec: Optional[Record] = None):
        self.name = name
        self.rec = rec

    def __enter__(self) -> Optional[Record]:
        rec = self._rec = _open if self.rec is None else self.rec
        self._ann = None
        if _profiling():
            self._ann = TraceAnnotation(PREFIX + self.name)
            self._ann.__enter__()
        self._t0 = _now_ns()
        return rec

    def __exit__(self, *exc) -> None:
        t1 = _now_ns()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        if self._rec is not None:
            self._rec.add(self.name, self._t0, t1)


class _Opening:
    """``span(name)`` that opens ``rec`` for the phases inside it, and logs
    it first when ``log_it``."""
    __slots__ = ("_span", "_prev", "_log")

    def __init__(self, name: str, rec: Record, log_it: bool):
        self._span = span(name, rec)
        self._log = log_it

    def __enter__(self) -> Record:
        global _open
        rec = self._span.rec
        self._prev, _open = _open, rec
        if self._log:
            _LOG.append(rec)
        self._span.__enter__()
        rec.t_open = self._span._t0
        return rec

    def __exit__(self, *exc) -> None:
        global _open
        self._span.__exit__(*exc)
        self._span.rec.t_close = _now_ns()
        _open = self._prev


_EXECUTE = span("execute")


def begin(rank: int) -> _Opening:
    """``fb.begin``: opens the record of the dispatch the engine forms; the
    engine logs it (``log``) once it dispatches."""
    return _Opening("begin", Record(rank=rank), log_it=False)


def execute():
    """``fb.execute``: joins the open record, or opens and logs one when no
    engine called the executor."""
    if _open is not None:
        return _EXECUTE
    return _Opening("execute", Record(), log_it=True)
