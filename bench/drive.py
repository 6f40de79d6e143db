"""Drive the system under test on the wall clock.

The harness builds the program's own serving path (``Engine`` →
``make_scheduler("fairbatching")`` → ``PagedTransformerExecutor`` in fused
mode), warms it by compile key, and then serves a mix for a fixed window:

- it submits each request when its due time passes on
  ``time.perf_counter``, and times it from that due time;
- it launches one step at a time (``begin_step`` at the current wall time,
  then ``complete_step``) and stamps every emitted token when its step has
  returned, so every latency is host wall time, not the engine's clock;
- it times its calls into the scheduler and the executor, and, in a traced
  run, marks them as profiler spans (``bench.*``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import json
import time
from pathlib import Path

import numpy as np

from lm import Shape
from stats import Served
from traffic import Job


@dataclasses.dataclass
class Step:
    t0: float                  # harness clock when the executor was called
    t1: float                  # ... and when it returned (device synced)
    exec_s: float              # the executor's own measure of the step
    predicted_s: float         # the scheduler's cost-model prediction
    seqs: list                 # [(pos0, n, ctx)] of the executed sequences
    decode_only: bool


@dataclasses.dataclass
class Window:
    """What one measured window produced."""
    seconds: float
    close: float = 0.0
    served: list = dataclasses.field(default_factory=list)   # due in window
    steps: list = dataclasses.field(default_factory=list)
    sched_s: float = 0.0
    n_sched: int = 0
    waiting_mid: int | None = None
    waiting_close: int = 0
    failed: int = 0
    trace_span: tuple | None = None   # harness-clock (start, end) traced
    new_shapes: list = dataclasses.field(default_factory=list)  # new keys
    keys: set = dataclasses.field(default_factory=set)   # keys it reached
    programs_in_window: int = 0
    gc_s: float = 0.0          # the garbage collector's pauses in the window
    gc_n: int = 0


class ProgramCounter:
    """Programs obtained by XLA (backend compiles and persistent-cache
    loads), counted through JAX's monitoring events. One per process:
    ``ProgramCounter.get()``."""

    _one = None

    @classmethod
    def get(cls) -> "ProgramCounter":
        if cls._one is None:
            cls._one = cls()
        return cls._one

    def __init__(self):
        import jax.monitoring as mon

        self.compiles = self.cache_hits = 0
        self.compile_s = 0.0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    @property
    def programs(self) -> int:
        return self.compiles + self.cache_hits


# ---------------------------------------------------------------------------
# building the system under test
# ---------------------------------------------------------------------------

def arch_config(conf: dict, s: Shape):
    from repro.configs.base import ArchConfig

    return ArchConfig(name=conf["name"], family="dense", n_layers=s.n_layers,
                      d_model=s.d_model, n_heads=s.n_heads,
                      n_kv_heads=s.n_kv_heads, d_ff=s.d_ff, vocab=s.vocab,
                      head_dim=s.head_dim, rope_theta=s.rope_theta,
                      window=s.window, norm_eps=s.norm_eps,
                      source=conf["source"])


def build_executor(conf: dict, s: Shape, weights):
    """The program's paged executor in fused mode, with its own defaults for
    everything the configuration file does not size."""
    from repro.engine import PagedTransformerExecutor

    srv = conf["serving"]
    return PagedTransformerExecutor(
        arch_config(conf, s), weights, num_pages=srv["num_pages"],
        page_size=srv["page_size"], max_pages_per_seq=srv["max_pages_per_seq"],
        mode="fused", kv_dtype=srv["kv_dtype"])


def build_engine(conf: dict, s: Shape, ex, mix: dict):
    """A fresh engine and FairBatching scheduler over ``ex``. The scheduler's
    step-cost prior is the one the program serves the chip with
    (``chip_smoke.cost_prior``); the engine recalibrates it online."""
    from chip_smoke import cost_prior
    from repro.core import make_scheduler
    from repro.engine import Engine, EngineConfig

    ecfg = EngineConfig(ttft_slo=mix["slo"]["ttft_s"],
                        tpot_slo=mix["slo"]["tpot_s"],
                        preemption=bool(conf["serving"]["preemption"]))
    return Engine(make_scheduler("fairbatching", cost_prior(ex.cfg)), ex, ecfg)


# ---------------------------------------------------------------------------
# warm-up by compile key
# ---------------------------------------------------------------------------

WARM_RID = 1 << 40      # request ids of warm-up's requests; no job has one


def step_shape(seqs) -> tuple:
    """What a fused step's compile key is made from, as plain counts: its
    tokens, its sequences, its longest chunk and its widest context."""
    return (sum(n for _, n, _ in seqs), len(seqs),
            max(n for _, n, _ in seqs), max(c for _, _, c in seqs))


def warm_step(ex, shape) -> None:
    """Run one fused step of ``shape`` through the executor's own
    ``execute``, as the engine does: every sequence a prefill chunk that
    completes its prompt (so the step's tokens are read back), the first
    with the longest chunk after a leading context as wide as the step's
    widest, on pages handed back afterwards."""
    from repro.core.types import BatchItem, BatchPlan, TaskKind
    from repro.engine import Request

    n_tok, n, m, ctx = (int(x) for x in shape)
    rest = n_tok - m
    chunks = [m] + [rest // (n - 1) + (i < rest % (n - 1))
                    for i in range(n - 1)] if n > 1 else [m]
    reqs, items = {}, []
    for i, c in enumerate(chunks):
        rid, lead = WARM_RID + i, (ctx - m if i == 0 else 0)
        reqs[rid] = Request(rid, arrival=0.0, prompt_len=lead + c,
                            max_new_tokens=1, ttft_slo=1.0, tpot_slo=1.0,
                            prefilled=lead, tokens=[0] * (lead + c))
        if lead:
            ex.alloc.extend(rid, lead)
        items.append(BatchItem(rid, c, TaskKind.PREFILL))
    plan = BatchPlan(items=items, predicted_time=0.0, time_budget=0.0,
                     token_budget_used=0, token_budget_total=0)
    try:
        ex.execute(plan, reqs, 0.0)
        if ex.last_deferred:
            raise RuntimeError(f"warm-up step {shape} found no free pages")
    finally:
        for rid in reqs:
            ex.release(rid)


def read_shapes(*paths: Path) -> list[tuple]:
    shapes = set()
    for p in paths:
        if p.is_file():
            shapes.update(tuple(k) for k in json.loads(p.read_text())["steps"])
    return sorted(shapes)


def save_shapes(path: Path, shapes) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps({"steps": sorted(list(k) for k in shapes)})
                    + "\n")


# ---------------------------------------------------------------------------
# the measured window
# ---------------------------------------------------------------------------

def _span(trace_on: bool, name: str):
    if not trace_on:
        return contextlib.nullcontext()
    import jax.profiler

    return jax.profiler.TraceAnnotation("bench." + name)


def instrument(eng, ex, win: Window, clock, traced) -> frozenset:
    """Time the calls into the scheduler and the executor (harness spans).
    The executor's own ``execute`` is wrapped afresh for every window.

    The executor adds every step's compile key to ``ex.compile_keys``; the
    set is emptied here so that it collects the window's keys, and the keys
    obtained before (returned) are added back when the window closes."""
    run_exec, run_sched = type(ex).execute.__get__(ex), eng.sched.schedule
    before = frozenset(ex.compile_keys)
    ex.compile_keys.clear()

    def execute(plan, requests, now):
        seqs = [(it.req_id, requests[it.req_id].prefilled, it.n_tokens)
                for it in plan.prefill_items]
        seqs += [(it.req_id, requests[it.req_id].context - 1, 1)
                 for it in plan.decode_items]
        n_keys = len(ex.compile_keys)
        t0 = clock()
        with _span(traced(), "execute"):
            dt, emitted = run_exec(plan, requests, now)
        t1 = clock()
        skip = ex.last_deferred
        step = Step(t0, t1, dt, plan.predicted_time,
                    [(p0, n, p0 + n) for rid, p0, n in seqs if rid not in skip],
                    decode_only=not plan.prefill_items)
        win.steps.append(step)
        if len(ex.compile_keys) > n_keys:     # the window's first step of a key
            if not ex.compile_keys - win.keys <= before:
                win.new_shapes.append(step_shape(step.seqs))
            win.keys |= ex.compile_keys
        return dt, emitted

    def schedule(now, tasks):
        t0 = time.perf_counter()
        with _span(traced(), "schedule"):
            plan = run_sched(now, tasks)
        win.sched_s += time.perf_counter() - t0
        win.n_sched += 1
        return plan

    ex.execute = execute
    eng.sched.schedule = schedule
    return before


def serve_window(eng, ex, jobs: list[Job], mix: dict, seconds: float,
                 window_attn: int | None, counter: ProgramCounter,
                 trace_dir: str | None = None) -> Window:
    """Serve ``jobs`` for ``seconds`` of wall time and return the record.
    With ``trace_dir`` the middle half of the window is traced. A step that
    reaches a compile key no earlier step (warm-up's included) reached is
    recorded by its shape; ``keys`` are all the compile keys the window's
    steps reached."""
    import jax

    from repro.engine import Request
    from repro.engine.request import RequestState

    win = Window(seconds)
    tracing = [False]
    t_w0 = time.perf_counter()

    def clock() -> float:
        return time.perf_counter() - t_w0

    before = instrument(eng, ex, win, clock, lambda: tracing[0])
    gc_t0 = [0.0]

    def on_gc(phase, info):
        if phase == "start":
            gc_t0[0] = time.perf_counter()
        else:
            win.gc_s += time.perf_counter() - gc_t0[0]
            win.gc_n += 1

    gc.callbacks.append(on_gc)
    programs0 = counter.programs
    served: dict[int, Served] = {}
    slo = mix["slo"]

    def submit(job: Job, due: float) -> None:
        eng.submit(Request(job.rid, arrival=due, prompt_len=len(job.prompt),
                           max_new_tokens=job.out_len,
                           ttft_slo=slo["ttft_s"], tpot_slo=slo["tpot_s"],
                           tokens=list(job.prompt), window=window_attn))
        served[job.rid] = Served(job.rid, due, job.prompt, job.out_len,
                                 sent=clock())

    open_loop = mix["loop"] == "open"
    client_of = {j.rid: j.client for j in jobs}
    if open_loop:
        queue = sorted(jobs, key=lambda j: j.due)
    else:
        by_client: dict[int, list] = {}
        for j in jobs:
            by_client.setdefault(j.client, []).append(j)
        queue = []
        for c in sorted(by_client):
            submit(by_client[c].pop(0), 0.0)
    nxt = 0
    trace_at = (seconds / 4, 3 * seconds / 4) if trace_dir else None
    annotation = None
    while True:
        now = clock()
        if now >= seconds:
            break
        if trace_at and not tracing[0] and win.trace_span is None \
                and now >= trace_at[0]:
            jax.profiler.start_trace(trace_dir)
            annotation = jax.profiler.TraceAnnotation("bench.window")
            annotation.__enter__()
            tracing[0], trace_t0 = True, clock()
        elif tracing[0] and now >= trace_at[1]:
            annotation.__exit__(None, None, None)
            win.trace_span = (trace_t0, clock())
            jax.profiler.stop_trace()
            tracing[0] = False
        if win.waiting_mid is None and now >= seconds / 2:
            win.waiting_mid = sum(not sv.stamps for sv in served.values())
        while open_loop and nxt < len(queue) and queue[nxt].due <= now:
            submit(queue[nxt], queue[nxt].due)
            nxt += 1
        if not (eng.active or eng.pending):
            wake = queue[nxt].due if open_loop and nxt < len(queue) else seconds
            with _span(tracing[0], "wait"):
                time.sleep(max(0.0, min(wake, seconds) - clock()))
            continue
        t_b = clock()
        inf = eng.begin_step(now=t_b)
        if inf is None:
            with _span(tracing[0], "wait"):
                time.sleep(0.001)
            continue
        with _span(tracing[0], "complete"):
            eng.complete_step()
        t_e = clock()
        for it in inf.plan.items:
            if it.req_id in inf.deferred:
                continue
            sv = served[it.req_id]
            if sv.first_launch is None:
                sv.first_launch = t_b
            req = eng.requests[it.req_id]
            sv.stamps += [t_e] * (len(req.generated_tokens) - len(sv.stamps))
            if req.state is RequestState.FINISHED and not sv.finished:
                sv.finished = True
                sv.tokens = list(req.generated_tokens)
                if not open_loop and by_client[client_of[sv.rid]]:
                    submit(by_client[client_of[sv.rid]].pop(0), t_e)
    if tracing[0]:
        annotation.__exit__(None, None, None)
        win.trace_span = (trace_t0, clock())
        jax.profiler.stop_trace()
    win.close = clock()
    gc.callbacks.remove(on_gc)
    for job in queue[nxt:]:       # due in the window, not yet sent at close
        if job.due < seconds:
            served[job.rid] = Served(job.rid, job.due, job.prompt, job.out_len)
    if win.waiting_mid is None:
        win.waiting_mid = sum(not sv.stamps for sv in served.values())
    win.waiting_close = sum(not sv.stamps for sv in served.values())
    for sv in served.values():
        req = eng.requests.get(sv.rid)
        if req is not None and not sv.finished:
            sv.tokens = list(req.generated_tokens)
        if req is not None and req.state in (RequestState.REJECTED,
                                             RequestState.SHED):
            win.failed += 1
    win.served = sorted(served.values(), key=lambda sv: sv.due)
    win.programs_in_window = counter.programs - programs0
    ex.compile_keys |= before
    return win

