"""Spans and step records inside the serving path (engine/trace.py).

The small preset's real fused executor under an ``Engine`` on the CPU: one
record per dispatch, with the engine's phases around the executor's, the
executor's phases in order inside ``fb.execute`` and covering its measured
step time, ``new_key`` on the dispatches that grew the compile keys, the
spans on the profiler's host plane, and a traced run deciding and emitting
exactly what an untraced one does.
"""
import dataclasses
import glob

import jax
import pytest

from repro.configs import get_reduced
from repro.core import LinearCostModel, make_scheduler
from repro.core.types import BatchItem, BatchPlan, TaskKind
from repro.engine import (BlockAllocator, Engine, EngineConfig,
                          PagedTransformerExecutor, Request, SimExecutor)
from repro.engine import trace
from repro.engine.numerics import (ModelTimedExecutor, assert_same_decisions,
                                   capture_schedule)
from repro.models import ModelOpts, build_model

PAGE, NUM_PAGES, MAX_PAGES = 16, 64, 8
ENGINE = ("begin", "admit", "project", "schedule", "horizon", "complete")
EXECUTOR = ("extend", "stage", "h2d", "dispatch", "sync")


@pytest.fixture(scope="module")
def execu():
    cfg = dataclasses.replace(get_reduced("stablelm-3b"), window=None)
    params = build_model(cfg, ModelOpts(attn_impl="dense")).init(
        jax.random.PRNGKey(0))
    return PagedTransformerExecutor(cfg, params, num_pages=NUM_PAGES,
                                    page_size=PAGE,
                                    max_pages_per_seq=MAX_PAGES)


def _reset(ex) -> None:
    ex.alloc = BlockAllocator(NUM_PAGES, PAGE)
    assert ex.alloc.extend(-1, PAGE) == [0]        # trash page
    ex.last_deferred = frozenset()
    ex.n_dispatches = 0
    ex.compile_keys = set()


def _engine(ex, n=4, new_tokens=12):
    sched = make_scheduler("fairbatching",
                           LinearCostModel(a=1e-4, b=1e-6, c=1e-10))
    eng = Engine(sched, ex, EngineConfig(ttft_slo=5.0, tpot_slo=5.0))
    for i in range(n):
        plen = 5 + 9 * i
        eng.submit(Request(i, arrival=0.001 * i, prompt_len=plen,
                           max_new_tokens=new_tokens, ttft_slo=5.0,
                           tpot_slo=5.0,
                           tokens=[(7 * i + j) % 97 for j in range(plen)]))
    return eng


def _logged_since(mark):
    recs = trace.steps()
    if mark is None:
        return recs
    last = max(i for i, r in enumerate(recs) if r is mark)
    return recs[last + 1:]


def _mark():
    recs = trace.steps()
    return recs[-1] if recs else None


@pytest.fixture(scope="module")
def served(execu):
    """One engine run from empty compile keys; for each dispatch, whether
    it grew ``compile_keys``."""
    _reset(execu)
    eng = _engine(execu, new_tokens=40)
    grew, real = [], execu.execute

    def execute(plan, requests, now):
        n = len(execu.compile_keys)
        out = real(plan, requests, now)
        grew.append(len(execu.compile_keys) > n)
        return out

    mark = _mark()
    execu.execute = execute
    try:
        eng.run()
    finally:
        del execu.execute
    return eng, _logged_since(mark), grew


def test_one_record_per_dispatch_with_engine_phases(served):
    eng, recs, _ = served
    assert len(eng.done) == 4
    assert len(recs) == eng.n_dispatches == len(eng.steps) > 33
    for rec, st in zip(recs, eng.steps):
        assert rec.rank == eng.rank
        assert set(ENGINE) <= set(rec.phases)
        assert rec.predicted_s == st.predicted
        assert rec.tokens == st.new_tokens
        assert rec.seqs == st.n_prefill + st.n_decode
        assert rec.t_open < rec.t_dispatch < rec.t_synced < rec.t_close
    assert trace.current() is None
    # the prior until the online fit has seen 32 steps, the fit after
    assert [r.calibrated for r in recs] == [i >= 32 for i in range(len(recs))]
    host = eng.host_stats()["host_measured_s"]
    assert host == pytest.approx(sum(r.host_ns for r in recs) / 1e9)
    assert 0 < host < sum(st.t_end - st.t_start for st in eng.steps)


def test_executor_phases_cover_its_step_time(served):
    """Per step the phases lie inside ``fb.execute``; over the run they
    cover 90% of the executor's measured time (summed, so that a collector
    pause between two phases of one step does not decide it)."""
    eng, recs, _ = served
    covered = [sum(r.phases.get(p, 0) for p in EXECUTOR) / 1e9 for r in recs]
    for c, rec in zip(covered, recs):
        assert c <= rec.phases["execute"] / 1e9
    assert sum(covered) >= 0.9 * sum(st.t_end - st.t_start
                                     for st in eng.steps)


def test_new_key_marks_dispatches_that_grew_compile_keys(served):
    _, recs, grew = served
    assert [r.new_key for r in recs] == grew
    assert any(grew) and not all(grew)
    assert {("fused",) + r.buckets for r in recs} == served[0].executor \
        .compile_keys


def test_bare_execute_records_no_engine_phases(execu):
    _reset(execu)
    rid = 1 << 40
    reqs = {rid: Request(rid, arrival=0.0, prompt_len=9, max_new_tokens=1,
                         ttft_slo=1.0, tpot_slo=1.0, tokens=list(range(9)))}
    plan = BatchPlan(items=[BatchItem(rid, 9, TaskKind.PREFILL)],
                     predicted_time=0.0, time_budget=0.0,
                     token_budget_used=0, token_budget_total=0)
    mark = _mark()
    dt, emitted = execu.execute(plan, reqs, 0.0)
    (rec,) = _logged_since(mark)
    assert rid in emitted and trace.current() is None
    assert rec.rank is None and rec.predicted_s is None
    assert set(rec.phases) == {"execute"} | set(EXECUTOR)
    assert (rec.seqs, rec.tokens, rec.prefill_tokens) == (1, 9, 9)
    covered = sum(rec.phases[p] for p in EXECUTOR)
    assert covered <= dt * 1e9 <= rec.phases["execute"]


def test_simulated_engine_records_without_measured_host_time():
    model = LinearCostModel(a=1e-3, b=1e-5, c=1e-8)
    eng = Engine(make_scheduler("fairbatching", model), SimExecutor(model),
                 EngineConfig())
    for i in range(3):
        eng.submit(Request(i, arrival=0.0, prompt_len=20, max_new_tokens=4,
                           ttft_slo=1.0, tpot_slo=1.0))
    mark = _mark()
    eng.run()
    recs = _logged_since(mark)
    assert len(recs) == eng.n_dispatches > 0
    assert all(set(ENGINE) == set(r.phases) for r in recs)
    assert "host_measured_s" not in eng.host_stats()


def test_log_drops_its_oldest_record_at_its_bound():
    first = trace.Record()
    trace.log(first)
    for _ in range(trace.LOG_SIZE - 1):
        trace.log(trace.Record())
    assert trace.steps()[0] is first
    trace.log(trace.Record())
    recs = trace.steps()
    assert len(recs) == trace.LOG_SIZE >= 16384
    assert all(r is not first for r in recs)


def _host_events(path):
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                        for ev in line.events
                        if ev.name.startswith(trace.PREFIX)]
    return out


def test_profiler_shows_executor_phases_in_order_inside_execute(execu,
                                                                tmp_path):
    _reset(execu)
    eng = _engine(execu, n=2, new_tokens=4)
    eng.step()                      # compiles outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.step()
        eng.step()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    events = _host_events(path)
    names = {n for n, _, _ in events}
    assert {"fb.stage", "fb.dispatch", "fb.sync"} <= names
    executes = [e for e in events if e[0] == "fb.execute"]
    assert len(executes) == 2
    for _, s, e in executes:
        inside = sorted((s0, n) for n, s0, e0 in events
                        if s <= s0 and e0 <= e and n != "fb.execute")
        assert [n for _, n in inside] == [trace.PREFIX + p for p in EXECUTOR]


def test_traced_run_decides_and_emits_as_untraced(execu, tmp_path):
    def run(traced):
        _reset(execu)
        eng = _engine(execu)
        eng.executor = ModelTimedExecutor(execu)
        decisions = capture_schedule(eng)
        if traced:
            jax.profiler.start_trace(str(tmp_path))
        try:
            eng.run()
        finally:
            if traced:
                jax.profiler.stop_trace()
        return decisions, {r: list(q.generated_tokens)
                           for r, q in eng.requests.items()}

    traced, tok_t = run(True)
    plain, tok_p = run(False)
    assert glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    assert tok_t == tok_p and all(len(t) == 12 for t in tok_t.values())
    assert_same_decisions(traced, plain, "traced vs untraced")
