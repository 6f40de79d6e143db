"""The readers of the program's step records (``host_gap_ms``,
``stage_ms``, ``decode_device_ms``, ``control_ms``) on the toy cell, run
traced on the CPU (after an untraced run that leaves its compile keys for
warm-up) in a checkout built as ``test_cell_by_name.py`` builds
it, with the toy cell added to the metrics' ``workloads``; and on a program
without the records, they give nothing and raise nothing."""
import json
import math
import sys
import types

import pytest
from test_cell_by_name import checkout, run_toy  # noqa: F401  (fixture)

NEW = ("host_gap_ms", "stage_ms", "decode_device_ms", "control_ms")


@pytest.fixture(scope="module")
def traced(checkout):  # noqa: F811
    spec_file = checkout / "BENCHMARK.json"
    spec = json.loads(spec_file.read_text())
    for m in spec["per_layer"]:
        if m["name"] in NEW + ("decode_step_ms",):
            m["workloads"].append("toy-open")
    spec_file.write_text(json.dumps(spec))
    run_toy(checkout)        # keeps the compile keys it reaches for warm-up
    return run_toy(checkout, trace=True)


def test_step_record_metrics_are_finite(traced):
    got = {k: v["value"] for k, v in traced["metrics"].items()}
    assert set(NEW) | {"decode_step_ms"} <= set(got)
    assert all(math.isfinite(got[k]) for k in NEW)
    assert got["host_gap_ms"] >= 0
    assert 0 < got["stage_ms"] < got["decode_step_ms"]
    assert 0 < got["decode_device_ms"] and 0 < got["control_ms"]
    assert traced["correct"] is True


def test_readers_give_nothing_without_records(monkeypatch):
    import run as bench_run
    from conftest import BENCH

    import repro.engine.trace as trace

    steps = [types.SimpleNamespace(t0=0.0, t1=1.0)] * 3
    run = types.SimpleNamespace(window=types.SimpleNamespace(
        steps=steps, trace_span=None))
    monkeypatch.setattr(trace, "steps", lambda: [])
    for name in NEW:
        assert bench_run.read_metric(BENCH, name, run) is None
    # a program that has no step records at all
    monkeypatch.setitem(sys.modules, "repro.engine.trace", None)
    for name in NEW:
        assert bench_run.read_metric(BENCH, name, run) is None
