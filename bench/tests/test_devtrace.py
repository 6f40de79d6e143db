"""The reduction from a device trace to busy time, op time and idle gaps."""
from pathlib import Path

import pytest

from devtrace import op_family, reduce_events

TESTDATA = Path(__file__).resolve().parents[1] / "testdata"


def test_op_family_strips_numeric_suffixes():
    assert op_family("fusion.123") == "fusion"
    assert op_family("copy.4") == "copy"
    assert op_family("paged_attention_ragged") == "paged_attention_ragged"
    assert op_family("convolution_bitcast_fusion.7") == "convolution_bitcast_fusion"
    hlo = ("%paged_attention_ragged.41 = f32[8,8192,80]{2,1,0:T(8,128)} "
           "custom-call(s32[12,24]{1,0} %copy-done.132), "
           "custom_call_target=\"tpu_custom_call\"")
    assert op_family(hlo) == "paged_attention_ragged"
    assert op_family("%copy.3 = f32[460,8,80,128]{2,1,3,0} copy(%p)") == "copy"


def test_busy_is_the_union_and_gaps_are_labelled():
    ns = 1_000_000          # 1 ms
    ops = {"/device:TPU:0": [("fusion.1", 0, 10 * ns),
                             ("copy.2", 5 * ns, 10 * ns),      # overlaps
                             ("paged_attention_ragged", 40 * ns, 20 * ns),
                             ("fusion.3", 95 * ns, 10 * ns)]}  # clipped
    host = [("bench.schedule", 15 * ns, 10 * ns),
            ("bench.wait", 60 * ns, 35 * ns)]
    r = reduce_events(ops, host, (0, 100 * ns))
    assert r.window_s == pytest.approx(0.1)
    assert r.busy_s == pytest.approx((15 + 20 + 5) * 1e-3)
    assert r.op_s["fusion"] == pytest.approx(15e-3)
    assert r.op_s["copy"] == pytest.approx(10e-3)
    assert r.op_s["paged_attention_ragged"] == pytest.approx(20e-3)
    assert r.idle_gaps[0] == ("wait", pytest.approx(35e-3))
    assert r.idle_gaps[1] == ("schedule", pytest.approx(25e-3))


def test_busy_averages_over_devices():
    ops = {"/device:TPU:0": [("a", 0, 50)], "/device:TPU:1": [("a", 0, 100)]}
    r = reduce_events(ops, [], (0, 100))
    assert r.busy_s == pytest.approx(75e-9)
    assert r.n_devices == 2


def test_recorded_chip_step():
    """One fused step of danube-1.8b recorded on a TPU v5 lite: events as
    the profiler gave them (op names cut to 160 characters)."""
    import json

    rec = json.loads((TESTDATA / "trace_one_step.json").read_text())
    ops = {d: [tuple(e) for e in ev] for d, ev in rec["device_ops"].items()}
    r = reduce_events(ops, [tuple(s) for s in rec["host_spans"]],
                      tuple(rec["window"]))
    assert r.n_devices == 1
    assert r.window_s == pytest.approx(0.116327776)
    assert r.busy_s == pytest.approx(0.107419414)
    assert 0 < r.busy_s <= r.window_s
    assert sum(r.op_s.values()) >= r.busy_s      # ops may overlap
    top = sorted(r.op_s, key=r.op_s.get, reverse=True)[:2]
    assert top == ["copy", "paged_attention_ragged"]
    assert r.op_s["paged_attention_ragged"] == pytest.approx(0.023769301)
    assert all(name == "execute" for name, _ in r.idle_gaps[:2])
