"""A cell added as data files only is found by name and runs end to end on
the CPU at a toy size; and a run whose served path is broken underneath
comes out not correct.

Each test builds a checkout in a temporary directory: ``BENCHMARK.json``
with a toy configuration, mix and cell added as entries, and a copy of the
benchmark's directory with the toy's files (and a per-layer metric reader)
added as new files. The harness's look for a chip is skipped; everything
else is the run as the benchmark makes it.
"""
import json
import shutil
import time

import pytest

@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    from conftest import BENCH

    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "toy", "source": "https://example.org/toy",
                            "file": "bench/configs/toy.json", "reduced": [],
                            "why": "toy"})
    spec["workloads"].append({"name": "toy-open", "config": "toy",
                              "traffic": "toy-open", "chips": 1, "why": "toy"})
    for m in spec["end_to_end"]:
        if "workloads" in m:
            m["workloads"].append("toy-open")
    # entries only: readers the benchmark already has, for metrics no
    # accepted cell reports yet
    spec["end_to_end"] += [
        {"name": "ttft_p90_ms", "unit": "ms", "better": "lower",
         "bound": 0.25, "source": "host_clock", "workloads": ["toy-open"]},
        {"name": "out_tok_s", "unit": "tokens/s", "better": "higher",
         "bound": 0.25, "source": "host_clock", "workloads": ["toy-open"]}]
    spec["per_layer"] += [
        {"name": "queue_wait_p90_ms", "unit": "ms", "better": "lower",
         "source": "host_clock", "layer": "engine", "moves": "ttft_p90_ms",
         "workloads": ["toy-open"]},
        # a new metric: this entry and a new reader file
        {"name": "toy_steps", "unit": "steps", "better": "higher",
         "source": "host_clock", "layer": "executor", "moves": "tpot_p90_ms",
         "workloads": ["toy-open"]}]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    conf = json.loads((BENCH / "configs" / "danube-1.8b.json").read_text())
    conf.update(name="toy", hidden_size=64, intermediate_size=128,
                num_attention_heads=4, num_key_value_heads=2,
                num_hidden_layers=2, vocab_size=256, sliding_window=64)
    conf["serving"].update(num_pages=64, page_size=16, max_pages_per_seq=8)
    (root / "bench" / "configs" / "toy.json").write_text(json.dumps(conf))
    (root / "bench" / "traffic" / "toy-open.json").write_text(json.dumps({
        "loop": "open", "base_seed": 0,
        "lengths": {"prompt_avg": 20, "prompt_p90": 40, "output_avg": 5,
                    "output_p90": 9, "max_total": 120},
        "slo": {"ttft_s": 0.5, "tpot_s": 0.05},
        "arrivals": {"rate": 6.0, "burst_factor": 1.8, "burst_frac": 0.35,
                     "burst_sojourn_s": 1.5, "calm_sojourn_s": 4.0}}))
    (root / "bench" / "metrics" / "toy_steps.py").write_text(
        "def read(run):\n    return float(len(run.window.steps))\n")
    peaks = json.loads((BENCH / "peaks.json").read_text())
    peaks["kinds"]["cpu"] = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
    (root / "bench" / "peaks.json").write_text(json.dumps(peaks))
    # the program prices its scheduler's prior from its own table of peaks
    import benchmarks.roofline_report as roofline

    roofline.CHIP_PEAKS.setdefault(
        "cpu", {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11})
    return root


def run_toy(root, trace=False, seed=2**31 + 5):
    import run as bench_run

    return bench_run.run_cell(root, "toy-open", seed, 3.0, trace,
                              require_chip=False, t_start=time.perf_counter())


@pytest.mark.parametrize("trace", [False, True], ids=["end_to_end", "per_layer"])
def test_toy_cell_runs_from_data_files(checkout, trace):
    res = run_toy(checkout, trace)
    assert res["correct"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    want = ({"toy_steps", "queue_wait_p90_ms"} if trace
            else {"ttft_p90_ms", "tpot_p90_ms", "out_tok_s", "setup_s"})
    assert set(res["metrics"]) == want
    assert list(res)[-1] == "check"
    assert res["check"]["mean_sq_gap"]["value"] == 0.0
    assert res["device"]["platform"] == "cpu"


def test_no_chip_means_no_result(checkout):
    import run as bench_run

    with pytest.raises(bench_run.NoChip):
        bench_run.run_cell(checkout, "toy-open", 1, 1.0, False)


def _next_id(emitted, vocab):
    return {rid: (tok + 1) % vocab for rid, tok in emitted.items()}


def _half_left_out(emitted, vocab):
    """Every second sequence of the step gets the first one's token, as if
    its row had been left out of the batch."""
    rids = sorted(emitted)
    return {rid: emitted[rids[0]] if i % 2 else emitted[rid]
            for i, rid in enumerate(rids)}


@pytest.mark.parametrize("fault", [_next_id, _half_left_out],
                         ids=["token_altered", "half_batch_left_out"])
def test_altered_token_is_not_correct(checkout, monkeypatch, fault):
    """Tokens altered where they are produced: the executor's emitted ids."""
    from repro.engine.executor import PagedTransformerExecutor

    real = PagedTransformerExecutor._execute_fused

    def altered(self, plan, requests, now):
        dt, emitted = real(self, plan, requests, now)
        return dt, fault(emitted, self.cfg.vocab)

    monkeypatch.setattr(PagedTransformerExecutor, "_execute_fused", altered)
    res = run_toy(checkout)
    assert res["correct"] is False
    assert res["check"]["mean_sq_gap"]["value"] > \
        res["check"]["mean_sq_gap"]["limit"]


def test_unchanged_cache_is_not_correct(checkout, monkeypatch):
    """A step that returns its state unchanged: K/V never reach the pool."""
    import repro.engine.executor as executor

    monkeypatch.setattr(executor, "scatter_rows",
                        lambda k_pools, v_pools, *a, **kw: (k_pools, v_pools))
    res = run_toy(checkout, seed=3)
    assert res["correct"] is False


def test_benchmark_files_alone_give_no_result(tmp_path):
    """A directory holding only BENCHMARK.json and the benchmark's own
    files has no program to run: the command fails and prints nothing."""
    import subprocess
    import sys

    from conftest import BENCH

    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(BENCH.parent / "BENCHMARK.json", tmp_path)
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "danube-offline",
         "--seed", "1", "--seconds", "51", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert out.stdout == ""
