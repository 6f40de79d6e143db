"""Run one benchmark cell once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name: the cell in ``BENCHMARK.json``,
its configuration file, ``bench/traffic/<traffic>.json``,
``bench/warm/<cell>.json`` (one step per compile key its traffic reaches)
and one reader per metric, ``bench/metrics/<metric>.py``. With ``--trace 0`` the
cell's end-to-end metrics are printed, with ``--trace 1`` its per-layer
metrics. The last line of standard output is one JSON object; the numbers
that decide ``correct`` are printed beside their limits as the last lines
of standard error and under ``check`` in that object.

The run needs the accelerator: with no chip, or fewer chips than the cell
asks for, it exits non-zero and prints no result. JAX's persistent
compilation cache, the steps of compile keys a run reached that the cell's
list lacks, and traces live under ``.bench_cache/`` in the checkout.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import numpy as np  # noqa: E402

CHECK_TOKENS = 8000          # served tokens the reference compares, at least
CHECK_MAX_PADDED = 200_000   # ... unless the sample's padded length passes this


class NoChip(RuntimeError):
    """The run found no accelerator, or too few chips."""


def log(msg: str) -> None:
    print(msg, flush=True)


def load_cell(root: Path, name: str) -> dict:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {c["name"]: c for c in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    conf_entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    bench = root / spec["paths"][0]
    return {"spec": spec, "cell": cell, "bench": bench,
            "conf": json.loads((root / conf_entry["file"]).read_text()),
            "mix": json.loads(
                (bench / "traffic" / f"{cell['traffic']}.json").read_text())}


def cell_metrics(spec: dict, cell: str, trace: bool) -> list[dict]:
    """The metrics this cell reports: its end-to-end ones, or (traced) its
    per-layer ones. A metric without ``workloads`` goes to every cell that
    reports the end-to-end metric it moves."""
    def reports(m):
        return "workloads" not in m or cell in m["workloads"]

    e2e = [m for m in spec["end_to_end"] if reports(m)]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}

    def listed(m):
        return cell in m["workloads"] if "workloads" in m else m["moves"] in names

    return [m for m in spec["per_layer"] if listed(m)]


def read_metric(bench: Path, name: str, run) -> float | None:
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", bench / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


def configure_jax(root: Path) -> None:
    """Keep the compilation cache inside the checkout, at a fixed path, and
    cache every program, however quickly it compiled, with no size cap (a
    cap smaller than the cell's programs evicts them between runs)."""
    import jax

    jax.config.update("jax_compilation_cache_dir",
                      str(root / ".bench_cache" / "jax"))
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def sample_finished(window, seed: int) -> list:
    """The requests the check compares: drawn from the seed among those
    finished in the window, always with the longest, until the sample holds
    ``CHECK_TOKENS`` served tokens (or its padded length passes
    ``CHECK_MAX_PADDED``)."""
    from lm import padded_len

    fin = [sv for sv in window.served if sv.finished]
    if not fin:
        return []
    longest = max(fin, key=lambda sv: (len(sv.tokens), -sv.rid))
    rest = [sv for sv in fin if sv is not longest]
    pick, n_tok = [longest], len(longest.tokens)
    padded = padded_len(len(longest.prompt) + n_tok)
    for i in np.random.default_rng([int(seed), 1]).permutation(len(rest)):
        if n_tok >= CHECK_TOKENS or padded >= CHECK_MAX_PADDED:
            break
        sv = rest[i]
        pick.append(sv)
        n_tok += len(sv.tokens)
        padded += padded_len(len(sv.prompt) + len(sv.tokens))
    return pick


def gap_numbers(gaps: np.ndarray) -> dict:
    """The check's numbers over the served positions, each from the gap by
    which a served token's reference logit lies below the reference's best:
    the mean of its square (the number compared: rounding moves it as the
    cube of its size, and one wrong token dominates it), the widest, the
    mean, and the share of positions where the served token is not the
    reference's best."""
    g = np.asarray(gaps, np.float64)
    return {"mean_sq_gap": float(np.mean(g * g)), "max_gap": float(g.max()),
            "mean_gap": float(g.mean()),
            "off_argmax_pct": 100.0 * float(np.mean(g > 0))}


def judge(check: dict, limits: dict) -> bool:
    """Correct when every number compared is within its limit."""
    return all(check.get(k, float("inf")) <= v for k, v in limits.items())


def check_served(window, shape, weights, seed: int) -> dict:
    """Compare the sample with the plain reference: how far each served
    token's reference logit lies below the reference's best at its
    position. Also count finished streams of the wrong length."""
    from lm import Reference

    fin = [sv for sv in window.served if sv.finished]
    out = {"short_streams": sum(len(sv.tokens) != sv.out_len for sv in fin)}
    pick = sample_finished(window, seed)
    if not pick:
        log("check: no request finished in the window; nothing to compare")
        return out
    t0 = time.perf_counter()
    ref = Reference(shape, weights)
    gaps = np.concatenate([ref.served_gaps(sv.prompt, sv.tokens) for sv in pick])
    out.update(gap_numbers(gaps))
    log(f"check: {len(pick)} of {len(fin)} finished requests, {gaps.size} "
        f"served tokens (longest {len(pick[0].tokens)}), reference in "
        f"{time.perf_counter() - t0:.1f} s; off the reference argmax at "
        f"{out['off_argmax_pct']:.4f}% of positions, mean gap "
        f"{out['mean_gap']:.6g}, max gap {out['max_gap']:.6g}, mean squared "
        f"gap {out['mean_sq_gap']:.6g}")
    return out


def prepare(root: Path, workload: str, seed: int, *,
            require_chip: bool = True, t_start: float | None = None):
    """Everything before the window: the cell's files, the device check,
    the weights drawn from the seed, the executor, and warm-up by key."""
    t_start = T_START if t_start is None else t_start
    c = types.SimpleNamespace(**load_cell(root, workload))
    configure_jax(root)
    import jax

    c.devs = jax.devices()
    dev = c.devs[0]
    if require_chip and dev.platform == "cpu":
        raise NoChip(f"no accelerator: JAX found only {dev.device_kind!r}")
    if len(c.devs) < c.cell["chips"]:
        raise NoChip(f"{workload} needs {c.cell['chips']} chips, found "
                     f"{len(c.devs)}")
    peaks_all = json.loads((c.bench / "peaks.json").read_text())["kinds"]
    if dev.device_kind not in peaks_all:
        raise KeyError(f"no published peaks for device kind "
                       f"{dev.device_kind!r} in peaks.json")
    c.peaks = peaks_all[dev.device_kind]

    from drive import ProgramCounter, build_executor, read_shapes, warm_step
    from lm import make_weights, shape_of

    c.counter = ProgramCounter.get()
    c.shape = shape_of(c.conf)
    c.weights = make_weights(c.shape, seed)
    log(f"model: {c.conf['name']} ({c.conf['source']}), {c.shape.n_layers} "
        f"layers, d_model {c.shape.d_model}, heads {c.shape.n_heads}/"
        f"{c.shape.n_kv_heads}, d_ff {c.shape.d_ff}, vocab {c.shape.vocab}; "
        f"weights {time.perf_counter() - t_start:.1f} s after start")
    c.ex = build_executor(c.conf, c.shape, c.weights)
    c.local_shapes = root / ".bench_cache" / "warm" / f"{workload}.json"
    shapes = read_shapes(c.bench / "warm" / f"{workload}.json",
                         c.local_shapes)
    t0, p0 = time.perf_counter(), c.counter.programs
    for shape in shapes:
        warm_step(c.ex, shape)
    c.warm_keys = frozenset(c.ex.compile_keys)
    log(f"warm-up: {len(shapes)} steps, {len(c.ex.compile_keys)} compile "
        f"keys, {c.counter.programs - p0} "
        f"programs obtained ({c.counter.compiles} backend compiles, "
        f"{c.counter.compile_s:.1f} s compiling), "
        f"{time.perf_counter() - t0:.1f} s")
    return c


def run_cell(root: Path, workload: str, seed: int, seconds: float,
             trace: bool, *, require_chip: bool = True,
             t_start: float | None = None) -> dict:
    """One run of one cell. Returns the result object; raises ``NoChip``
    when the accelerator the cell needs is absent."""
    t_start = T_START if t_start is None else t_start
    c = prepare(root, workload, seed, require_chip=require_chip,
                t_start=t_start)
    from drive import build_engine, read_shapes, save_shapes, serve_window
    from traffic import make_jobs

    eng = build_engine(c.conf, c.shape, c.ex, c.mix)
    jobs = make_jobs(c.mix, seed, seconds, c.shape.vocab)
    trace_dir = None
    if trace:
        trace_dir = root / ".bench_cache" / "trace" / workload
        shutil.rmtree(trace_dir, ignore_errors=True)
    setup_s = time.perf_counter() - t_start
    log(f"setup: {setup_s:.3f} s; {len(jobs)} jobs, {c.mix['loop']} loop")

    win = serve_window(eng, c.ex, jobs, c.mix, seconds, c.shape.window,
                       c.counter, str(trace_dir) if trace_dir else None)
    dev = c.devs[0]
    stats = dev.memory_stats() or {}
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in c.devs[:c.cell["chips"]])
    if win.new_shapes:
        save_shapes(c.local_shapes,
                    set(read_shapes(c.local_shapes)) | set(win.new_shapes))
    report_window(win)
    log(f"warmed compile keys the window did not reach: "
        f"{sorted(c.warm_keys - win.keys)}")

    reduced = None
    if trace_dir is not None:
        from devtrace import newest_xplane, reduce_file

        try:
            reduced = reduce_file(newest_xplane(str(trace_dir)))
        except FileNotFoundError as e:
            log(f"trace: {e}")
        shutil.rmtree(trace_dir, ignore_errors=True)

    del eng
    c.ex = None
    gc.collect()
    check = check_served(win, c.shape, c.weights, seed)
    limits = c.conf["check"]["limits"]
    correct = judge(check, limits)

    run = types.SimpleNamespace(window=win, setup_s=setup_s, shape=c.shape,
                                peaks=c.peaks, trace=reduced,
                                kv_bytes=c.conf["serving"]["kv_bytes"])
    metrics = {}
    for m in cell_metrics(c.spec, workload, trace):
        v = read_metric(c.bench, m["name"], run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(c.devs), "memory_peak_bytes": peak}
    if stats.get("bytes_limit"):
        log(f"memory: peak {peak} of {stats['bytes_limit']} bytes")
    result = {"correct": bool(correct), "attempted": len(win.served),
              "failed": win.failed, "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        ops = sorted(reduced.op_s.items(), key=lambda kv: -kv[1])[:10]
        result["breakdown"] = {"device_ops": [[k, v] for k, v in ops],
                               "idle_gaps": [list(g) for g in reduced.idle_gaps]}
    result["check"] = {k: {"value": check.get(k), "limit": v}
                       for k, v in limits.items()}
    return result


def report_window(win) -> None:
    """The counts and medians behind the tails, before the result line."""
    from stats import percentile, queue_waits, tpots, ttfts

    served = win.served
    tt, tp = ttfts(served, win.close), tpots(served, win.close)
    late = [sv.sent - sv.due for sv in served if sv.sent is not None]
    no_first = sum(not sv.stamps for sv in served)
    decode = [s for s in win.steps if s.decode_only]

    def ms(x):
        return "n/a" if x is None else f"{1000 * x:.1f} ms"

    log(f"window: {win.close:.3f} s, {len(served)} requests due, "
        f"{sum(sv.finished for sv in served)} finished, {no_first} without a "
        f"first token at close, {sum(len(sv.stamps) for sv in served)} "
        f"output tokens, {len(win.steps)} steps ({len(decode)} decode-only)")
    log(f"ttft: n={len(tt)} median {ms(percentile(tt, 50))} p90 "
        f"{ms(percentile(tt, 90))}; tpot: n={len(tp)} median "
        f"{ms(percentile(tp, 50))} p90 {ms(percentile(tp, 90))}; queue wait "
        f"median {ms(percentile(queue_waits(served, win.close), 50))}")
    log(f"generator: lateness median {ms(percentile(late, 50))} max "
        f"{ms(max(late) if late else None)}; waiting at mid-window "
        f"{win.waiting_mid}, at close {win.waiting_close}")
    steps = win.steps
    mixed = [s for s in steps if not s.decode_only]
    gaps = [b.t0 - a.t1 for a, b in zip(steps, steps[1:])]
    log(f"steps: wall median {ms(percentile([s.t1 - s.t0 for s in decode], 50))}"
        f" decode-only, {ms(percentile([s.t1 - s.t0 for s in mixed], 50))} "
        f"mixed; host between steps {sum(gaps):.3f} s in all, largest "
        f"{ms(max(gaps) if gaps else None)}; garbage collector {win.gc_n} "
        f"pauses, {win.gc_s:.3f} s")
    log(f"compiles in window: {win.programs_in_window} programs, "
        f"{len(win.new_shapes)} steps of new compile keys {win.new_shapes}")
    log(f"compile keys the window reached: {len(win.keys)} "
        f"{sorted(win.keys)}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    root = BENCH.parent
    if not (root / "src" / "repro").is_dir():
        print(f"run.py: no program (src/repro) in {root}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(root / "src"))
    sys.path.append(str(root))      # the program's chip entry, chip_smoke.py
    try:
        result = run_cell(root, args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 3
    for k, v in result["check"].items():
        print(f"check {k}: {v['value']} limit {v['limit']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
