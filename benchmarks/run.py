"""Benchmark driver — one function per paper table/figure.

``python -m benchmarks.run [--full] [--only NAME]``

Prints one CSV line per bench: ``name,us_per_call,derived`` (derived =
headline metric), followed by detail rows. Full row dumps land in
``experiments/bench_results.json``; additionally every bench writes a
compact repo-root ``BENCH_<name>.json`` perf-trajectory summary (median
TTFT/TPOT percentiles, steps/s, dispatch counts — whatever numeric columns
its rows carry) so the trajectory of headline numbers is diffable across
commits without digging into the experiments blob.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import time

_REPO_ROOT = pathlib.Path(__file__).resolve().parents[1]

# row columns that make it into the BENCH_<name>.json trajectory summary
_TRAJECTORY_KEYS = (
    "ttft_p50", "ttft_p95", "ttft_p99", "tpot_p50", "tpot_p95", "tpot_p99",
    "ttft_p50_ms", "ttft_p99_ms", "tpot_p50_ms", "tpot_p99_ms",
    "sched_delay_p99_ms", "steps_per_s", "steps_per_dispatch", "dispatches",
    "steps", "slo_attainment", "effective_rps", "peak_effective_rps",
    "speedup", "dispatches_per_step", "dispatch_ratio", "step_ms",
    "hit_rate", "host_overhead_s",
    "interactive_ttft_p99", "interactive_tpot_p99",
    "interactive_p99_vs_isolated", "preemptions",
    "fused_dispatches_per_step", "tuning_gain", "tuned_cost_us",
    "default_cost_us", "modeled_step_ms",
    "goodput_ratio", "completed", "shed", "retried", "crashes",
    "detections", "warm_joins",
    "modeled_tokens_per_s", "spec_speedup", "acceptance", "tokens_per_round",
)


def write_bench_summary(name: str, rows: list[dict],
                        headline: str = "") -> pathlib.Path:
    """Write the repo-root ``BENCH_<name>.json`` perf-trajectory summary.

    Per numeric trajectory column present in ``rows``: min/median/max over
    the rows that carry it, plus a per-mode/system breakdown when rows are
    labeled. Metric rollups are SEGMENTED BY LABEL: a key carried by rows of
    more than one label (mode/system) is reported only per label — pooling
    incomparable populations into one median produced artifacts like the
    BENCH_hybrid_step.json "median 2.0 dispatches/step" (sequential rows'
    N-dispatch steps averaged against the fused path's 1.0). Keys carried by
    a single population still land in the top-level ``metrics``.
    """
    import statistics

    def numeric(v):
        return isinstance(v, (int, float)) and not isinstance(v, bool)

    def stats(vals):
        return {"min": min(vals), "median": statistics.median(vals),
                "max": max(vals)}

    def label_of(r):
        # unlabeled rows form their own pseudo-population
        lab = r.get("mode") or r.get("system")
        return str(lab) if lab else None

    metrics = {}
    for key in _TRAJECTORY_KEYS:
        carriers = [r for r in rows if numeric(r.get(key))]
        if not carriers:
            continue
        if len({label_of(r) for r in carriers}) == 1:
            metrics[key] = stats([r[key] for r in carriers])
    by_label = {}
    for key in _TRAJECTORY_KEYS:
        groups: dict = {}
        for r in rows:
            lab = label_of(r)
            if lab is not None and numeric(r.get(key)):
                groups.setdefault(lab, []).append(r[key])
        for lab, vals in groups.items():
            by_label.setdefault(lab, {})[key] = stats(vals)
    out = {"bench": name, "n_rows": len(rows), "headline": headline,
           "metrics": metrics}
    if by_label:
        out["by_label"] = by_label
    # autotuned kernel tilings (DESIGN.md §14): rows may carry the chosen
    # (kb, tb) per bucket — surfaced in the summary so tiling choices are
    # diffable across commits alongside the numbers they produced
    tilings = {}
    for r in rows:
        if isinstance(r.get("tilings"), dict):
            tilings.update(r["tilings"])
    if tilings:
        out["tilings"] = tilings
    path = _REPO_ROOT / f"BENCH_{name}.json"
    path.write_text(json.dumps(out, indent=1, default=str) + "\n")
    return path


def _headline(name: str, rows: list[dict]) -> str:
    try:
        if name == "goodput":
            g = {r["system"]: r["peak_effective_rps"] for r in rows
                 if r.get("trace") == "GEOMEAN"}
            return (f"geomean_peak_rps fb-pab={g.get('fb-pab')} "
                    f"fb-vanilla={g.get('fb-vanilla')} "
                    f"sarathi={g.get('vllm-sarathi')} "
                    f"vanilla={g.get('vllm-vanilla')}")
        if name == "latency":
            fb = next(r for r in rows if r["system"] == "fb-vanilla")
            sa = next(r for r in rows if r["system"] == "vllm-sarathi")
            return (f"p99_ttft fb={fb['ttft_p99_ms']}ms "
                    f"sarathi={sa['ttft_p99_ms']}ms "
                    f"(x{sa['ttft_p99_ms']/max(fb['ttft_p99_ms'],1e-9):.2f})")
        if name == "slo_grid":
            return ("fb_vanilla_avg=+" + str(round(sum(
                r["fb_vanilla_improvement_pct"] for r in rows) / len(rows), 1))
                + "% fb_pab_avg=+" + str(round(sum(
                    r["fb_pab_improvement_pct"] for r in rows) / len(rows), 1))
                + "%")
        if name == "breakdown":
            return " -> ".join(f"{r['system']}={r['peak_effective_rps']}"
                               for r in rows)
        if name == "cluster":
            dp8 = [r for r in rows if r.get("dp") == max(r2.get("dp", 0)
                                                         for r2 in rows)]
            pab = next((r for r in dp8 if r["lb"] == "pab-lb"
                        and "failure" not in r["scheduler"]), None)
            base = max((r["peak_effective_rps"] for r in dp8
                        if r["lb"] == "vllm-lb"), default=0)
            if pab and base:
                return (f"dp8 pab-lb={pab['peak_effective_rps']} "
                        f"best_count_lb={base} "
                        f"(+{100*(pab['peak_effective_rps']/base-1):.1f}%)")
        if name == "disagg":
            sys_rows = [r for r in rows if "system" in r]
            dis = min((r for r in sys_rows
                       if r["system"].startswith("disagg-")),
                      key=lambda r: r["ttft_p99_ms"])
            mono = min((r for r in sys_rows
                        if r["system"].startswith("mono-")),
                       key=lambda r: r["ttft_p99_ms"])
            return (f"p99_ttft {dis['system']}={dis['ttft_p99_ms']}ms vs "
                    f"{mono['system']}={mono['ttft_p99_ms']}ms "
                    f"tpot_att={dis['tpot_slo_attainment']}"
                    f"/{mono['tpot_slo_attainment']}")
        if name == "chaos":
            by = {r["mode"]: r for r in rows if "mode" in r}
            return (f"goodput_ratio light={by['light']['goodput_ratio']} "
                    f"heavy={by['heavy']['goodput_ratio']} | heavy "
                    f"crashes={by['heavy']['crashes']}"
                    f"/warm_joins={by['heavy']['warm_joins']} "
                    f"retried={by['heavy']['retried']} "
                    f"deterministic={by['determinism']['identical']}")
        if name == "unfairness":
            sa = next(r for r in rows if r["system"] == "sarathi")
            fb = next(r for r in rows if r["system"] == "fairbatching")
            return (f"decode_ahead sarathi={sa['decode_tokens_ahead_mean']:.0f}tok"
                    f"/ttft_viol={sa['ttft_violations']} "
                    f"fb={fb['decode_tokens_ahead_mean']:.0f}tok"
                    f"/ttft_viol={fb['ttft_violations']}")
        if name == "cost_model":
            r = rows[0]
            return (f"token_only_p95={r['token_only_p95_err_pct']}% "
                    f"linear_p95={r['linear_p95_err_pct']}%")
        if name == "prefix_cache":
            fb = [r for r in rows if r["scenario"] == "shared-sysprompt"
                  and r["system"] == "fairbatching"]
            cold = next(r for r in fb if r["cache_pages"] == 0)
            warm = max((r for r in fb if r["cache_pages"] > 0),
                       key=lambda r: r["cache_pages"])
            aff = {r["lb"]: r["hit_rate"] for r in rows
                   if r["scenario"] == "affinity-dp4"}
            return (f"sysprompt p99_ttft {cold['ttft_p99_ms']}ms -> "
                    f"{warm['ttft_p99_ms']}ms @hit={warm['hit_rate']} | "
                    f"dp4 hit cache-lb={aff.get('cache')} "
                    f"rr={aff.get('roundrobin')}")
        if name == "fairness":
            by = {r["system"]: r for r in rows}
            return ("interactive p99 vs isolated: "
                    f"fcfs={by['fcfs-admission']['interactive_p99_vs_isolated']}x "
                    f"vtc={by['vtc-admission']['interactive_p99_vs_isolated']}x")
        if name == "roofline":
            n = len(rows)
            dom = {}
            for r in rows:
                dom[r["dominant"]] = dom.get(r["dominant"], 0) + 1
            return f"cells={n} dominant={dom}"
        if name == "hybrid_step":
            sp = {r["mix"]: r["speedup"] for r in rows
                  if r["mode"] == "speedup"}
            disp = {r["mix"]: r["dispatches_per_step"] for r in rows
                    if r["mode"] == "fused"}
            return (f"fused_speedup {sp} dispatches/step "
                    f"{sorted(set(disp.values()))}")
        if name == "tp_step":
            sp = {r["mode"]: r["speedup"] for r in rows
                  if r["mix"] == "prefill-heavy"}
            ran = sorted(r["tp"] for r in rows
                         if r["executed"] and r["mix"] == "prefill-heavy")
            par = all(r.get("parity") == "ok" for r in rows
                      if r["mix"] == "prefill-heavy" and r["executed"])
            return (f"modeled_speedup {sp} ran=TP{ran} "
                    f"parity={'ok' if par else 'FAIL'}")
        if name == "autotune_attention":
            gains = [r["tuning_gain"] for r in rows if r["mode"] == "winner"]
            import statistics
            return (f"cells={len(gains)} tuning_gain median="
                    f"{statistics.median(gains):.2f} max={max(gains):.2f}")
        if name == "async_pipeline":
            by = {r["mode"]: r for r in rows}
            seq, pipe = by["sequential"], by["pipelined"]
            multi = by["multi-step"]
            return (f"steps/s seq={seq['steps_per_s']} "
                    f"pipe={pipe['steps_per_s']} "
                    f"multi={multi['steps_per_s']} | dispatches "
                    f"{seq['dispatches']} -> {multi['dispatches']} "
                    f"(real h8: {by['real-h8']['steps_per_dispatch']} "
                    f"steps/dispatch)")
        if name == "spec_decode":
            hd = next(r for r in rows if r.get("mode") == "headline")
            fg = next(r for r in rows if r.get("mode") == "fairness-guard")
            return (f"decode tok/s x{hd['spec_speedup']} @gamma="
                    f"{hd['gamma']} acc={hd['acceptance']} | vtc "
                    f"interactive_p99_vs_isolated spec="
                    f"{fg['interactive_p99_vs_isolated']}x "
                    f"base={fg['baseline_p99_vs_isolated']}x")
    except (StopIteration, KeyError, ZeroDivisionError):
        pass
    return f"rows={len(rows)}"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only")
    ap.add_argument("--json-out", default="experiments/bench_results.json")
    args = ap.parse_args()
    quick = not args.full
    from repro.launch.compile_cache import setup_compile_cache
    setup_compile_cache()

    from . import (async_pipeline_bench, autotune_attention, breakdown_bench,
                   chaos_bench, cluster_bench, cost_model_bench, disagg_bench,
                   fairness_bench, goodput_bench, hybrid_step_bench,
                   latency_bench, prefix_cache_bench, roofline_report,
                   slo_grid_bench, spec_decode_bench, tp_scaling_bench,
                   unfairness_bench)
    benches = {
        "cost_model": cost_model_bench.run,      # paper §3.2 accuracy claim
        "unfairness": unfairness_bench.run,      # Fig 1/2
        "goodput": goodput_bench.run,            # Table 3 / Fig 5
        "latency": latency_bench.run,            # Table 4 / Fig 6
        "slo_grid": slo_grid_bench.run,          # Table 5
        "breakdown": breakdown_bench.run,        # Fig 7
        "cluster": cluster_bench.run,            # Fig 8
        "prefix_cache": prefix_cache_bench.run,  # DESIGN.md §10 reuse
        "autotune_attention": autotune_attention.run,  # DESIGN.md §14 tiling
        "hybrid_step": hybrid_step_bench.run,    # DESIGN.md §11 fused step
        "tp_step": tp_scaling_bench.run,         # DESIGN.md §17 TP scaling
        "async_pipeline": async_pipeline_bench.run,  # DESIGN.md §12
        "spec_decode": spec_decode_bench.run,    # DESIGN.md §18 speculation
        "fairness": fairness_bench.run,          # DESIGN.md §13 VTC stack
        "disagg": disagg_bench.run,              # DESIGN.md §15 P/D split
        "chaos": chaos_bench.run,                # DESIGN.md §16 fault plane
        "roofline": roofline_report.run,         # deliverable (g)
    }
    all_rows = {}
    for name, fn in benches.items():
        if args.only and name != args.only:
            continue
        t0 = time.time()
        rows = fn(quick=quick)
        dt_us = (time.time() - t0) * 1e6
        all_rows[name] = rows
        headline = _headline(name, rows)
        print(f"{name},{dt_us:.0f},{headline}")
        for r in rows:
            print("  " + json.dumps(r))
        write_bench_summary(name, rows, headline)
    if args.json_out:
        import os
        os.makedirs(os.path.dirname(args.json_out), exist_ok=True)
        with open(args.json_out, "w") as f:
            json.dump(all_rows, f, indent=1, default=str)


if __name__ == "__main__":
    main()
