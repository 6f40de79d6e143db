"""Async pipelined control plane + slack-bounded multi-step decode (§12).

The fairness math forms the right batch; this bench measures what the
*control plane* costs to keep doing that every step. Three engines replay
the identical bursty-gamma trace with a realistic per-dispatch host cost:

* ``sequential``  — depth-1 engine: every step pays the host overhead as a
  device bubble (form + dispatch land on TBT, exactly the §3.1 metric the
  envelope machinery protects);
* ``pipelined``   — depth-2 engine: batch N+1 is formed against projected
  state while N runs, so the bubble disappears;
* ``multi-step``  — depth-2 + slack-bounded decode commitment
  (``capacity.commit_horizon``): pure-decode phases run H steps per
  dispatch, cutting dispatch count itself without busting any envelope.

Headline: steps/s and dispatches/step versus the sequential engine, plus
TTFT/TPOT tails and the scheduling-delay breakdown.

A second, real-data-plane section drives ``PagedTransformerExecutor`` with
``commit_horizon`` > 1 and asserts the H-steps ⇒ 1-jit-dispatch contract on
hardware (the CI compile-guard hook).

Standalone: ``PYTHONPATH=src python -m benchmarks.async_pipeline_bench
[--smoke]`` — ``--smoke`` is the seconds-scale CI mode. Also runs under the
``benchmarks.run`` driver as ``--only async_pipeline``; both entry points
write a repo-root ``BENCH_async_pipeline.json`` perf-trajectory summary.
"""
from __future__ import annotations

import json

HOST_OVERHEAD = 0.004       # 4 ms of form+dispatch per step, paper-§4-ish
HORIZON = 16

VARIANTS = {
    # name -> replay kwargs beyond the shared ones
    "sequential": {},
    "pipelined": {"pipeline_depth": 2},
    "multi-step": {"pipeline_depth": 2, "commit_horizon": HORIZON,
                   "predicted_prefill_tokens": 512},
}


def _sim_rows(duration: float, seed: int) -> list[dict]:
    from repro.data.traces import make_gamma_trace
    from repro.sim import replay

    from .common import DEFAULT_HW, HARDWARE, capacity_rps, initial_estimate

    hw = HARDWARE[DEFAULT_HW]
    rps = 0.85 * capacity_rps(hw, "qwentrace")
    trace = make_gamma_trace("qwentrace", rps=rps, duration=duration,
                             seed=seed)
    rows = []
    for name, kw in VARIANTS.items():
        res = replay(trace, scheduler="fairbatching", n_ranks=1,
                     lb="roundrobin", true_model=hw.model(),
                     est_model=initial_estimate(hw), seed=seed,
                     host_overhead=HOST_OVERHEAD, **kw)
        s = res.summary
        rows.append({
            "bench": "async_pipeline", "mode": name,
            "n_requests": s["n_requests"],
            "slo_attainment": round(s["slo_attainment"], 4),
            "ttft_p50_ms": round(s["ttft_p50"] * 1e3, 2),
            "ttft_p99_ms": round(s["ttft_p99"] * 1e3, 2),
            "tpot_p50_ms": round(s["tpot_p50"] * 1e3, 2),
            "tpot_p99_ms": round(s["tpot_p99"] * 1e3, 2),
            "sched_delay_p99_ms": round(s["sched_delay_p99"] * 1e3, 2),
            "steps": s["engine_steps"],
            "dispatches": s["dispatches"],
            "steps_per_dispatch": round(s["engine_steps"]
                                        / max(s["dispatches"], 1), 2),
            "steps_per_s": round(s["engine_steps"] / res.duration, 1),
            "host_overhead_s": round(s["host_overhead_s"], 3),
            "duration_s": round(res.duration, 2),
            "rollbacks": s["rollbacks"],
        })
    return rows


def _real_rows(n_req: int, n_new: int) -> list[dict]:
    """Real data plane: commit_horizon on ``PagedTransformerExecutor``."""
    import dataclasses as dc
    import statistics

    import jax

    from repro.configs import get_reduced
    from repro.core import LinearCostModel, make_scheduler
    from repro.engine import (Engine, EngineConfig, PagedTransformerExecutor,
                              Request)
    from repro.models import ModelOpts, build_model

    import jax.numpy as jnp

    from repro.engine import BlockAllocator

    cfg = dc.replace(get_reduced("stablelm-3b"), window=None)
    model = build_model(cfg, ModelOpts(attn_impl="dense"))
    params = model.init(jax.random.PRNGKey(0))
    rows = []
    for horizon in (1, 8):
        execu = PagedTransformerExecutor(cfg, params, num_pages=256,
                                         page_size=16, max_pages_per_seq=8)

        def run_once():
            # fresh paged state, warm jit caches
            execu.alloc = BlockAllocator(256, 16)
            assert execu.alloc.extend(-1, 16) == [0]       # trash page
            execu.k_pages = jax.tree.map(jnp.zeros_like, execu.k_pages)
            execu.v_pages = jax.tree.map(jnp.zeros_like, execu.v_pages)
            execu.n_dispatches = 0
            eng = Engine(make_scheduler(
                "fairbatching", LinearCostModel(1e-4, 1e-6, 1e-10)),
                execu, EngineConfig(5.0, 5.0, commit_horizon=horizon))
            rng = jax.random.PRNGKey(5)
            for i in range(n_req):
                plen = 6 + 5 * i
                toks = [int(x) for x in jax.random.randint(
                    jax.random.fold_in(rng, i), (plen,), 0, cfg.vocab)]
                eng.submit(Request(i, 0.0, plen, n_new, 5.0, 5.0,
                                   tokens=toks))
            n = 0
            while eng.has_work and n < 1000:
                eng.step()
                n += 1
            assert not eng.has_work
            return eng
        run_once()                        # cold pass pays the XLA compiles
        eng = run_once()                  # warm pass is what we report
        dts = [s.t_end - s.t_start for s in eng.steps]
        rows.append({
            "bench": "async_pipeline", "mode": f"real-h{horizon}",
            "horizon": horizon, "steps": len(eng.steps),
            "dispatches": execu.n_dispatches,
            "steps_per_dispatch": round(len(eng.steps)
                                        / max(execu.n_dispatches, 1), 2),
            "decode_step_ms": round(1e3 * statistics.median(dts), 3),
            "tokens": sum(r.generated for r in eng.requests.values()),
        })
    return rows


def run(quick: bool = True, smoke: bool = False) -> list[dict]:
    if smoke:
        rows = _sim_rows(duration=20.0, seed=7)
        rows += _real_rows(n_req=4, n_new=17)
    else:
        rows = _sim_rows(duration=40.0 if quick else 120.0, seed=7)
        rows += _real_rows(n_req=8 if quick else 12, n_new=24)
    return rows


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="seconds-scale run for CI")
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args()
    rows = run(quick=not args.full, smoke=args.smoke)
    for r in rows:
        print(json.dumps(r))
    # standalone runs write the repo-root perf-trajectory summary too; the
    # benchmarks.run driver writes it (with a headline) for driver runs
    from .run import _headline, write_bench_summary
    print("trajectory -> "
          f"{write_bench_summary('async_pipeline', rows, _headline('async_pipeline', rows))}")
    if not args.smoke:
        return
    by = {r["mode"]: r for r in rows}
    seq, pipe, multi = by["sequential"], by["pipelined"], by["multi-step"]
    # pipelining must hide the host bubble: more steps per sim-second
    assert pipe["steps_per_s"] > seq["steps_per_s"], (pipe, seq)
    # commitment must cut dispatches without costing SLO attainment
    assert multi["dispatches"] < pipe["dispatches"], (multi, pipe)
    assert multi["slo_attainment"] >= seq["slo_attainment"], (multi, seq)
    # real data plane: H committed steps ran as ONE jit dispatch
    real = by["real-h8"]
    assert real["steps_per_dispatch"] > 2.0, real
    assert by["real-h1"]["steps_per_dispatch"] == 1.0, by["real-h1"]
    print("smoke OK: pipelining hides the host bubble, H steps => 1 dispatch")


if __name__ == "__main__":
    main()
