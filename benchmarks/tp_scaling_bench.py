"""Tensor-parallel fused-step scaling + cross-TP parity (DESIGN.md §17).

Two claims, one bench:

* **Parity** — the sharded fused hybrid step at TP=2/4 must emit token
  streams bit-identical to the TP=1 run on identical deterministic plan
  sequences (the hybrid-step bench's fixed round-robin driver), and still
  run exactly ONE dispatch per warm step. This executes for every TP degree
  the backend can actually hold (fake host devices from
  ``xla_force_host_platform_device_count``); degrees the backend can't run
  degrade to modeled-only rows, never a crash.

* **Scaling** — per-step speedup at each TP degree from the §17 per-shard
  cost model over roofline-derived coefficients (``per_shard_model``: the
  marginal compute/HBM terms divide by TP, the launch overhead doesn't).
  Wall-clock on emulated host devices is reported per row but is
  *informational only* — collectives on one physical CPU serialize, so the
  acceptance number is the modeled speedup, exactly the quantity the
  scheduler's per-shard budgets act on. The smoke gate asserts >= 1.5x at
  TP=4 on the compute-bound mix.

Standalone: ``PYTHONPATH=src python -m benchmarks.tp_scaling_bench
[--smoke]``; also runs under the ``benchmarks.run`` driver as
``--only tp_step``.
"""
from __future__ import annotations

import os

# fake host devices for the sharded passes — must precede jax backend init;
# appended, never clobbered (same contract as tests/conftest.py)
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = \
        f"{_flags} --xla_force_host_platform_device_count=8".strip()

import dataclasses  # noqa: E402
import statistics  # noqa: E402

from .roofline_report import V5E

# fixed per-step launch/host overhead (seconds): the `a` of the roofline-
# derived cost model. Paid once per step on EVERY shard — the term TP can
# never shrink, which is what keeps small decode steps from scaling 4x.
DISPATCH_OVERHEAD_S = 30e-6

TP_DEGREES = (1, 2, 4)

# modeled mixes: (new_tokens, total_context) per step on the FULL config.
# prefill-heavy is the compute-bound cell the >=1.5x acceptance targets.
MODEL_MIXES = {
    "prefill-heavy": (256, 2048),
    "balanced": (64, 8192),
    "decode-heavy": (8, 16384),
}


def _roofline_model(cfg, tp: int = 1):
    """LinearCostModel(a, b, c) for one shard of the full config: b prices
    a new token's FLOPs at roofline compute, c prices a context token's KV
    reads at roofline HBM bandwidth — then §17's per-shard division."""
    from repro.core.cost_model import (LinearCostModel, kv_bytes_per_token,
                                      per_shard_model)

    b = 2.0 * cfg.active_param_count() / V5E["bf16_flops"]
    c = kv_bytes_per_token(cfg.n_layers, cfg.n_kv_heads, cfg.head_dim,
                           "fp32") / V5E["hbm_bytes_per_s"]
    return per_shard_model(
        LinearCostModel(a=DISPATCH_OVERHEAD_S, b=b, c=c), tp)


def _runnable_degrees(cfg) -> list[int]:
    import jax

    out = []
    for tp in TP_DEGREES:
        if jax.device_count() >= tp and cfg.n_kv_heads % tp == 0 \
                and cfg.n_heads % tp == 0:
            out.append(tp)
    return out


def _wall_clock(cfg, params, degrees, reps: int) -> dict:
    """Warm per-step wall-clock + dispatch counts per TP degree, all
    degrees executing the identical deterministic plan sequence (the
    hybrid-step driver asserts the emitted tokens match across executors —
    the cross-TP parity gate rides on that)."""
    from repro.engine import PagedTransformerExecutor
    from repro.launch.mesh import make_test_mesh

    from .hybrid_step_bench import _drive

    execs = {}
    for tp in degrees:
        mesh = None if tp == 1 else make_test_mesh(data=1, model=tp)
        execs[f"tp{tp}"] = PagedTransformerExecutor(
            cfg, params, num_pages=256, page_size=16, max_pages_per_seq=8,
            mode="fused", mesh=mesh)
    _drive(execs, cfg, "prefill-heavy", n_req=8)       # cold: compiles
    warm = [_drive(execs, cfg, "prefill-heavy", n_req=8)
            for _ in range(reps)]
    out = {}
    for tp in degrees:
        m = f"tp{tp}"
        steps = sum(w["steps"] for w in warm)
        disp = sum(w["dispatches"][m] for w in warm)
        out[tp] = {
            "step_ms": round(1e3 * statistics.median(
                dt for w in warm for dt in w["dts"][m]), 3),
            "dispatches_per_step": round(disp / max(steps, 1), 2),
        }
    return out


def run(quick: bool = True, smoke: bool = False) -> list[dict]:
    import jax

    from repro.configs import get, get_reduced
    from repro.models import ModelOpts, build_model

    smoke_cfg = dataclasses.replace(get_reduced("stablelm-3b"), window=None)
    full_cfg = get("stablelm-3b")
    model = build_model(smoke_cfg, ModelOpts(attn_impl="dense"))
    params = model.init(jax.random.PRNGKey(0))

    degrees = _runnable_degrees(smoke_cfg)
    wall = _wall_clock(smoke_cfg, params, degrees,
                       reps=3 if (smoke or quick) else 5)

    rows = []
    for mix, (nt, ctx) in MODEL_MIXES.items():
        t1 = _roofline_model(full_cfg, 1).step_time(nt, ctx)
        for tp in TP_DEGREES:
            t = _roofline_model(full_cfg, tp).step_time(nt, ctx)
            row = {
                "bench": "tp_step", "mode": f"tp{tp}", "tp": tp, "mix": mix,
                "new_tokens": nt, "context": ctx,
                "modeled_step_ms": round(1e3 * t, 4),
                "speedup": round(t1 / t, 2),         # modeled, vs TP=1
                "executed": tp in wall,
            }
            # wall-clock/parity come from the driven prefill-heavy pass
            # only — attaching them to modeled-only mixes would read as if
            # those mixes ran (informational either way on host devices)
            if tp in wall and mix == "prefill-heavy":
                row.update(wall[tp])
                row["parity"] = "ok"    # _drive asserted identical tokens
            rows.append(row)
    if skipped := [tp for tp in TP_DEGREES if tp not in wall]:
        # no silent caps: modeled-only degrees are called out
        print(f"tp_scaling_bench: TP degrees {skipped} not runnable on "
              f"{jax.device_count()} {jax.default_backend()} device(s) — "
              "modeled rows only")
    return rows


def main() -> None:
    import argparse
    import json

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="seconds-scale run for CI")
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args()
    rows = run(quick=not args.full, smoke=args.smoke)
    for r in rows:
        print(json.dumps(r))
    from .run import _headline, write_bench_summary
    print("trajectory -> "
          f"{write_bench_summary('tp_step', rows, _headline('tp_step', rows))}")
    if not args.smoke:
        return
    # smoke gates (DESIGN.md §17):
    # (1) parity — every degree that executed emitted the TP=1 stream
    #     (asserted inside the shared driver; rows record it) and kept the
    #     1-dispatch/step contract under sharding
    ran = [r for r in rows if r["executed"] and r["mix"] == "prefill-heavy"]
    assert len(ran) >= 2, f"need TP>=2 executing for the parity gate: {rows}"
    assert all(r.get("parity") == "ok" for r in ran), rows
    assert all(r["dispatches_per_step"] == 1.0 for r in ran), \
        f"sharding multiplied launches: {ran}"
    # (2) scaling — per-shard pricing yields >= 1.5x at TP=4 on the
    #     compute-bound mix (the acceptance number; wall-clock on emulated
    #     host devices is informational)
    tp4 = next(r for r in rows
               if r["tp"] == 4 and r["mix"] == "prefill-heavy")
    assert tp4["speedup"] >= 1.5, \
        f"TP=4 modeled speedup below 1.5x: {tp4}"
    print(f"tp smoke OK: degrees ran={sorted(r['tp'] for r in ran)} "
          f"tp4 modeled speedup={tp4['speedup']}x")


if __name__ == "__main__":
    main()
