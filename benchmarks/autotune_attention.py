"""Block-shape autotuner for the ragged paged-attention kernel (§14).

The fused hybrid step launches ``paged_attention_ragged[_quant]`` on a grid
``(kv_head, q_block, seq, page_block)``; the tiling ``(pages_per_block kb,
q_block tb)`` trades per-grid-step overhead (fewer, fatter steps) against
early-skip granularity (a fat tile straddling two sequences or running past
a short context does wasted work a finer tile would have skipped).

On this repo's CI backend the Pallas kernel cannot execute compiled, so the
tuner is *analytic*, built on the repo's existing cost machinery: roofline
TPU v5e peaks from ``benchmarks.roofline_report`` (``V5E``) price
the compute and HBM terms, ``repro.launch.hlo_analysis.shape_bytes`` prices
each VMEM tile from its HLO shape string, and the kernel's exact host-side
skip predicate (same arithmetic as the ``pl.when`` guard) is evaluated over
representative bucket workloads to count executed vs merely-issued grid
steps. ``cost = max(flops/peak, bytes/bw) + overhead · grid_steps``.

Winners are recorded per ``(t_bucket, pages_bucket)`` compile-key cell —
the same two axes the fused executor's staging ladder uses — written to
``experiments/autotune_attention.json``, installed into the kernel registry
via ``set_ragged_tilings``, and carried into the hybrid-step bench summary
(``BENCH_hybrid_step.json``) so the chosen tilings are diffable across
commits.

Standalone: ``PYTHONPATH=src python -m benchmarks.autotune_attention
[--smoke]``; also runs under the ``benchmarks.run`` driver as
``--only autotune_attention``.
"""
from __future__ import annotations

import dataclasses
import json
import os

from .roofline_report import V5E

# per-grid-step issue overhead (index maps, DMA descriptors, predicate):
# dominates when tiles are tiny, which is exactly what the tuner must
# penalize — the TPU guide's "grid overhead vs tile size" trade
GRID_STEP_OVERHEAD_S = 2e-7

TUNE_JSON = "experiments/autotune_attention.json"

# candidate pages-per-block values (kb repeated in_specs on the page pool)
KB_CANDIDATES = (1, 2, 4, 8)


@dataclasses.dataclass(frozen=True)
class KernelGeom:
    """Shapes the tuner prices: the fused step's attention operands."""
    n_kv_heads: int
    group: int          # query heads per kv head
    head_dim: int
    page: int
    kv_dtype: str       # "f32" or "s8" (HLO dtype spelling)


def _ladder(n: int, lo: int) -> int:
    b = lo
    while b < n:
        b = b * 3 // 2 if b % 3 else b * 4 // 3
    return b


def _workloads(t_bucket: int, pg_bucket: int, page: int):
    """Representative packed steps for a bucket cell, mirroring the
    hybrid-step bench mixes: chunk-heavy, balanced, pure decode. Each is a
    list of (q_len, context_len) with Σ q_len <= t_bucket and contexts
    spanning the pages bucket."""
    max_ctx = pg_bucket * page
    outs = []
    # prefill-heavy: two chunks splitting the token budget
    c1 = max(1, t_bucket // 2)
    outs.append([(c1, min(max_ctx, c1)),
                 (max(1, t_bucket - c1), min(max_ctx, t_bucket - c1 + page))])
    # balanced: one chunk + decode rows with staggered contexts
    chunk = max(1, t_bucket // 2)
    seqs = [(chunk, min(max_ctx, chunk + page))]
    ctx = page // 2
    for _ in range(t_bucket - chunk):
        seqs.append((1, min(max_ctx, ctx)))
        ctx += page // 2 + 1
    outs.append(seqs)
    # decode-heavy: all single-token rows, contexts filling the bucket
    seqs = []
    for i in range(t_bucket):
        seqs.append((1, 1 + (i * max_ctx) // max(t_bucket, 1)))
    outs.append(seqs)
    return outs


def _cost(geom: KernelGeom, seqs, t_bucket: int, pg_bucket: int,
          kb: int, tb: int) -> float:
    """Analytic seconds for one launch of the tiled ragged kernel.

    Walks the exact grid the kernel would issue and applies its skip
    predicate per (q_block, seq, page_block) cell; executed cells pay the
    roofline max(compute, memory) for their tiles, every cell pays the
    per-grid-step issue overhead.
    """
    from repro.launch.hlo_analysis import shape_bytes

    g, d, page = geom.group, geom.head_dim, geom.page
    n_pb = -(-pg_bucket // kb)
    if t_bucket % tb:
        tb = t_bucket                       # kernel falls back untiled
    n_qb = t_bucket // tb
    n_seq = _ladder(len(seqs), 4)
    q_lens = [q for q, _ in seqs] + [0] * (n_seq - len(seqs))
    ctxs = [c for _, c in seqs] + [0] * (n_seq - len(seqs))
    q_starts, off = [], 0
    for q in q_lens:
        q_starts.append(off)
        off += q

    q_tile = shape_bytes(f"f32[{tb},{g},{d}]")
    kv_tile = 2 * kb * shape_bytes(f"{geom.kv_dtype}[{page},{d}]")
    if geom.kv_dtype != "f32":
        kv_tile += 2 * kb * shape_bytes(f"f32[{page}]")   # scale rows
    o_tile = shape_bytes(f"f32[{tb},{g},{d}]")
    tile_flops = 2.0 * 2.0 * (tb * g) * d * (kb * page)   # qk^T + pv

    flops = 0.0
    bytes_acc = 0.0
    for qb in range(n_qb):
        row0 = qb * tb
        for s in range(n_seq):
            overlap = (q_lens[s] > 0 and row0 < q_starts[s] + q_lens[s]
                       and row0 + tb > q_starts[s])
            for pb in range(n_pb):
                if overlap and pb * kb * page < ctxs[s]:
                    flops += tile_flops
                    bytes_acc += q_tile + kv_tile
        bytes_acc += o_tile                  # one flush write per q block
    grid_steps = geom.n_kv_heads * n_qb * n_seq * n_pb
    flops *= geom.n_kv_heads
    bytes_acc *= geom.n_kv_heads
    return (max(flops / V5E["bf16_flops"], bytes_acc / V5E["hbm_bytes_per_s"])
            + GRID_STEP_OVERHEAD_S * grid_steps)


def _tb_candidates(t_bucket: int) -> list[int]:
    return [tb for tb in range(1, t_bucket + 1) if t_bucket % tb == 0]


def sweep(geom: KernelGeom, t_buckets, pg_buckets):
    """Full (bucket × tiling) sweep. Returns (rows, winners) where winners
    maps (t_bucket, pg_bucket) -> (kb, tb) — ``set_ragged_tilings`` format."""
    rows, winners = [], {}
    for t in t_buckets:
        for pg in pg_buckets:
            cells = _workloads(t, pg, geom.page)
            best, best_cost, default_cost = None, None, None
            for kb in (k for k in KB_CANDIDATES if k <= pg):
                for tb in _tb_candidates(t):
                    c = sum(_cost(geom, seqs, t, pg, kb, tb)
                            for seqs in cells) / len(cells)
                    if kb == 1 and tb == t:
                        default_cost = c     # untuned (1, None) behaviour
                    if best_cost is None or c < best_cost:
                        best, best_cost = (kb, tb), c
            winners[(t, pg)] = best
            rows.append({
                "bench": "autotune_attention", "mode": "winner",
                "t_bucket": t, "pg_bucket": pg,
                "kb": best[0], "tb": best[1],
                "tuned_cost_us": round(best_cost * 1e6, 4),
                "default_cost_us": round(default_cost * 1e6, 4),
                "tuning_gain": round(default_cost / best_cost, 3),
            })
    return rows, winners


def _geom_from_cfg(cfg, kv_dtype: str = "f32", page: int = 16,
                   tp: int = 1) -> KernelGeom:
    """Kernel geometry; under TP each shard's launch covers n_kv_heads/TP
    head groups (DESIGN.md §17), which shifts the roofline balance — the
    reason the registry is keyed per mesh shape."""
    return KernelGeom(n_kv_heads=max(1, cfg.n_kv_heads // max(tp, 1)),
                      group=cfg.n_heads // cfg.n_kv_heads,
                      head_dim=cfg.head_dim, page=page, kv_dtype=kv_dtype)


def _bucket_grids(smoke: bool):
    """The ladder cells the hybrid-step bench actually compiles."""
    t_buckets, t = [], 4
    while t <= (16 if smoke else 64):
        t_buckets.append(t)
        t = _ladder(t + 1, 4)
    pg_buckets, p = [], 2
    while p <= 8:
        pg_buckets.append(p)
        p = _ladder(p + 1, 2)
    return t_buckets, pg_buckets


def tune_and_install(cfg=None, kv_dtype: str = "f32", page: int = 16,
                     smoke: bool = False, mesh_key=None,
                     json_path: str = TUNE_JSON) -> tuple[list, dict]:
    """Run the sweep, persist winners, install them into the kernel registry.

    Returns (rows, winners). The persisted JSON keys are
    ``"{t_bucket}x{pg_bucket}"`` (JSON has no tuple keys). ``mesh_key``
    (``paged_attention.mesh_tiling_key`` format, None = single device)
    tunes the per-shard geometry of that mesh shape and installs winners
    under its registry key only — single-device winners never leak into
    sharded launches (DESIGN.md §17).
    """
    from repro.kernels.paged_attention import set_ragged_tilings

    if cfg is None:
        from repro.configs import get_reduced
        cfg = dataclasses.replace(get_reduced("stablelm-3b"), window=None)
    tp = dict(mesh_key or ()).get("model", 1)
    geom = _geom_from_cfg(cfg, kv_dtype=kv_dtype, page=page, tp=tp)
    t_buckets, pg_buckets = _bucket_grids(smoke)
    rows, winners = sweep(geom, t_buckets, pg_buckets)
    set_ragged_tilings(winners, mesh=mesh_key)
    if json_path:
        os.makedirs(os.path.dirname(json_path) or ".", exist_ok=True)
        with open(json_path, "w") as f:
            json.dump({"geom": dataclasses.asdict(geom),
                       "mesh": (None if mesh_key is None
                                else [list(kv) for kv in mesh_key]),
                       "winners": {f"{t}x{p}": list(v)
                                   for (t, p), v in winners.items()}},
                      f, indent=1)
    return rows, winners


def load_tilings(json_path: str = TUNE_JSON) -> dict:
    """Read persisted winners back into ``set_ragged_tilings`` format;
    empty dict when no tuning artifact exists yet."""
    if not os.path.exists(json_path):
        return {}
    with open(json_path) as f:
        blob = json.load(f)
    out = {}
    for key, val in blob.get("winners", {}).items():
        t, p = key.split("x")
        out[(int(t), int(p))] = (int(val[0]), val[1])
    return out


def run(quick: bool = True, smoke: bool = False) -> list[dict]:
    rows, _ = tune_and_install(smoke=smoke)
    return rows


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="seconds-scale run for CI")
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args()
    rows, winners = tune_and_install(smoke=args.smoke)
    for r in rows:
        print(json.dumps(r))
    from .run import _headline, write_bench_summary
    print("trajectory -> "
          f"{write_bench_summary('autotune_attention', rows, _headline('autotune_attention', rows))}")
    if not args.smoke:
        return
    # smoke gate: every bucket cell got a winner, no winner loses to the
    # untuned default, and the registry round-trips exactly
    from repro.kernels.paged_attention import get_ragged_tiling
    assert rows and all(r["tuning_gain"] >= 1.0 for r in rows), rows
    for (t, p), (kb, tb) in winners.items():
        assert get_ragged_tiling(t, p) == (kb, tb), (t, p)
    reloaded = load_tilings()
    assert reloaded == winners, "tuning artifact did not round-trip"
    print(f"autotune smoke OK: {len(winners)} bucket cells tuned")


if __name__ == "__main__":
    main()
