"""Roofline analysis (deliverable g): three terms per (arch × shape) cell
from the dry-run artifacts.

    compute    = HLO_FLOPs_per_device / peak_FLOPs_per_chip
    memory     = HLO_bytes_per_device / HBM_bandwidth
    collective = collective_bytes_per_device / ICI_link_bandwidth

FLOPs/bytes/collectives are the trip-count-corrected numbers from
launch/hlo_analysis.py (XLA's cost_analysis counts scan bodies once; raw
values are kept in the JSONs for cross-checking). Collective traffic uses
output bytes with an all-reduce ×2 factor (ring algorithm, documented
approximation). MODEL_FLOPS = 2·N_active·tokens (serving fwd) or
6·N·tokens (training) — the ratio to HLO FLOPs surfaces remat/dispatch
waste.
"""
from __future__ import annotations

import glob
import json
import os

from repro import configs

# Published per-chip peaks keyed by ``jax.Device.device_kind``. Source:
# Google Cloud documentation, "TPU v5e" (system architecture table):
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s
# of chip-to-chip interconnect over 4 ICI links.
CHIP_PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
        "ici_link_bytes_per_s": 1600e9 / 8 / 4,
    },
}
# the chip every modeled number in this repo is priced for
V5E = CHIP_PEAKS["TPU v5 lite"]


def device_peaks(device=None) -> dict:
    """Published peaks of ``device`` (default: the first JAX device). A
    kind missing from ``CHIP_PEAKS`` is an error, never a default."""
    import jax

    kind = (device or jax.devices()[0]).device_kind
    if kind not in CHIP_PEAKS:
        raise KeyError(f"no published peaks for device kind {kind!r}; add "
                       "them to CHIP_PEAKS with their source")
    return CHIP_PEAKS[kind]

_COLL_FACTOR = {"all-gather": 1.0, "all-reduce": 2.0, "reduce-scatter": 1.0,
                "all-to-all": 1.0, "collective-permute": 1.0,
                "collective-broadcast": 1.0}


def model_flops_per_device(rec: dict) -> float:
    arch = configs.get(rec["arch"])
    shape = configs.SHAPES[rec["shape"]]
    n_dev = rec["n_devices"]
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * arch.active_param_count() * tokens / n_dev
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
    else:
        tokens = shape.global_batch        # one new token per sequence
    return 2.0 * arch.active_param_count() * tokens / n_dev


def analytic_min_bytes_per_device(rec: dict) -> float:
    """Lower bound on per-device HBM traffic for the step: weights touched
    + KV/state sweep + minimal activation I/O. The gap to the HLO-derived
    bytes is an upper bound on lowering waste + CPU-backend f32 artifacts
    (EXPERIMENTS.md §Dry-run notes)."""
    arch = configs.get(rec["arch"])
    shape = configs.SHAPES[rec["shape"]]
    n_dev = rec["n_devices"]
    d = arch.d_model
    weights = 2.0 * arch.param_count()            # bf16
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode"
                                   else 1)
    if shape.kind == "train":
        # f32 params: fwd read + bwd read + update RW, grads RW, act IO
        base = 4.0 * arch.param_count() * 5
        act = 2.0 * 2 * 6 * arch.n_layers * tokens * d
        return (base + act) / n_dev
    # serving: KV sweep per new token
    kv = 0.0
    if arch.has_attention():
        per_tok_layers = []
        n_attn = (arch.n_layers if arch.family not in ("hybrid",)
                  else arch.n_layers // (arch.attn_period or arch.n_layers))
        for i in range(arch.n_layers if arch.family != "hybrid" else n_attn):
            w = arch.layer_window(i) if arch.family != "hybrid" else None
            per_tok_layers.append(min(shape.seq_len, w or shape.seq_len))
        kv_row = 2 * arch.n_kv_heads * arch.head_dim * 2  # k+v bf16
        kv = float(sum(per_tok_layers)) * kv_row * shape.global_batch
    if arch.ssm is not None:
        s = arch.ssm
        kv += (4.0 * arch.n_layers * shape.global_batch *
               s.n_heads(d) * s.head_dim * s.d_state)
    act = 2.0 * 2 * 4 * arch.n_layers * tokens * d
    return (weights + kv + act) / n_dev


def analyze_record(rec: dict) -> dict:
    fl = rec.get("flops_corrected", rec.get("flops_raw", 0.0))
    by = rec.get("bytes_corrected", rec.get("bytes_raw", 0.0))
    coll = rec.get("collectives_corrected", {})
    coll_bytes = sum(v["bytes"] * _COLL_FACTOR.get(k, 1.0)
                     for k, v in coll.items())
    t_c = fl / V5E["bf16_flops"]
    t_m_hlo = by / V5E["hbm_bytes_per_s"]
    t_m_min = analytic_min_bytes_per_device(rec) / V5E["hbm_bytes_per_s"]
    # memory term: analytic floor (HLO bytes from the CPU lowering carry
    # f32-artifact + fusion-operand overcounts; both reported)
    t_m = t_m_min
    t_x = coll_bytes / V5E["ici_link_bytes_per_s"]
    dom = max((t_c, "compute"), (t_m, "memory"), (t_x, "collective"))[1]
    mf = model_flops_per_device(rec)
    bound = max(t_c, t_m, t_x)
    # achievable bound for this cell = the larger of ideal compute & memory
    ideal = max(mf / V5E["bf16_flops"], t_m_min)
    return {
        "arch": rec["arch"], "shape": rec["shape"], "mesh": rec["mesh"],
        "compute_s": t_c, "memory_s": t_m, "memory_s_hlo": t_m_hlo,
        "collective_s": t_x,
        "dominant": dom,
        "model_flops_ratio": (mf / fl) if fl else 0.0,
        # fraction of roofline the lowering achieves (1.0 = at the bound)
        "roofline_frac": min(1.0, (ideal / bound) if bound > 0 else 0.0),
        "hbm_per_device_gib": rec.get("per_device_hbm_bytes", 0) / 2**30,
        "fits_16g": rec.get("fits_16g"),
        "notes": rec.get("notes", ""),
    }


def suggest(row: dict) -> str:
    d = row["dominant"]
    if d == "compute" and row["model_flops_ratio"] < 0.6:
        return ("compute-bound with low useful-FLOP ratio: cut redundant "
                "compute (MoE capacity slack / remat recompute)")
    if d == "compute":
        return "near compute roofline: gains need lower-precision or sparsity"
    if d == "memory":
        return ("HBM-bound: shrink bytes/step — KV dtype, layout fusion, "
                "larger per-step batch to amortize weight reads")
    return ("collective-bound: reshard to cut cross-chip traffic or overlap "
            "collectives with compute")


def run(quick: bool = True, mesh: str = "pod_16x16",
        out_md: str = "experiments/roofline.md") -> list[dict]:
    rows = []
    for path in sorted(glob.glob(f"experiments/dryrun/{mesh}/*.json")):
        rec = json.load(open(path))
        if rec.get("status") != "ok":
            continue
        r = analyze_record(rec)
        r["bench"] = "roofline"
        r["hint"] = suggest(r)
        rows.append(r)
    if rows and out_md:
        os.makedirs(os.path.dirname(out_md), exist_ok=True)
        with open(out_md, "w") as f:
            f.write("| arch | shape | compute s | memory s (floor) | "
                    "memory s (HLO) | collective s | dominant | MODEL/HLO | "
                    "roofline frac | HBM GiB | hint |\n")
            f.write("|---|---|---|---|---|---|---|---|---|---|---|\n")
            for r in rows:
                f.write(f"| {r['arch']} | {r['shape']} | {r['compute_s']:.3e} "
                        f"| {r['memory_s']:.3e} | {r['memory_s_hlo']:.3e} "
                        f"| {r['collective_s']:.3e} "
                        f"| {r['dominant']} | {r['model_flops_ratio']:.2f} "
                        f"| {r['roofline_frac']:.2f} "
                        f"| {r['hbm_per_device_gib']:.1f} "
                        f"| {r['hint']} |\n")
    return rows
