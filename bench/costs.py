"""Operations and bytes that the served work needs, computed from shapes.

A step is a list of packed sequences, each ``(pos0, n, ctx)``: ``n`` new
tokens at positions ``pos0 .. pos0 + n - 1``, attending a context of ``ctx``
tokens (``ctx == pos0 + n``). These functions count only real tokens: the
padding a bucket adds is not work the model needs.
"""
from __future__ import annotations

from lm import Shape


def attended_keys(pos0: int, n: int, window: int | None) -> int:
    """Sum over the n query positions of the keys each attends (causal,
    within the sliding window)."""
    total = 0
    for p in range(pos0, pos0 + n):
        total += p + 1 if window is None else min(p + 1, window)
    return total


def attention_flops(s: Shape, seqs) -> float:
    """QK^T and PV of every layer: 2 x 2 flops per (query head, key, dim)."""
    keys = sum(attended_keys(p0, n, s.window) for p0, n, _ in seqs)
    return 4.0 * s.n_heads * s.head_dim * keys * s.n_layers


def attention_bytes(s: Shape, seqs, kv_bytes: int = 4,
                    act_bytes: int = 4) -> float:
    """Least HBM traffic of the attention kernel over every layer: each
    sequence's K and V rows that some query attends, read once, plus the
    queries read and the outputs written."""
    total = 0.0
    for p0, n, _ in seqs:
        lo = 0 if s.window is None else max(0, p0 + 1 - s.window)
        rows = p0 + n - lo
        total += 2 * rows * s.kv_dim * kv_bytes
        total += 2 * n * s.q_dim * act_bytes
    return total * s.n_layers


def step_flops(s: Shape, seqs) -> float:
    """Model flops of one fused step: the per-layer matmuls of every real
    token, attention, and the head for each sequence's last token."""
    tokens = sum(n for _, n, _ in seqs)
    matmul = 2.0 * s.layer_matmul_params() * s.n_layers * tokens
    head = 2.0 * s.d_model * s.vocab * len(seqs)
    return matmul + head + attention_flops(s, seqs)


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    """The least time the chip could take, and which peak bounds it."""
    tc = flops / peaks["flops_per_s"]
    tm = nbytes / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
