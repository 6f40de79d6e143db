"""Pure-jnp oracles for every Pallas kernel in this package.

These are the ground truth the kernels' interpret-mode runs are swept
against (tests/test_kernels.py), and what the CPU test runs compute in the
kernels' place (kernels/ops.py). A layer's page pool is laid out
(P, Hkv, D, page) and its scale pool (P, Hkv, page), as the kernels read
them.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def paged_gather(pages: jnp.ndarray, block_table: jnp.ndarray) -> jnp.ndarray:
    """pages: (P, Hkv, D, page); block_table: (B, n_pages) → (B, n_pages*page, Hkv, D)."""
    g = jnp.moveaxis(pages[block_table], -1, 2)  # (B, n_pages, page, Hkv, D)
    b, n, p, h, d = g.shape
    return g.reshape(b, n * p, h, d)


def paged_gather_scales(scale_pages: jnp.ndarray,
                        scale_table: jnp.ndarray) -> jnp.ndarray:
    """scale_pages: (P, Hkv, page); scale_table: (B, n_pages)
    → (B, n_pages*page, Hkv). The scale-row companion of ``paged_gather``
    (DESIGN.md §14)."""
    g = jnp.moveaxis(scale_pages[scale_table], -1, 2)  # (B, n_pages, page, Hkv)
    b, n, p, h = g.shape
    return g.reshape(b, n * p, h)


def _attend_gathered(q, k, v, context_lens, q_starts, *, window, scale):
    """Core masked-softmax attention over already-gathered per-seq KV.

    q: (B, Tq, H, D); k/v: (B, L, Hkv, D) f32 gathered context. Shared by
    the fp32 and the dequantizing quantized oracles so both run the *same*
    math — the quant refs differ only in how k/v were materialized.
    """
    b, tq, h, d = q.shape
    hkv = k.shape[2]
    g = h // hkv
    s_len = k.shape[1]
    kv_pos = jnp.arange(s_len)[None, :]                     # (1, S)
    q_pos = q_starts[:, None] + jnp.arange(tq)[None, :]     # (B, Tq)
    valid = kv_pos < context_lens[:, None]
    mask = valid[:, None, :] & (kv_pos[:, None, :] <= q_pos[..., None])
    if window is not None:
        mask &= (q_pos[..., None] - kv_pos[:, None, :]) < window
    qf = q.reshape(b, tq, hkv, g, d).astype(jnp.float32)
    s = jnp.einsum("bthgd,bshd->bhgts", qf,
                   k.astype(jnp.float32)) * scale
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(mask[:, None, None], p, 0.0)
    o = jnp.einsum("bhgts,bshd->bthgd", p, v.astype(jnp.float32))
    return o.reshape(b, tq, h, d).astype(q.dtype)


def paged_attention_ref(q, k_pages, v_pages, block_table, context_lens,
                        q_starts, *, window: Optional[int] = None,
                        scale: Optional[float] = None) -> jnp.ndarray:
    """Reference ragged paged attention (decode AND chunked prefill).

    q: (B, Tq, H, D)       — Tq = 1 for decode, = chunk for prefill chunks
    k_pages/v_pages: (P, Hkv, D, page)
    block_table: (B, n_pages) int32 — page ids per sequence
    context_lens: (B,) int32 — total tokens in cache (incl. current chunk)
    q_starts: (B,) int32 — global position of q[:, 0]
    """
    d = q.shape[-1]
    scale = scale if scale is not None else d ** -0.5
    k = paged_gather(k_pages, block_table)  # (B, L, Hkv, D)
    v = paged_gather(v_pages, block_table)
    return _attend_gathered(q, k, v, context_lens, q_starts,
                            window=window, scale=scale)


def paged_attention_quant_ref(q, k_pages, v_pages, k_scales, v_scales,
                              block_table, scale_table, context_lens,
                              q_starts, *, window: Optional[int] = None,
                              scale: Optional[float] = None) -> jnp.ndarray:
    """Quantized-KV oracle (DESIGN.md §14): dequantize the gathered context
    with per-(token, kv-head) scales, then run the exact fp32 reference math.

    k_pages/v_pages: (P, Hkv, D, page) int8/fp8; k_scales/v_scales:
    (Ps, Hkv, page) f32 scale pages; scale_table: (B, n_pages) parallel to
    block_table (``BlockAllocator.scale_table``).
    """
    d = q.shape[-1]
    scale = scale if scale is not None else d ** -0.5
    k = (paged_gather(k_pages, block_table).astype(jnp.float32)
         * paged_gather_scales(k_scales, scale_table)[..., None])
    v = (paged_gather(v_pages, block_table).astype(jnp.float32)
         * paged_gather_scales(v_scales, scale_table)[..., None])
    return _attend_gathered(q, k, v, context_lens, q_starts,
                            window=window, scale=scale)


def paged_attention_ragged_ref(q, k_pages, v_pages, block_tables,
                               context_lens, q_starts, q_lens, pos0,
                               *, window: Optional[int] = None,
                               scale: Optional[float] = None) -> jnp.ndarray:
    """Reference token-packed ragged paged attention (fused hybrid step).

    One packed query stream carries every sequence of the step — prefill
    chunks and decode tokens alike (DESIGN.md §11):

    q: (T, H, D)           — packed stream; seq s owns rows
                             [q_starts[s], q_starts[s] + q_lens[s])
    k_pages/v_pages: (P, Hkv, D, page)
    block_tables: (S, n_pages) int32 — page ids per sequence
    context_lens: (S,) int32 — tokens in cache incl. this step's (0 = pad seq)
    q_starts: (S,) int32 — packed-stream offset of each sequence
    q_lens: (S,) int32   — query tokens per sequence (0 = pad seq)
    pos0: (S,) int32     — global position of each sequence's first query

    Rows not owned by any sequence (stream padding) return zeros.
    """
    d = q.shape[-1]
    scale = scale if scale is not None else d ** -0.5
    k = paged_gather(k_pages, block_tables)                # (S, L, Hkv, D)
    v = paged_gather(v_pages, block_tables)
    return _attend_ragged_gathered(q, k, v, context_lens, q_starts, q_lens,
                                   pos0, window=window, scale=scale)


def paged_attention_ragged_quant_ref(q, k_pages, v_pages, k_scales, v_scales,
                                     block_tables, scale_tables, context_lens,
                                     q_starts, q_lens, pos0,
                                     *, window: Optional[int] = None,
                                     scale: Optional[float] = None
                                     ) -> jnp.ndarray:
    """Quantized token-packed ragged oracle (DESIGN.md §14): dequantize each
    sequence's gathered context with its scale pages, then run the exact
    fp32 ragged reference math. scale_tables: (S, n_pages) parallel to
    block_tables."""
    d = q.shape[-1]
    scale = scale if scale is not None else d ** -0.5
    k = (paged_gather(k_pages, block_tables).astype(jnp.float32)
         * paged_gather_scales(k_scales, scale_tables)[..., None])
    v = (paged_gather(v_pages, block_tables).astype(jnp.float32)
         * paged_gather_scales(v_scales, scale_tables)[..., None])
    return _attend_ragged_gathered(q, k, v, context_lens, q_starts, q_lens,
                                   pos0, window=window, scale=scale)


def _attend_ragged_gathered(q, k, v, context_lens, q_starts, q_lens, pos0,
                            *, window, scale):
    """Ragged attention core over per-sequence gathered KV (S, L, Hkv, D) —
    shared by the fp32 and quantized oracles (same math, same rounding)."""
    t, h, d = q.shape
    hkv = k.shape[2]
    g = h // hkv
    tok = jnp.arange(t)
    owns = ((tok[None, :] >= q_starts[:, None])
            & (tok[None, :] < (q_starts + q_lens)[:, None]))    # (S, T)
    token_seq = jnp.argmax(owns, axis=0)                        # (T,)
    owned = jnp.any(owns, axis=0)                               # (T,)
    k = k[token_seq]                                       # (T, L, Hkv, D)
    v = v[token_seq]
    s_len = k.shape[1]
    q_pos = pos0[token_seq] + tok - q_starts[token_seq]         # (T,)
    kv_pos = jnp.arange(s_len)[None, :]                         # (1, L)
    mask = (owned[:, None]
            & (kv_pos < context_lens[token_seq][:, None])
            & (kv_pos <= q_pos[:, None]))
    if window is not None:
        mask &= (q_pos[:, None] - kv_pos) < window
    qf = q.reshape(t, hkv, g, d).astype(jnp.float32)
    s = jnp.einsum("thgd,tlhd->thgl", qf, k.astype(jnp.float32)) * scale
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(mask[:, None, None], p, 0.0)
    o = jnp.einsum("thgl,tlhd->thgd", p, v.astype(jnp.float32))
    return o.reshape(t, h, d).astype(q.dtype)


def moe_gmm_ref(x_groups: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """Batched expert GEMM: (E, C, K) × (E, K, N) → (E, C, N)."""
    return jnp.einsum("eck,ekn->ecn", x_groups.astype(jnp.float32),
                      w.astype(jnp.float32)).astype(x_groups.dtype)


def mamba_chunk_scan_ref(xdt, a_dt, b, c, init_state=None):
    """SSD over pre-chunked inputs.

    xdt: (B, NC, L, H, P); a_dt: (B, NC, L, H); b, c: (B, NC, L, N).
    Returns (y (B,NC,L,H,P), final_state (B,H,P,N)). Same math as
    models/mamba2.ssd_chunked (which is itself validated against stepwise
    recurrence)."""
    from ..models.mamba2 import ssd_chunked
    bsz, nc, l, h, p = xdt.shape
    y, st = ssd_chunked(xdt.reshape(bsz, nc * l, h, p),
                        a_dt.reshape(bsz, nc * l, h),
                        b.reshape(bsz, nc * l, -1),
                        c.reshape(bsz, nc * l, -1), l, init_state)
    return y.reshape(bsz, nc, l, h, p), st
