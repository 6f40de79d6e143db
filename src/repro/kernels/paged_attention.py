"""Ragged paged attention — Pallas TPU kernel (flash-decoding style).

One kernel serves both FairBatching step item types:
  * decode        — Tq = 1, many sequences per launch
  * prefill chunk — Tq = chunk (chunked prefill continuation against the
                    cached prefix; the chunk's own K/V are already written
                    into the page pool by the executor)

Layout/TPU adaptation (DESIGN.md §3): the KV cache lives in HBM as 128-token
pages, one pool per layer laid out ``(P, Hkv, D, page)``: one page of one kv
head is a ``(D, page)`` tile with the page's tokens on the lanes. The TPU
compiler requires a block's two minor dims to be 8- and 128-aligned or
whole, and the pool's own tiled layout pads neither (D = 80 would pad to
128 lanes in a ``(page, D)`` tile, and XLA would copy the pool into that
layout around every launch). The block table rides scalar-prefetch so each
grid step's BlockSpec index_map resolves its page id and the DMA pipeline
streams page tiles HBM→VMEM. Online softmax accumulates in f32 VMEM scratch
across the page axis of the grid (sequential on TPU); the GQA query heads
of one KV head are packed token-major into the sublane dim outside the
kernel, so the MXU sees (Tq·G, D) × (D, page) tiles with no in-kernel
reshape.

Block-shape tuning (DESIGN.md §14): the kernel's grid is tiled by
``(pages_per_block, q_block)`` — how many KV pages stream through VMEM per
grid step, and how many packed query tokens each output tile covers. The
analytic autotuner (benchmarks/autotune_attention.py, roofline + HLO byte
model) sweeps the candidates per (token-bucket, pages-bucket) and records
winners in the module registry below; the executor consults it per compile
key. Untuned buckets take one page per grid step and ``DEFAULT_Q_TILE``.

Quantized variant (DESIGN.md §14): ``paged_attention_ragged_quant`` reads
int8/fp8 value pages plus per-(token, kv-head) f32 scale pages laid out
``(Ps, Hkv, page)`` and dequantizes inside the kernel, after the DMA and
before the softmax — HBM traffic is the quantized byte count.

The batched ``paged_attention`` (B sequences × Tq rows) is the same kernel
over a stream in which every sequence owns Tq rows.

Oracle: ref.paged_attention_ref / ref.paged_attention_ragged_quant_ref.
Validated with interpret=True over shape/dtype sweeps in
tests/test_kernels.py, and compiled for a described TPU v5e at published
widths in tests/test_chip_compile.py.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# query tokens per tile when the caller gives no q_block (see
# _resolve_tiling); a multiple of 8 rows for any GQA group size
DEFAULT_Q_TILE = 128

# autotuned (pages_per_block, q_block) per (mesh_key, t_bucket,
# pages_bucket) — filled by benchmarks/autotune_attention.py
# (set_ragged_tilings); (1, None) = the default grid. Keying by mesh
# shape matters under TP (DESIGN.md §17): a sharded kernel sees
# n_kv_heads/TP head groups and per-shard VMEM working sets, so a winner
# tuned single-device is NOT a winner for the sharded launch — lookups for
# an untuned mesh fall back to the safe default instead of silently reusing
# single-device tilings.
_TUNED_TILINGS: dict[tuple, tuple[int, Optional[int]]] = {}


def mesh_tiling_key(mesh) -> Optional[tuple]:
    """Registry key for a mesh (or None = single-device): the ordered
    (axis_name, size) shape — what actually changes the per-shard kernel
    footprint — not device identities."""
    if mesh is None:
        return None
    return tuple((name, int(size)) for name, size
                 in zip(mesh.axis_names, mesh.devices.shape))


def set_ragged_tilings(table: dict, mesh=None) -> None:
    """Install autotuned tilings {(t_bucket, pages_bucket): (kb, tb)} for
    one mesh shape (None = single-device). Other meshes' entries persist —
    each shard shape is tuned and cleared independently."""
    mk = mesh if (mesh is None or isinstance(mesh, tuple)) \
        else mesh_tiling_key(mesh)
    for key in [k for k in _TUNED_TILINGS if k[0] == mk]:
        del _TUNED_TILINGS[key]
    for key, val in table.items():
        t, n_pages = key
        kb, tb = val
        _TUNED_TILINGS[(mk, int(t), int(n_pages))] = (
            int(kb), None if tb is None else int(tb))


def get_ragged_tiling(t_bucket: int, pages_bucket: int,
                      mesh=None) -> tuple[int, Optional[int]]:
    """(pages_per_block, q_block) for a bucket on a mesh shape; (1, None)
    when that mesh shape is untuned (no cross-mesh fallback)."""
    mk = mesh if (mesh is None or isinstance(mesh, tuple)) \
        else mesh_tiling_key(mesh)
    return _TUNED_TILINGS.get((mk, int(t_bucket), int(pages_bucket)),
                              (1, None))


def _resolve_tiling(t: int, n_pages: int, pages_per_block: Optional[int],
                    q_block: Optional[int]) -> tuple[int, int]:
    """Clamp the requested (kb, tb) to the launch's shape. ``tb`` must tile
    the stream exactly (Pallas blocks are uniform) — a non-divisor falls
    back to the untiled ``tb = t``. ``q_block=None`` tiles a stream at
    ``DEFAULT_Q_TILE`` tokens when that divides it: the untiled query,
    output and softmax-scratch blocks of a multi-thousand-token prefill
    step would not fit the chip's scoped VMEM."""
    kb = max(1, min(int(pages_per_block or 1), n_pages))
    if q_block is None:
        q_block = DEFAULT_Q_TILE if t % DEFAULT_Q_TILE == 0 else t
    tb = max(1, min(int(q_block), t))
    if t % tb:
        tb = t
    return kb, tb


def _ragged_kernel(block_tables, context_lens, q_starts, q_lens, pos0,
                   *refs, page: int, kb: int, n_pb: int, n_seq: int, tb: int,
                   g: int, window: Optional[int], scale: float, quant: bool):
    """Tiled ragged kernel body (fp32 and quantized).

    Grid (kv_head, q_block, seq, page_block): each grid step streams ``kb``
    pages (as ``kb`` separate scalar-prefetch-indexed tiles of the same page
    pool) against one ``tb``-token query tile, whose ``tb·g`` rows hold the
    ``g`` query heads of this kv head token-major; online-softmax scratch
    spans the query tile and persists across the (seq, page_block) inner
    loops. When ``quant`` the page tiles are int8/fp8 and each page's
    (Hkv, page) f32 scale tile rides along: a key's scale multiplies its
    score column and a value's scale its probability column, post-DMA.
    """
    hk = pl.program_id(0)
    qb_idx = pl.program_id(1)
    s_idx = pl.program_id(2)
    pb_idx = pl.program_id(3)

    if quant:
        refs = refs[1:]               # scale_tables: read by index maps only
    q_ref = refs[0]
    k_refs = refs[1:1 + kb]
    v_refs = refs[1 + kb:1 + 2 * kb]
    if quant:
        ks_refs = refs[1 + 2 * kb:1 + 3 * kb]
        vs_refs = refs[1 + 3 * kb:1 + 4 * kb]
        o_ref, m_s, l_s, acc_s = refs[1 + 4 * kb:]
    else:
        o_ref, m_s, l_s, acc_s = refs[1 + 2 * kb:]

    @pl.when((s_idx == 0) & (pb_idx == 0))
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    # early-skip: pad sequences (q_lens == 0), sequences whose packed rows
    # miss this query tile, and page blocks past the sequence's context
    # contribute nothing — their DMA'd tiles are never touched
    row0 = qb_idx * tb
    overlap = ((row0 < q_starts[s_idx] + q_lens[s_idx])
               & (row0 + tb > q_starts[s_idx]))

    @pl.when((q_lens[s_idx] > 0) & overlap
             & (pb_idx * kb * page < context_lens[s_idx]))
    def _compute():
        q = q_ref[0].astype(jnp.float32)                  # (tb·g, D)
        for j in range(kb):
            k = k_refs[j][0, 0].astype(jnp.float32)       # (D, page)
            v = v_refs[j][0, 0].astype(jnp.float32)

            s = jax.lax.dot_general(q, k, (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
            if quant:
                s = s * ks_refs[j][0, pl.ds(hk, 1), :]    # (1, page)

            tok = (row0 + jax.lax.broadcasted_iota(
                jnp.int32, (tb * g, page), 0) // g)
            kv_pos = ((pb_idx * kb + j) * page
                      + jax.lax.broadcasted_iota(jnp.int32, (tb * g, page), 1))
            q_pos = pos0[s_idx] + tok - q_starts[s_idx]
            mask = ((tok >= q_starts[s_idx])
                    & (tok < q_starts[s_idx] + q_lens[s_idx])
                    & (kv_pos < context_lens[s_idx]) & (kv_pos <= q_pos))
            if window is not None:
                mask &= (q_pos - kv_pos) < window
            s = jnp.where(mask, s, NEG_INF)

            m_prev = m_s[...]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            p = jnp.where(mask, p, 0.0)
            l_s[...] = l_s[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
            if quant:
                p = p * vs_refs[j][0, pl.ds(hk, 1), :]
            acc_s[...] = acc_s[...] * alpha + jax.lax.dot_general(
                p, v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_s[...] = m_new

    @pl.when((s_idx == n_seq - 1) & (pb_idx == n_pb - 1))
    def _flush():
        out = acc_s[...] / jnp.maximum(l_s[...], 1e-30)
        o_ref[0] = out.astype(o_ref.dtype)


def _ragged_call(q, k_pages, v_pages, scale_pages, block_tables,
                 scale_tables, context_lens, q_starts, q_lens, pos0, *,
                 window, scale, pages_per_block, q_block, interpret):
    """Launch the ragged kernel; ``scale_pages`` is None for fp32 pages or
    the (k_scales, v_scales) pair of (Ps, Hkv, page) f32 scale pools."""
    t, h, d = q.shape
    n_seq, n_pages = block_tables.shape
    _, hkv, _, page = k_pages.shape
    g = h // hkv
    scale = scale if scale is not None else d ** -0.5
    quant = scale_pages is not None
    kb, tb = _resolve_tiling(t, n_pages, pages_per_block, q_block)
    n_pb = -(-n_pages // kb)
    n_qb = t // tb
    if n_pb * kb != n_pages:   # pad table columns; masked past context
        pad = ((0, 0), (0, n_pb * kb - n_pages))
        block_tables = jnp.pad(block_tables, pad)
        if quant:
            scale_tables = jnp.pad(scale_tables, pad)
    # (T, H, D) → (Hkv, T·g, D): one kv head's g query heads packed
    # token-major, so a query tile is a plain (tb·g, D) block
    qr = q.reshape(t, hkv, g, d).transpose(1, 0, 2, 3).reshape(hkv, t * g, d)

    def _page_spec(j):
        return pl.BlockSpec((1, 1, d, page),
                            lambda hk, qb, s, pb, bt, *_, j=j:
                                (bt[s, pb * kb + j], hk, 0, 0))

    def _scale_spec(j):
        # the whole (Hkv, page) scale page: a (1, page) block would leave
        # the second-minor dim neither 8-aligned nor whole
        return pl.BlockSpec((1, hkv, page),
                            lambda hk, qb, s, pb, bt, cl, qs, ql, p0, st,
                            j=j: (st[s, pb * kb + j], 0, 0))

    q_spec = pl.BlockSpec((1, tb * g, d), lambda hk, qb, *_: (hk, qb, 0))
    in_specs = ([q_spec] + [_page_spec(j) for j in range(kb)]
                + [_page_spec(j) for j in range(kb)])
    operands = [qr] + [k_pages] * kb + [v_pages] * kb
    prefetch = [block_tables, context_lens, q_starts, q_lens, pos0]
    if quant:
        in_specs += ([_scale_spec(j) for j in range(kb)]
                     + [_scale_spec(j) for j in range(kb)])
        operands += [scale_pages[0]] * kb + [scale_pages[1]] * kb
        prefetch.append(scale_tables)
    kernel = functools.partial(_ragged_kernel, page=page, kb=kb, n_pb=n_pb,
                               n_seq=n_seq, tb=tb, g=g, window=window,
                               scale=scale, quant=quant)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(hkv, n_qb, n_seq, n_pb),
            in_specs=in_specs,
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((tb * g, 1), jnp.float32),
                pltpu.VMEM((tb * g, 1), jnp.float32),
                pltpu.VMEM((tb * g, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((hkv, t * g, d), q.dtype),
        name="paged_attention_ragged",
        interpret=interpret,
    )(*prefetch, *operands)
    return out.reshape(hkv, t, g, d).transpose(1, 0, 2, 3).reshape(t, h, d)


def paged_attention_ragged(q, k_pages, v_pages, block_tables, context_lens,
                           q_starts, q_lens, pos0,
                           *, window: Optional[int] = None,
                           scale: Optional[float] = None,
                           pages_per_block: Optional[int] = None,
                           q_block: Optional[int] = None,
                           interpret: bool = False) -> jnp.ndarray:
    """Token-packed ragged paged attention: one launch for the whole hybrid
    step (DESIGN.md §11). q: (T, H, D) packed stream; pages: (P, Hkv, D,
    page); block_tables: (S, n_pages); context_lens/q_starts/q_lens/
    pos0: (S,). Returns (T, H, D).

    Grid is (kv_head, q_block, seq, page_block): the online-softmax scratch
    spans one ``q_block`` query tile and each (seq, page_block) step masks
    to the rows the sequence owns; pages beyond a sequence's context, pad
    sequences, and non-overlapping query tiles early-skip. The
    (pages_per_block, q_block) tiling is the autotuned axis (DESIGN.md §14).
    """
    return _ragged_call(q, k_pages, v_pages, None, block_tables, None,
                        context_lens, q_starts, q_lens, pos0, window=window,
                        scale=scale, pages_per_block=pages_per_block,
                        q_block=q_block, interpret=interpret)


def paged_attention_ragged_quant(q, k_pages, v_pages, k_scales, v_scales,
                                 block_tables, scale_tables, context_lens,
                                 q_starts, q_lens, pos0,
                                 *, window: Optional[int] = None,
                                 scale: Optional[float] = None,
                                 pages_per_block: Optional[int] = None,
                                 q_block: Optional[int] = None,
                                 interpret: bool = False) -> jnp.ndarray:
    """Quantized-KV ragged paged attention (DESIGN.md §14).

    Same contract as ``paged_attention_ragged`` plus: k_pages/v_pages hold
    int8 (or fp8-e4m3) values, k_scales/v_scales: (Ps, Hkv, page) f32 scale
    pages, scale_tables: (S, n_pages) scale-page ids parallel to
    block_tables (``BlockAllocator.scale_table``). Dequantization happens
    inside the kernel after the DMA — HBM reads stay at quantized width.
    """
    return _ragged_call(q, k_pages, v_pages, (k_scales, v_scales),
                        block_tables, scale_tables, context_lens, q_starts,
                        q_lens, pos0, window=window, scale=scale,
                        pages_per_block=pages_per_block, q_block=q_block,
                        interpret=interpret)


def paged_attention(q, k_pages, v_pages, block_table, context_lens, q_starts,
                    *, window: Optional[int] = None,
                    scale: Optional[float] = None,
                    interpret: bool = False) -> jnp.ndarray:
    """Batched paged attention (decode Tq=1 / prefill chunk Tq=chunk) as a
    ragged launch in which each of the B sequences owns Tq consecutive rows.
    q: (B, Tq, H, D); pages: (P, Hkv, D, page); block_table: (B, n_pages);
    context_lens, q_starts: (B,), q_starts the global position of q[:, 0].
    Returns (B, Tq, H, D)."""
    b, tq, h, d = q.shape
    out = paged_attention_ragged(
        q.reshape(b * tq, h, d), k_pages, v_pages, block_table, context_lens,
        jnp.arange(b, dtype=jnp.int32) * tq, jnp.full((b,), tq, jnp.int32),
        q_starts, window=window, scale=scale, interpret=interpret)
    return out.reshape(b, tq, h, d)
