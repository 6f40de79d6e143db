"""Pallas kernels vs jnp oracles — interpret=True shape/dtype sweeps."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import quant as kvq
from repro.kernels.mamba2_scan import mamba_chunk_scan
from repro.kernels.moe_gmm import moe_gmm
from repro.kernels.paged_attention import (paged_attention,
                                           paged_attention_ragged,
                                           paged_attention_ragged_quant)
from repro.kernels.ref import (mamba_chunk_scan_ref, moe_gmm_ref,
                               paged_attention_ragged_quant_ref,
                               paged_attention_ragged_ref, paged_attention_ref)

KEY = jax.random.PRNGKey(0)


def _tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 2e-4


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,Tq,H,Hkv,D,page,n_pages,window",
    [
        (2, 1, 4, 2, 32, 16, 3, None),       # decode
        (3, 1, 8, 1, 64, 32, 4, None),       # MQA decode
        (1, 16, 4, 4, 32, 16, 4, None),      # prefill chunk, MHA
        (2, 8, 8, 2, 16, 8, 5, 12),          # SWA chunk
        (2, 1, 4, 2, 128, 128, 2, 64),       # TPU-aligned page/D
    ])
def test_paged_attention_sweep(B, Tq, H, Hkv, D, page, n_pages, window, dtype):
    P = n_pages * 2 + 1
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (B, Tq, H, D)).astype(dtype)
    kp = jax.random.normal(ks[1], (P, Hkv, D, page)).astype(dtype)
    vp = jax.random.normal(ks[2], (P, Hkv, D, page)).astype(dtype)
    bt = jax.random.randint(ks[3], (B, n_pages), 0, P)
    total = page * n_pages
    ctx = jnp.asarray([(total * (i + 1)) // (B + 1) + Tq for i in range(B)],
                      jnp.int32)
    ctx = jnp.minimum(ctx, total)
    qs = ctx - Tq
    out = paged_attention(q, kp, vp, bt, ctx, qs, window=window,
                          interpret=True)
    expect = paged_attention_ref(q, kp, vp, bt, ctx, qs, window=window)
    err = float(jnp.abs(out.astype(jnp.float32)
                        - expect.astype(jnp.float32)).max())
    assert err < _tol(dtype), f"err={err}"


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("E,C,K,N,bc,bn,bk", [
    (2, 32, 32, 32, 32, 32, 32),
    (4, 64, 96, 128, 32, 64, 32),
    (1, 128, 128, 128, 128, 128, 128),   # single full MXU tile
    (8, 16, 48, 64, 16, 64, 16),
])
def test_moe_gmm_sweep(E, C, K, N, bc, bn, bk, dtype):
    ks = jax.random.split(KEY, 2)
    x = (jax.random.normal(ks[0], (E, C, K)) * 0.3).astype(dtype)
    w = (jax.random.normal(ks[1], (E, K, N)) * 0.3).astype(dtype)
    out = moe_gmm(x, w, bc=bc, bn=bn, bk=bk, interpret=True)
    expect = moe_gmm_ref(x, w)
    err = float(jnp.abs(out.astype(jnp.float32)
                        - expect.astype(jnp.float32)).max())
    assert err < _tol(dtype) * K ** 0.5, f"err={err}"


@pytest.mark.parametrize("B,NC,L,H,P,N", [
    (1, 2, 8, 2, 8, 8),
    (2, 3, 16, 4, 16, 8),
    (2, 4, 32, 2, 32, 16),
])
def test_mamba_chunk_scan_sweep(B, NC, L, H, P, N):
    ks = jax.random.split(KEY, 4)
    xdt = jax.random.normal(ks[0], (B, NC, L, H, P)) * 0.3
    a = -jnp.abs(jax.random.normal(ks[1], (B, NC, L, H))) * 0.1
    bm = jax.random.normal(ks[2], (B, NC, L, N)) * 0.3
    cm = jax.random.normal(ks[3], (B, NC, L, N)) * 0.3
    y, st = mamba_chunk_scan(xdt, a, bm, cm, interpret=True)
    yr, str_ = mamba_chunk_scan_ref(xdt, a, bm, cm)
    assert float(jnp.abs(y - yr).max()) < 1e-4
    assert float(jnp.abs(jnp.moveaxis(st, -2, -1) - str_).max()) < 1e-4


def _packed_layout(q_lens, gap=0):
    """(q_starts, q_lens, T) for a packed stream with `gap` pad tokens at
    the end of the stream (and between nothing — packing is contiguous)."""
    q_starts, off = [], 0
    for n in q_lens:
        q_starts.append(off)
        off += n
    return q_starts, off + gap


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "q_lens,pos0,H,Hkv,D,page,n_pages,window",
    [
        ([5, 1, 3], [10, 20, 0], 4, 2, 32, 16, 3, None),   # mixed chunk+decode
        ([1, 1, 1, 1], [7, 12, 0, 33], 8, 1, 64, 32, 2, None),  # all decode, MQA
        ([16], [8], 4, 4, 32, 16, 4, None),                # one prefill chunk
        ([8, 2, 1], [4, 9, 30], 8, 2, 16, 8, 5, 12),       # SWA mix
    ])
def test_paged_attention_ragged_sweep(q_lens, pos0, H, Hkv, D, page, n_pages,
                                      window, dtype):
    """Interpret-mode kernel vs ragged oracle vs per-sequence oracle."""
    P = n_pages * 2 + 1
    S = len(q_lens)
    q_starts, T = _packed_layout(q_lens, gap=3)
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (T, H, D)).astype(dtype)
    kp = jax.random.normal(ks[1], (P, Hkv, D, page)).astype(dtype)
    vp = jax.random.normal(ks[2], (P, Hkv, D, page)).astype(dtype)
    bt = jax.random.randint(ks[3], (S, n_pages), 0, P)
    ctx = jnp.asarray([p + n for p, n in zip(pos0, q_lens)], jnp.int32)
    ctx = jnp.minimum(ctx, page * n_pages)
    qs = jnp.asarray(q_starts, jnp.int32)
    ql = jnp.asarray(q_lens, jnp.int32)
    p0 = jnp.minimum(jnp.asarray(pos0, jnp.int32), ctx - ql)
    expect = paged_attention_ragged_ref(q, kp, vp, bt, ctx, qs, ql, p0,
                                        window=window)
    # the ragged oracle is the per-sequence oracle applied to each segment
    for s in range(S):
        lo, n = q_starts[s], q_lens[s]
        per_seq = paged_attention_ref(q[lo:lo + n][None], kp, vp, bt[s:s + 1],
                                      ctx[s:s + 1], p0[s:s + 1], window=window)
        err = float(jnp.abs(expect[lo:lo + n].astype(jnp.float32)
                            - per_seq[0].astype(jnp.float32)).max())
        assert err < _tol(dtype), f"seq {s}: err={err}"
    assert float(jnp.abs(expect[sum(q_lens):].astype(jnp.float32)).max()) == 0.0
    out = paged_attention_ragged(q, kp, vp, bt, ctx, qs, ql, p0,
                                 window=window, interpret=True)
    err = float(jnp.abs(out.astype(jnp.float32)
                        - expect.astype(jnp.float32)).max())
    assert err < _tol(dtype), f"err={err}"


def test_paged_attention_ragged_hypothesis_layouts():
    """Random ragged layouts (0 prefill / all decode / single-token chunks /
    empty pad sequences) agree with the per-sequence oracle."""
    hyp = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    page, n_pages, Hkv, G, D, T = 8, 4, 2, 2, 16, 32
    P = 9
    ks = jax.random.split(KEY, 3)
    kp = jax.random.normal(ks[0], (P, Hkv, D, page))
    vp = jax.random.normal(ks[1], (P, Hkv, D, page))
    q = jax.random.normal(ks[2], (T, Hkv * G, D))

    @st.composite
    def layouts(draw):
        n_seq = draw(st.integers(1, 5))
        q_lens, total = [], 0
        for _ in range(n_seq):
            n = draw(st.integers(0, min(9, T - total)))   # 0 = pad sequence
            q_lens.append(n)
            total += n
        pos0 = [draw(st.integers(0, page * n_pages - max(n, 1)))
                for n in q_lens]
        seed = draw(st.integers(0, 2 ** 16))
        return q_lens, pos0, seed

    @given(layouts())
    @settings(max_examples=25, deadline=None)
    def check(layout):
        q_lens, pos0, seed = layout
        S = len(q_lens)
        q_starts, _ = _packed_layout(q_lens)
        bt = jax.random.randint(jax.random.PRNGKey(seed), (S, n_pages), 0, P)
        ctx = jnp.asarray([p + n for p, n in zip(pos0, q_lens)], jnp.int32)
        out = paged_attention_ragged_ref(
            q, kp, vp, bt, ctx, jnp.asarray(q_starts, jnp.int32),
            jnp.asarray(q_lens, jnp.int32), jnp.asarray(pos0, jnp.int32))
        for s in range(S):
            lo, n = q_starts[s], q_lens[s]
            if n == 0:
                continue
            per_seq = paged_attention_ref(
                q[lo:lo + n][None], kp, vp, bt[s:s + 1], ctx[s:s + 1],
                jnp.asarray(pos0[s:s + 1], jnp.int32))
            assert np.allclose(out[lo:lo + n], per_seq[0], atol=1e-6), \
                f"seq {s} of {q_lens}"
        used = sum(q_lens)
        assert float(jnp.abs(out[used:]).max()) == 0.0

    check()


# ---------------------------------------------------------------------------
# quantized-KV numerics (DESIGN.md §14): derived-bound sweep vs fp32 oracle
# ---------------------------------------------------------------------------


def _quant_specs():
    """Every KV quantization format."""
    return [kvq.kv_quant_spec("int8"), kvq.kv_quant_spec("fp8_e4m3")]


def test_kv_quant_round_trip_bound():
    """|dequant(quant(x)) − x| ≤ ``row_error_bound`` elementwise — the §14
    bound everything downstream is derived from — and all-zero rows survive
    the scale floor without NaNs."""
    ks = jax.random.split(KEY, 2)
    # spread row magnitudes over several orders so per-row scaling matters
    x = jax.random.normal(ks[0], (64, 4, 32)) \
        * jnp.exp(2.0 * jax.random.normal(ks[1], (64, 4, 1)))
    for spec in _quant_specs():
        vals, scales = kvq.quantize_kv(x, spec)
        assert vals.dtype == spec.dtype and scales.dtype == jnp.float32
        err = jnp.abs(kvq.dequantize_kv(vals, scales) - x)
        bound = kvq.row_error_bound(x, spec)[..., None]
        worst = float(jnp.max(err - bound))
        assert worst <= 0.0, f"{spec.name}: bound violated by {worst}"
        v0, s0 = kvq.quantize_kv(jnp.zeros((3, 8)), spec)
        assert bool(jnp.all(kvq.dequantize_kv(v0, s0) == 0.0)), spec.name


def _quantize_pool(pages, spec):
    """Quantize a (P, Hkv, D, page) pool per (token, kv-head) row, as the
    executor's scatter does: values stay (P, Hkv, D, page), scales are
    (P, Hkv, page)."""
    vals, scales = kvq.quantize_kv(jnp.swapaxes(pages, -1, -2), spec)
    return jnp.swapaxes(vals, -1, -2), scales


def _quant_attention_tol(q, kp, vp, spec, *, scale):
    """Attention-output tolerance vs the fp32 oracle, derived from the
    quantization step size (DESIGN.md §14).

    Every k element is off by ≤ its row absmax × half_step, so each masked
    score moves by at most δ = scale · max‖q_row‖₁ · max|k| · half_step.
    Perturbing every softmax logit by ≤ δ rescales each probability within
    [e^{-2δ}, e^{2δ}]; since both distributions sum to 1 the total
    variation is ≤ e^{2δ} − 1, and the output (a convex combination of v
    rows, each itself off by ≤ max|v| × half_step) moves by at most
        (e^{2δ} − 1) · max|v| + max|v| · half_step.
    """
    q1 = float(jnp.max(jnp.sum(jnp.abs(q), axis=-1)))
    kmax = float(jnp.max(jnp.abs(kp)))
    vmax = float(jnp.max(jnp.abs(vp)))
    delta = scale * q1 * kmax * spec.half_step
    return (math.exp(2.0 * delta) - 1.0) * vmax + vmax * spec.half_step + 1e-6


def _quant_failure_triple(err, q_starts, q_lens, pos0, bt, page):
    """Map the worst output element to its (seq, head, page) triple — the
    §14 failure-report contract for the numerics sweep."""
    t, h, _ = np.unravel_index(int(jnp.argmax(err)), err.shape)
    seq = next((s for s in range(len(q_lens))
                if q_starts[s] <= t < q_starts[s] + q_lens[s]), None)
    if seq is None:
        return ("pad-row", int(h), None)
    q_pos = pos0[seq] + (t - q_starts[seq])
    return (seq, int(h), int(bt[seq][q_pos // page]))


def _quant_layout(q_lens, pos0, H, Hkv, D, page, n_pages, window, seed=0):
    """Build one quantized ragged-attention workload: fp32 originals plus
    their quantized pages/scales (scale tables alias the block tables —
    the kernels only require *parallel* id arrays, exactly what
    ``BlockAllocator.scale_table`` provides in production)."""
    P = n_pages * 2 + 1
    S = len(q_lens)
    q_starts, T = _packed_layout(q_lens, gap=3)
    ks = jax.random.split(jax.random.fold_in(KEY, seed), 4)
    q = jax.random.normal(ks[0], (T, H, D))
    kp = jax.random.normal(ks[1], (P, Hkv, D, page))
    vp = jax.random.normal(ks[2], (P, Hkv, D, page))
    bt = jax.random.randint(ks[3], (S, n_pages), 0, P)
    ctx = jnp.minimum(jnp.asarray([p + n for p, n in zip(pos0, q_lens)],
                                  jnp.int32), page * n_pages)
    args = (jnp.asarray(q_starts, jnp.int32), jnp.asarray(q_lens, jnp.int32),
            jnp.minimum(jnp.asarray(pos0, jnp.int32),
                        jnp.maximum(ctx - jnp.asarray(q_lens, jnp.int32), 0)))
    return q, kp, vp, bt, ctx, args, q_starts, T


# odd shapes (ISSUE 6 satellite): single-token decode rows, context lens on
# exact page boundaries, chunks starting at a boundary, empty prefill slots
QUANT_LAYOUTS = [
    ([1], [15], 4, 2, 32, 16, 2, None),               # ctx lands on a page end
    ([1, 1, 1], [15, 31, 7], 4, 2, 32, 16, 2, None),  # decode rows @ bounds
    ([5, 0, 1, 3], [10, 0, 20, 0], 4, 2, 32, 16, 3, None),  # empty slot
    ([16], [16], 8, 2, 16, 8, 5, 12),                 # boundary chunk, SWA
]


@pytest.mark.parametrize("q_lens,pos0,H,Hkv,D,page,n_pages,window",
                         QUANT_LAYOUTS)
def test_paged_attention_ragged_quant_sweep(q_lens, pos0, H, Hkv, D, page,
                                            n_pages, window):
    """Quantized ragged attention vs the fp32 oracle within the derived
    bound, and the interpret-mode Pallas kernel vs the quantized oracle at
    kernel tolerance. Failures report the offending (seq, head, page)."""
    for spec in _quant_specs():
        q, kp, vp, bt, ctx, (qs, ql, p0), q_starts, T = _quant_layout(
            q_lens, pos0, H, Hkv, D, page, n_pages, window)
        kq, ks_ = _quantize_pool(kp, spec)
        vq, vs_ = _quantize_pool(vp, spec)
        expect = paged_attention_ragged_ref(q, kp, vp, bt, ctx, qs, ql, p0,
                                            window=window)
        got = paged_attention_ragged_quant_ref(
            q, kq, vq, ks_, vs_, bt, bt, ctx, qs, ql, p0, window=window)
        tol = _quant_attention_tol(q, kp, vp, spec, scale=D ** -0.5)
        err = jnp.abs(got.astype(jnp.float32) - expect.astype(jnp.float32))
        assert float(err.max()) < tol, (
            f"{spec.name} vs fp32 oracle: err={float(err.max()):.3e} > "
            f"tol={tol:.3e} at (seq, head, page)="
            f"{_quant_failure_triple(err, q_starts, q_lens, pos0, bt, page)}")
        out = paged_attention_ragged_quant(
            q, kq, vq, ks_, vs_, bt, bt, ctx, qs, ql, p0, window=window,
            interpret=True)
        kerr = jnp.abs(out.astype(jnp.float32) - got.astype(jnp.float32))
        assert float(kerr.max()) < _tol(jnp.float32), (
            f"{spec.name} kernel vs quant oracle: err={float(kerr.max()):.3e}"
            f" at (seq, head, page)="
            f"{_quant_failure_triple(kerr, q_starts, q_lens, pos0, bt, page)}")


@pytest.mark.slow
@pytest.mark.parametrize("kb,tb", [(1, None), (2, None), (4, 1), (2, 4)])
def test_paged_attention_ragged_quant_tilings_slow(kb, tb):
    """Heavy half of the numerics sweep (CI slow step): the autotuner's
    (pages_per_block, q_block) tilings over every odd layout and format,
    including a non-divisor q_block that must fall back untiled."""
    for seed, (q_lens, pos0, H, Hkv, D, page, n_pages, window) in \
            enumerate(QUANT_LAYOUTS):
        for spec in _quant_specs():
            q, kp, vp, bt, ctx, (qs, ql, p0), q_starts, T = _quant_layout(
                q_lens, pos0, H, Hkv, D, page, n_pages, window, seed=seed)
            kq, ks_ = _quantize_pool(kp, spec)
            vq, vs_ = _quantize_pool(vp, spec)
            oracle = paged_attention_ragged_quant_ref(
                q, kq, vq, ks_, vs_, bt, bt, ctx, qs, ql, p0, window=window)
            out = paged_attention_ragged_quant(
                q, kq, vq, ks_, vs_, bt, bt, ctx, qs, ql, p0, window=window,
                pages_per_block=kb, q_block=tb, interpret=True)
            err = jnp.abs(out.astype(jnp.float32)
                          - oracle.astype(jnp.float32))
            assert float(err.max()) < _tol(jnp.float32), (
                f"{spec.name} (kb={kb}, tb={tb}): err={float(err.max()):.3e}"
                f" at (seq, head, page)="
                f"{_quant_failure_triple(err, q_starts, q_lens, pos0, bt, page)}")


def test_paged_attention_ignores_garbage_beyond_context():
    """Pages past context_len must not affect output (allocator reuse)."""
    B, Tq, H, Hkv, D, page = 1, 1, 2, 1, 16, 16
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, Tq, H, D))
    kp = jax.random.normal(ks[1], (4, Hkv, D, page))
    vp = jax.random.normal(ks[2], (4, Hkv, D, page))
    bt = jnp.array([[0, 1]], jnp.int32)
    ctx = jnp.array([20], jnp.int32)
    out1 = paged_attention(q, kp, vp, bt, ctx, ctx - 1, interpret=True)
    kp2 = kp.at[1, ..., 10:].set(1e4)   # garbage beyond token 20
    vp2 = vp.at[1, ..., 10:].set(1e4)
    out2 = paged_attention(q, kp2, vp2, bt, ctx, ctx - 1, interpret=True)
    assert float(jnp.abs(out1 - out2).max()) < 1e-6
