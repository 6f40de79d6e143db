"""Compile the serving path's attention kernels for a described TPU v5e at
``h2o-danube-1.8b`` widths (DESIGN.md §3).

Interpret mode (tests/test_kernels.py) runs the kernel bodies in Python and
cannot see what the TPU compiler refuses: blocks whose two minor dims are
neither 8-/128-aligned nor whole, or more VMEM than a kernel may use. These
tests hand each kernel shapes only — no array, no chip — and compile it for
one chip of a described ``v5e:2x2`` topology. The topology is described in
the module fixture, never at import: only one process at a time may load the
TPU library, and under several test workers only the worker given this file
loads it.
"""
import os
import re

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from repro import configs  # noqa: E402
from repro.engine.executor import (pages_bound, row_dest,  # noqa: E402
                                   scatter_rows)
from repro.kernels.paged_attention import (  # noqa: E402
    paged_attention, paged_attention_ragged, paged_attention_ragged_quant)

CFG = configs.get("h2o-danube-1.8b")
POOL_PAGES, PAGE, SEQS, TABLE_PAGES = 256, 128, 8, 12
HBM_BYTES = 16e9                     # one TPU v5e chip


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back; keep the cache out of these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(one_chip, fn, shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < HBM_BYTES, f"{total / 1e9:.2f} GB does not fit the chip"
    return compiled


def _meta(n_seqs):
    """Block tables, then the four per-sequence int32 vectors."""
    return ([((n_seqs, TABLE_PAGES), jnp.int32)]
            + [((n_seqs,), jnp.int32)] * 4)


def _pool(dtype=jnp.float32):
    return ((POOL_PAGES, CFG.n_kv_heads, CFG.head_dim, PAGE), dtype)


@pytest.mark.parametrize("tokens", [16, 2048])
def test_ragged_kernel_compiles(one_chip, tokens):
    """The fused step's attention launch, at a decode bucket and at a
    prefill bucket that tiles the query stream."""
    q = ((tokens, CFG.n_heads, CFG.head_dim), jnp.float32)
    _compile(one_chip,
             lambda q, k, v, bt, cl, qs, ql, p0: paged_attention_ragged(
                 q, k, v, bt, cl, qs, ql, p0, window=CFG.window),
             [q, _pool(), _pool()] + _meta(SEQS))


def test_ragged_quant_kernel_compiles(one_chip):
    """int8 pages with their (Ps, Hkv, page) f32 scale pages."""
    q = ((2048, CFG.n_heads, CFG.head_dim), jnp.float32)
    scales = ((POOL_PAGES, CFG.n_kv_heads, PAGE), jnp.float32)
    meta = _meta(SEQS)
    shapes = ([q, _pool(jnp.int8), _pool(jnp.int8), scales, scales,
               meta[0]] + meta)
    _compile(one_chip,
             lambda q, k, v, ks, vs, bt, st, cl, qs, ql, p0:
                 paged_attention_ragged_quant(q, k, v, ks, vs, bt, st, cl,
                                              qs, ql, p0, window=CFG.window),
             shapes)


def test_batched_kernel_compiles(one_chip):
    """The sequential and multi-step decode path's launch (Tq = 1)."""
    q = ((SEQS, 1, CFG.n_heads, CFG.head_dim), jnp.float32)
    _compile(one_chip,
             lambda q, k, v, bt, cl, qs: paged_attention(
                 q, k, v, bt, cl, qs, window=CFG.window),
             [q, _pool(), _pool()] + _meta(SEQS)[:3])


@pytest.mark.parametrize("rows,seqs", [(64, 64), (2048, 8)])
def test_kv_write_keeps_pool_layout(one_chip, rows, seqs):
    """The step's K/V write into donated (P, Hkv, D, page) pools, at a
    decode and a prefill bucket: no instruction copies or transposes a
    whole pool into another layout (DESIGN.md §3)."""
    def write(k_pools, v_pools, pages, slots, k, v):
        dest = row_dest(pages, slots, pages_bound(rows, seqs, PAGE,
                                                  POOL_PAGES), POOL_PAGES)
        return scatter_rows(k_pools, v_pools, 0, k, v, dest)

    pool = jax.ShapeDtypeStruct(_pool()[0], jnp.float32, sharding=one_chip)
    ids = jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((rows, CFG.n_kv_heads, CFG.head_dim),
                              jnp.float32, sharding=one_chip)
    hlo = jax.jit(write, donate_argnums=(0, 1)).lower(
        (pool,), (pool,), ids, ids, kv, kv).compile().as_text()
    shape = ",".join(map(str, _pool()[0]))
    relaid = re.findall(rf"= f32\[{shape}\]\S* (?:copy|transpose)\(", hlo)
    assert not relaid, relaid
    assert len(re.findall(rf"= f32\[{shape}\]\S* fusion\(", hlo)) == 2


@pytest.mark.parametrize("t,s,tq", [(64, 64, 1), (2048, SEQS, 1024)])
def test_fused_step_updates_pools_in_place(one_chip, monkeypatch, t, s, tq):
    """The whole fused step at danube's head widths (two layers, narrow
    FFN and vocab), with the ragged kernel, on pools of ``POOL_PAGES``:
    each pool is written by one in-place fusion, and no instruction copies
    or transposes a whole pool (DESIGN.md §3)."""
    import dataclasses

    import repro.kernels.ops as ops
    from repro.engine import PagedTransformerExecutor
    from repro.models import ModelOpts, build_model

    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    cfg = dataclasses.replace(CFG, n_layers=2, d_model=640, n_heads=8,
                              d_ff=256, vocab=256)
    params = build_model(cfg, ModelOpts(attn_impl="dense")).init(
        jax.random.PRNGKey(0))
    ex = PagedTransformerExecutor(cfg, params, num_pages=4, page_size=PAGE,
                                  max_pages_per_seq=TABLE_PAGES,
                                  ragged_attention=True)

    def on_chip(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    pools = tuple(on_chip(*_pool()) for _ in range(cfg.n_layers))
    pg = TABLE_PAGES
    args = [on_chip((n,)) for n in (t, t, t, t, 0)] + [
        on_chip((s, pg)), on_chip((0, pg))] + [on_chip((s,))] * 5 + [
        on_chip((s, tq)), on_chip((t,))]
    weights = jax.tree.map(lambda a: on_chip(a.shape, a.dtype),
                           ex._fused_fn.args[0])
    hlo = ex._fused_fn.func.lower(
        weights, pools, pools, (), *args, t_bucket=t, s_bucket=s,
        tq_bucket=tq, pg_bucket=pg).compile().as_text()
    shape = ",".join(map(str, _pool()[0]))
    relaid = re.findall(rf"= f32\[{shape}\]\S* (?:copy|transpose)\(", hlo)
    assert not relaid, relaid
    written = re.findall(rf"= f32\[{shape}\]\S* fusion\(", hlo)
    assert len(written) == 2 * cfg.n_layers
