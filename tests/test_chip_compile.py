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

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from repro import configs  # noqa: E402
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
