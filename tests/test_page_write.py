"""The page-wise K/V write (DESIGN.md §3): ``scatter_rows`` against the
plain (page, slot) scatter it replaces, the bound on the pages a step
touches, and the shape of what the fused step lowers to.

The oracle, ``_slot_scatter``, is the old write: one scatter that indexes
the page and the slot dims of a (P, Hkv[, D], page) pool. It is kept here
only. On the TPU that scatter made XLA relay every whole pool around it;
``scatter_rows`` must write the same bits without it.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.engine import PagedTransformerExecutor
from repro.engine.executor import (pages_bound, row_dest, scatter_rows,
                                   table_dest)
from repro.models import ModelOpts, build_model

PAGE, NUM_PAGES, HKV, D = 16, 37, 2, 8


def _slot_scatter(pool, pages, slots, rows):
    idx = (pages,) + (slice(None),) * (rows.ndim - 1) + (slots,)
    return pool.at[idx].set(rows)


def _runs(starts, lengths, tables):
    """Rows of consecutive positions: sequence i writes ``lengths[i]``
    tokens from global position ``starts[i]`` through ``tables[i]``."""
    pages, slots = [], []
    for p0, n, tbl in zip(starts, lengths, tables):
        pos = np.arange(p0, p0 + n)
        pages += [tbl[p // PAGE] for p in pos]
        slots += list(pos % PAGE)
    return pages, slots


def _decode():
    # 5 decodes at scattered slots, bucketed to 8 rows: 3 pads → trash
    pages, slots = [4, 9, 17, 30, 2], [0, 15, 7, 3, 11]
    return pages + [0] * 3, slots + [0] * 3, 5


def _prefill_mid_page():
    # two chunks that start mid-page and cross two page boundaries each
    pages, slots = _runs([13, 40], [30, 30], [[5, 6, 7], [1, 2, 3, 4, 22]])
    return pages, slots, 2


def _one_page():
    # a whole page from one chunk, then a decode on another page
    pages, slots = _runs([32, 5], [16, 1], [[0, 0, 11], [12]])
    return pages, slots, 2


def _pads():
    # a short chunk and a long run of padding rows, all to slot 0 of page 0
    pages, slots = _runs([3], [6], [[21]])
    return pages + [0] * 10, slots + [0] * 10, 1


def _bound_met():
    # each 18-token chunk starts at a page's last slot and spans three
    # pages; with the padding rows' trash page that is the bound exactly
    pages, slots = _runs([15, 31], [18, 18], [[3, 4, 5], [7, 8, 9, 10]])
    return pages + [0] * 4, slots + [0] * 4, 2


CASES = {"decode": _decode, "prefill_mid_page": _prefill_mid_page,
         "one_page": _one_page, "pad_rows": _pads, "bound_met": _bound_met}


@pytest.mark.parametrize("scale_pool", [False, True], ids=["kv", "scales"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_page_write_matches_slot_scatter(case, scale_pool):
    """Bit-identical pools, K/V (P, Hkv, D, page) and scale (P, Hkv,
    page), for each shape of write the callers make."""
    pages, slots, n_seqs = CASES[case]()
    n = len(pages)
    bound = pages_bound(n, n_seqs, PAGE, NUM_PAGES)
    assert len(set(pages)) <= bound
    if case == "bound_met":
        assert len(set(pages)) == bound
    rng = np.random.default_rng(len(case))
    tail = (HKV,) if scale_pool else (HKV, D)
    k_pool, v_pool = (jnp.asarray(rng.normal(size=(NUM_PAGES, *tail, PAGE)),
                                  jnp.float32) for _ in range(2))
    k, v = (jnp.asarray(rng.normal(size=(n, *tail)), jnp.float32)
            for _ in range(2))
    pages, slots = jnp.asarray(pages, jnp.int32), jnp.asarray(slots, jnp.int32)

    @jax.jit
    def paged(k_pool, v_pool, k, v, pages, slots):
        dest = row_dest(pages, slots, bound, NUM_PAGES)
        return scatter_rows((k_pool,), (v_pool,), 0, k, v, dest)

    (k_new,), (v_new,) = paged(k_pool, v_pool, k, v, pages, slots)
    oracle = jax.jit(_slot_scatter)
    np.testing.assert_array_equal(k_new, oracle(k_pool, pages, slots, k))
    np.testing.assert_array_equal(v_new, oracle(v_pool, pages, slots, v))


def test_pages_bound_covers_random_steps():
    """Over random packed steps (chunks at any offset, decodes, padding),
    the distinct pages written never pass ``pages_bound``, and the
    ``table_dest`` of a (B, T) block agrees with the packed pages."""
    rng = np.random.default_rng(0)
    for _ in range(300):
        n_seqs = int(rng.integers(1, 6))
        lengths = rng.integers(1, 3 * PAGE, n_seqs)
        starts = rng.integers(0, 4 * PAGE, n_seqs)
        perm = rng.permutation(np.arange(1, 400))
        tables = [perm[8 * i:8 * i + 8] for i in range(n_seqs)]
        pages, _ = _runs(starts, lengths, tables)
        n_rows = len(pages) + int(rng.integers(0, 2 * PAGE))
        n_pad = n_rows - len(pages)
        distinct = len(set(pages) | ({0} if n_pad else set()))
        assert distinct <= pages_bound(n_rows, n_seqs, PAGE, 400)
    positions = jnp.asarray([[20, 21, 22], [47, 48, 49]], jnp.int32)
    table = jnp.asarray([[3, 4, 5, 6], [7, 8, 9, 10]], jnp.int32)
    valid = jnp.asarray([[True, True, False], [True, True, True]])
    dest = table_dest(table, positions, PAGE, NUM_PAGES, valid)
    written = np.asarray(dest.pages)[np.asarray(dest.inv)]
    np.testing.assert_array_equal(written, [4, 4, 0, 9, 10, 10])
    np.testing.assert_array_equal(dest.slots, [4, 5, 6, 15, 0, 1])


@pytest.fixture(scope="module")
def fused():
    cfg = dataclasses.replace(get_reduced("stablelm-3b"), window=None)
    params = build_model(cfg, ModelOpts(attn_impl="dense")).init(
        jax.random.PRNGKey(0))
    return PagedTransformerExecutor(cfg, params, num_pages=NUM_PAGES,
                                    page_size=PAGE, max_pages_per_seq=8)


def _fused_lowered(execu, t, s, tq, pg):
    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)
    step = execu._fused_fn
    args = (i32(t), i32(t), i32(t), i32(t), i32(0), i32(s, pg), i32(0, pg),
            i32(s), i32(s), i32(s), i32(s), i32(s), i32(s, tq), i32(t))
    return step.func.lower(step.args[0], execu.k_pages, execu.v_pages, (),
                           *args, t_bucket=t, s_bucket=s, tq_bucket=tq,
                           pg_bucket=pg)


def _ops(op):
    for region in op.regions:
        for block in region.blocks:
            for child in block.operations:
                yield child
                yield from _ops(child.operation)


def test_fused_step_touches_pools_by_page_only(fused):
    """In the fused step's lowered module, every operation that takes a
    whole pool is a gather or a scatter of whole pages on dim 0 (or a call
    or return passing the pool on): no (page, slot) scatter, which the
    TPU's compiler answers with a relayout of the pool."""
    dims = "x".join(map(str, fused.k_pages[0].shape))
    pool_ty = f"tensor<{dims}xf32>"
    module = _fused_lowered(fused, 48, 6, 16, 4).compiler_ir("stablehlo")
    seen = {}
    for op in _ops(module.operation):
        name = op.operation.name
        if not any(str(x.type) == pool_ty for x in op.operands):
            continue
        seen[name] = seen.get(name, 0) + 1
        if name == "stablehlo.gather":
            assert "start_index_map = [0]," in str(
                op.attributes["dimension_numbers"]), op
        elif name == "stablehlo.scatter":
            assert "scatter_dims_to_operand_dims = [0]," in str(
                op.attributes["scatter_dimension_numbers"]), op
        else:
            assert name in ("func.call", "func.return"), op
    # the layer body is lowered once and called per layer: its K and V
    # writes are the module's only scatters into a pool
    assert seen["stablehlo.scatter"] == 2
    assert seen["stablehlo.gather"] >= 2


def test_one_program_per_fused_key(fused):
    """Steps of one compile key with different writes (a different number
    of rows, pages and sequences inside the same buckets) share one
    program: the page bound is static per key, and nothing compiles per
    step."""
    from repro.core.types import BatchItem, BatchPlan, TaskKind
    from repro.engine import Request

    def step(chunks, base):
        reqs, items = {}, []
        for i, n in enumerate(chunks):
            rid = base + i
            reqs[rid] = Request(rid, arrival=0.0, prompt_len=n,
                                max_new_tokens=1, ttft_slo=1.0, tpot_slo=1.0,
                                tokens=[1] * n)
            items.append(BatchItem(rid, n, TaskKind.PREFILL))
        plan = BatchPlan(items=items, predicted_time=0.0, time_budget=0.0,
                         token_budget_used=0, token_budget_total=0)
        fused.execute(plan, reqs, 0.0)
        for rid in reqs:
            fused.release(rid)

    keys0 = len(fused.compile_keys)
    step([20, 9, 11], 100)                # 40 tokens, 3 seqs, 4 pages
    n0 = fused._fused_fn._cache_size()
    step([25, 10, 3, 2], 200)             # 40 tokens, 4 seqs, 5 pages
    assert len(fused.compile_keys) == keys0 + 1
    assert n0 == 1 and fused._fused_fn._cache_size() == 1
