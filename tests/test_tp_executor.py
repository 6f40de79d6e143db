"""Tensor-parallel fused hybrid step vs the single-device oracle
(DESIGN.md §17).

Parity bar: **token streams**, not logits bits. Under TP the partitioned
o-proj / FFN-down / MoE-combine matmuls end in an all-reduce whose fp
summation order differs from the single-device matmul, so logits agree only
to reassociation; the greedy argmax tokens — the only thing the serving
stack emits — must still be bit-identical to the ``mode="sequential"``
single-device stream, for fp32 AND int8 KV, dense AND MoE archs.

Also pinned here: one dispatch per warm engine step survives sharding, and
scheduler decisions (plans / deferral sets / VTC counters) are byte-equal
across TP degrees at equal per-shard budgets — data-plane parallelism must
not leak into the control plane (§17's per-shard budget contract).
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_reduced
from repro.core import (LinearCostModel, SchedTask, TaskKind, commit_horizon,
                        make_scheduler)
from repro.core.cost_model import (PaddedCostModel, TokenCostModel,
                                   kv_bytes_per_token, per_shard_model)
from repro.engine import (BlockAllocator, Engine, EngineConfig,
                          PagedTransformerExecutor, Request)
from repro.engine.numerics import (ModelTimedExecutor, assert_same_decisions,
                                   capture_schedule, vtc_counters)
from repro.models import ModelOpts, build_model

KEY = jax.random.PRNGKey(0)
PAGE, NUM_PAGES, MAX_PAGES = 16, 64, 8


def _build(name):
    cfg = dataclasses.replace(get_reduced(name), window=None)
    model = build_model(cfg, ModelOpts(attn_impl="dense"))
    return cfg, model.init(KEY)


@pytest.fixture(scope="module")
def dense_setup():
    return _build("stablelm-3b")


def _executor(cfg, params, *, mode, mesh=None, kv_dtype="fp32"):
    return PagedTransformerExecutor(
        cfg, params, num_pages=NUM_PAGES, page_size=PAGE,
        max_pages_per_seq=MAX_PAGES, mode=mode, mesh=mesh, kv_dtype=kv_dtype)


def _reset(execu):
    execu.alloc = BlockAllocator(NUM_PAGES, PAGE)
    assert execu.alloc.extend(-1, PAGE) == [0]     # trash page
    # re-commit the page shardings (zeros_like alone would be enough on a
    # single device; under a mesh the pools must stay head-sharded)
    zeros = functools.partial(jax.tree.map, jnp.zeros_like)
    execu.k_pages = execu._shard_kv(zeros(execu.k_pages))
    execu.v_pages = execu._shard_kv(zeros(execu.v_pages))
    if execu.qspec is not None:
        execu.k_scales = execu._shard_scale(zeros(execu.k_scales))
        execu.v_scales = execu._shard_scale(zeros(execu.v_scales))
    execu.last_deferred = frozenset()
    execu.n_dispatches = 0
    execu.compile_keys = set()


def _engine(execu, cost_shards=1):
    sched = make_scheduler("fairbatching",
                           LinearCostModel(a=1e-4, b=1e-6, c=1e-10))
    return Engine(sched, execu, EngineConfig(ttft_slo=5.0, tpot_slo=5.0,
                                             cost_shards=cost_shards))


def _mixed_requests(cfg, seed, n=5, max_prompt=40, n_new=5):
    rng = jax.random.PRNGKey(seed)
    reqs = []
    for i in range(n):
        plen = 1 + (7 * i + seed) % max_prompt
        toks = [int(x) for x in jax.random.randint(
            jax.random.fold_in(rng, i), (plen,), 0, cfg.vocab)]
        reqs.append(Request(i, arrival=0.002 * i, prompt_len=plen,
                            max_new_tokens=n_new, ttft_slo=5.0, tpot_slo=5.0,
                            tokens=toks))
    return reqs


def _run(execu, cfg, seed, max_steps=400, wrap=None, cost_shards=1):
    _reset(execu)
    eng = _engine(execu if wrap is None else wrap(execu), cost_shards)
    for r in _mixed_requests(cfg, seed):
        eng.submit(r)
    trace = capture_schedule(eng)
    n = 0
    while eng.has_work and n < max_steps:
        eng.step()
        n += 1
    tokens = {rid: list(r.generated_tokens)
              for rid, r in eng.requests.items()}
    return tokens, trace, eng


# ---------------------------------------------------------------------------
# TP parity vs the single-device sequential oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kv_dtype", ["fp32", "int8"])
@pytest.mark.parametrize("tp", [2, 4])
def test_tp_fused_matches_single_device_oracle(dense_setup, tp_meshes,
                                               tp, kv_dtype):
    cfg, params = dense_setup
    oracle = _executor(cfg, params, mode="sequential", kv_dtype=kv_dtype)
    ref, _, _ = _run(oracle, cfg, seed=1)
    sharded = _executor(cfg, params, mode="fused", mesh=tp_meshes[tp],
                        kv_dtype=kv_dtype)
    assert sharded.n_shards == tp
    got, _, _ = _run(sharded, cfg, seed=1)
    assert got == ref, f"TP={tp} {kv_dtype} token stream diverged"


@pytest.mark.parametrize("arch", ["mixtral-8x7b", "kimi-k2-1t-a32b"])
def test_moe_tp2_parity(tp_meshes, arch):
    """Expert-parallel MoE archs under TP=2 (the smoke configs' kv=2 bound)
    against the single-device sequential oracle. The default exact MoE path
    is per-token independent, so packing/sharding can't change tokens."""
    cfg, params = _build(arch)
    ref, _, _ = _run(_executor(cfg, params, mode="sequential"), cfg, seed=1)
    sharded = _executor(cfg, params, mode="fused", mesh=tp_meshes[2])
    got, _, _ = _run(sharded, cfg, seed=1)
    assert got == ref, f"{arch} TP=2 token stream diverged"


def test_one_dispatch_per_step_under_tp(dense_setup, tp_meshes):
    """Sharding must not multiply launches: still exactly ONE fused
    dispatch per warm engine step at TP=2 (DESIGN.md §11 counter)."""
    cfg, params = dense_setup
    execu = _executor(cfg, params, mode="fused", mesh=tp_meshes[2])
    _, _, eng = _run(execu, cfg, seed=2)
    assert len(eng.steps) > 5
    assert execu.n_dispatches == len(eng.steps)


def test_scheduler_decisions_byte_equal_across_tp(dense_setup, tp_meshes):
    """At equal per-shard budgets (same scheduler cost model, deterministic
    model clock), plans, deferral sets and VTC counters are byte-identical
    across TP degrees — the data plane's parallelism never leaks into
    control-plane decisions."""
    cfg, params = dense_setup
    clock = LinearCostModel(a=1e-3, b=1e-4, c=0.0)

    def wrap(execu):
        return ModelTimedExecutor(execu, clock)

    runs = {}
    for tp in (1, 2, 4):
        mesh = None if tp == 1 else tp_meshes[tp]
        mode = "sequential" if tp == 1 else "fused"
        execu = _executor(cfg, params, mode=mode, mesh=mesh)
        tokens, trace, eng = _run(execu, cfg, seed=3, wrap=wrap,
                                  cost_shards=tp)
        runs[tp] = (tokens, trace, vtc_counters(eng))
    for tp in (2, 4):
        assert runs[tp][0] == runs[1][0]
        assert_same_decisions(runs[1][1], runs[tp][1],
                              label=f"TP=1 vs TP={tp}")
        assert runs[tp][2] == runs[1][2], f"VTC counters drift at TP={tp}"


# ---------------------------------------------------------------------------
# per-shard scheduler budgets (§17): cost model + commit horizon
# ---------------------------------------------------------------------------


def test_per_shard_model_divides_marginals_only():
    m = LinearCostModel(a=3e-3, b=2e-4, c=8e-8)
    s = per_shard_model(m, 4)
    assert (s.a, s.b, s.c) == (m.a, m.b / 4, m.c / 4)
    assert per_shard_model(m, 1) is m
    # subclasses keep their type (padding semantics survive sharding)
    p = per_shard_model(PaddedCostModel(a=1e-3, b=1e-5, c=1e-9), 2)
    assert isinstance(p, PaddedCostModel) and p.pad(100) >= 100
    t = per_shard_model(TokenCostModel(a=1e-3, b=1e-5), 2)
    assert isinstance(t, TokenCostModel) and t.c == 0.0


def test_kv_bytes_per_token_tp_shards_heads_not_pages():
    full = kv_bytes_per_token(32, 8, 128, "int8")
    shard = kv_bytes_per_token(32, 8, 128, "int8", tp=4)
    assert full == 4 * shard          # per-shard bytes shrink with heads...
    assert kv_bytes_per_token(32, 8, 128, "int8", tp=16) == \
        kv_bytes_per_token(32, 1, 128, "int8")   # ...floored at 1 head


def _decode_task(i, *, slack_s, tpot, ctx=1000, now=0.0):
    j = 5
    arrival = now + slack_s - 0.5 - tpot * j
    return SchedTask(req_id=i, arrival=arrival, ttft_slo=0.5, tpot_slo=tpot,
                     next_output_idx=j, new_tokens=1, context=ctx,
                     kind=TaskKind.DECODE)


def test_commit_horizon_deepens_with_shards():
    """Per-shard pricing funds deeper commitments from the same slack;
    the KV page bound is NOT scaled (page IDs stay global under TP)."""
    model = LinearCostModel(a=1e-4, b=190e-6, c=20e-9)
    # tpot below per-shard step time: each committed step consumes slack,
    # so the horizon ~ slack / step_time and per-shard pricing deepens it
    tasks = [_decode_task(0, slack_s=0.05, tpot=1e-5, ctx=4000)]
    h1 = commit_horizon(tasks, 0.0, model, max_horizon=512, ttft_slo=0.5)
    h4 = commit_horizon(tasks, 0.0, model, max_horizon=512, ttft_slo=0.5,
                        n_shards=4)
    assert h4 > h1 >= 1
    # page pool binds identically at any shard count: a horizon limited by
    # free pages must not move when n_shards does
    hp1 = commit_horizon(tasks, 0.0, model, max_horizon=512, ttft_slo=5.0,
                         free_pages=2, page_size=16)
    hp4 = commit_horizon(tasks, 0.0, model, max_horizon=512, ttft_slo=5.0,
                         free_pages=2, page_size=16, n_shards=4)
    assert hp1 == hp4
