"""Fused single-launch hybrid step (DESIGN.md §11) vs the sequential path.

Parity contract: the fused executor must emit bit-identical tokens to the
per-item sequential path on seeded mixed plans, with logits that are
argmax-exact and allclose at 1e-5, with or without jit (XLA picks its dot
kernels and fusions by operand shape, and the two paths pack rows into
differently-shaped operands, so they round differently at ~1e-6). Plus:
exactly one forward dispatch per engine step, a bounded compile ladder over
a warm trace, and the out-of-blocks deferral regression (mid-decode pool
exhaustion).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.core import LinearCostModel, make_scheduler
from repro.engine import (BlockAllocator, Engine, EngineConfig,
                          PagedTransformerExecutor, Request)
from repro.models import ModelOpts, build_model

KEY = jax.random.PRNGKey(0)
PAGE, NUM_PAGES, MAX_PAGES = 16, 64, 8


@pytest.fixture(scope="module")
def setup():
    cfg = dataclasses.replace(get_reduced("stablelm-3b"), window=None)
    model = build_model(cfg, ModelOpts(attn_impl="dense"))
    params = model.init(KEY)
    return cfg, model, params


@pytest.fixture(scope="module")
def executors(setup):
    """One executor per mode, shared across tests (warm jit caches);
    ``_reset`` gives each test a clean allocator and zeroed pages."""
    cfg, _, params = setup
    return {mode: PagedTransformerExecutor(
                cfg, params, num_pages=NUM_PAGES, page_size=PAGE,
                max_pages_per_seq=MAX_PAGES, mode=mode, capture_logits=True)
            for mode in ("fused", "sequential")}


def _reset(execu) -> None:
    execu.alloc = BlockAllocator(NUM_PAGES, PAGE)
    assert execu.alloc.extend(-1, PAGE) == [0]     # trash page
    execu.k_pages = jax.tree.map(jnp.zeros_like, execu.k_pages)
    execu.v_pages = jax.tree.map(jnp.zeros_like, execu.v_pages)
    execu.last_deferred = frozenset()
    execu.n_dispatches = 0
    execu.compile_keys = set()


def _engine(execu, ttft=5.0, tpot=5.0):
    sched = make_scheduler("fairbatching",
                           LinearCostModel(a=1e-4, b=1e-6, c=1e-10))
    return Engine(sched, execu, EngineConfig(ttft_slo=ttft, tpot_slo=tpot))


def _mixed_requests(cfg, seed, n=5, max_prompt=40, n_new=5):
    rng = jax.random.PRNGKey(seed)
    reqs = []
    for i in range(n):
        plen = 1 + (7 * i + seed) % max_prompt
        toks = [int(x) for x in jax.random.randint(
            jax.random.fold_in(rng, i), (plen,), 0, cfg.vocab)]
        # staggered arrivals interleave chunked prefills with live decodes
        reqs.append(Request(i, arrival=0.002 * i, prompt_len=plen,
                            max_new_tokens=n_new, ttft_slo=5.0, tpot_slo=5.0,
                            tokens=toks))
    return reqs


def _run(execu, cfg, seed, max_steps=400):
    """Drive a seeded mixed workload; capture tokens + first-token logits."""
    _reset(execu)
    eng = _engine(execu)
    for r in _mixed_requests(cfg, seed):
        eng.submit(r)
    first_logits, n = {}, 0
    while eng.has_work and n < max_steps:
        eng.step()
        n += 1
        for rid, lg in execu.last_logits.items():
            if rid not in first_logits:
                first_logits[rid] = lg.copy()
    tokens = {rid: list(r.generated_tokens) for rid, r in eng.requests.items()}
    return tokens, first_logits, eng


@pytest.mark.parametrize("seed", [1, 9])
def test_fused_matches_sequential_on_mixed_plans(executors, setup, seed):
    cfg, _, _ = setup
    tok_f, lg_f, _ = _run(executors["fused"], cfg, seed)
    tok_s, lg_s, _ = _run(executors["sequential"], cfg, seed)
    assert tok_f == tok_s                      # bit-identical emitted tokens
    assert lg_f.keys() == lg_s.keys()
    for rid in lg_s:
        assert int(np.argmax(lg_f[rid])) == int(np.argmax(lg_s[rid]))
        np.testing.assert_allclose(lg_f[rid], lg_s[rid], atol=1e-5, rtol=0)


def test_fused_bitwise_logits_without_jit(executors, setup):
    """Under ``jax.disable_jit()`` both step bodies run op by op and form
    the same plans, so emitted tokens are identical and first-token logits
    agree to the jitted test's tolerance. They are not bit-identical: each
    eager op compiles for its own operand shape, and XLA:CPU picks its dot
    kernel by shape, so a row packed into the fused stream beside other
    sequences' rows accumulates in a different order than the same row in
    a per-sequence launch (a sequence served alone in its step matches
    exactly). The pages-bucket trim (§14) is pinned off here, so the
    gathered contexts have equal widths in both modes."""
    cfg, _, params = setup
    fused = PagedTransformerExecutor(
        cfg, params, num_pages=NUM_PAGES, page_size=PAGE,
        max_pages_per_seq=MAX_PAGES, mode="fused", capture_logits=True,
        trim_page_tables=False)
    with jax.disable_jit():
        tok_f, lg_f, _ = _run(fused, cfg, seed=4, max_steps=60)
        tok_s, lg_s, _ = _run(executors["sequential"], cfg, seed=4,
                              max_steps=60)
    assert tok_f == tok_s
    assert lg_f.keys() == lg_s.keys() and len(lg_f) == 5
    for rid in lg_s:
        assert int(np.argmax(lg_f[rid])) == int(np.argmax(lg_s[rid]))
        np.testing.assert_allclose(lg_f[rid], lg_s[rid], atol=1e-5, rtol=0,
                                   err_msg=f"req {rid} logits drift")


def test_exactly_one_dispatch_per_step(executors, setup):
    cfg, _, _ = setup
    _, _, eng = _run(executors["fused"], cfg, seed=2)
    assert len(eng.steps) > 5
    assert executors["fused"].n_dispatches == len(eng.steps)


def test_compile_ladder_bound_over_warm_trace(setup):
    """100 warm steps: ≤ 2 jit entries per (token-bucket × seq-bucket) pair,
    and the two-axis ladder keeps the pair count itself small."""
    cfg, _, params = setup
    # ample pool: this test measures recompiles, not allocation pressure
    execu = PagedTransformerExecutor(cfg, params, num_pages=512,
                                     page_size=PAGE, max_pages_per_seq=MAX_PAGES)
    eng = _engine(execu)
    rng = jax.random.PRNGKey(7)
    for i in range(40):      # steady stream: prefills keep joining decodes
        plen = 4 + (3 * i) % 12
        toks = [int(x) for x in jax.random.randint(
            jax.random.fold_in(rng, i), (plen,), 0, cfg.vocab)]
        eng.submit(Request(i, arrival=0.01 * i, prompt_len=plen,
                           max_new_tokens=112, ttft_slo=5.0, tpot_slo=5.0,
                           tokens=toks))
    n = 0
    while eng.has_work and n < 400:
        eng.step()
        n += 1
    assert len(eng.steps) >= 100, f"only {len(eng.steps)} steps ran"
    assert len(eng.done) == 40, "workload did not complete"
    pairs = {k for k in execu.compile_keys if k[0] == "fused"}
    n_compiles = execu._fused_fn._cache_size()
    assert n_compiles <= 2 * len(pairs), (n_compiles, pairs)
    assert len(pairs) <= 10, f"bucket ladder too leaky: {sorted(pairs)}"


def test_one_dispatch_per_warm_step_across_bucket_ladder(setup):
    """Dispatch-count regression (ISSUE 6 satellite): exactly ONE forward
    dispatch per engine step — cold AND warm — while the workload walks
    multiple cells of the two-axis bucket ladder (so no bucket transition
    sneaks in an extra launch).

    Context: the BENCH_hybrid_step.json rollup's ``dispatches_per_step``
    median of 2.0 was an artifact of ``write_bench_summary`` mixing
    sequential-mode rows (3 launches/step) with fused rows (1/step) in one
    min/median/max. The summary now segments metric rollups by label (a key
    spanning several modes/systems is reported only per label), so the
    pooled median is gone at the source; the fused path's own invariant is
    pinned here per step and surfaced per label in the summary's
    ``by_label`` stats.
    """
    cfg, _, params = setup
    execu = PagedTransformerExecutor(cfg, params, num_pages=512,
                                     page_size=PAGE,
                                     max_pages_per_seq=MAX_PAGES)
    eng = _engine(execu)
    rng = jax.random.PRNGKey(13)
    # ramp of prompt lengths + staggered arrivals: step widths sweep the
    # token-bucket ladder up and (as requests drain) back down, and the
    # growing tables walk the pages-bucket axis too
    for i in range(24):
        plen = 3 + (5 * i) % 60
        toks = [int(x) for x in jax.random.randint(
            jax.random.fold_in(rng, i), (plen,), 0, cfg.vocab)]
        eng.submit(Request(i, arrival=0.02 * i, prompt_len=plen,
                           max_new_tokens=24, ttft_slo=5.0, tpot_slo=5.0,
                           tokens=toks))
    dispatches_per_step = []
    n = 0
    while eng.has_work and n < 600:
        before = execu.n_dispatches
        steps_before = len(eng.steps)
        eng.step()
        n += 1
        if len(eng.steps) > steps_before:      # a batch actually ran
            dispatches_per_step.append(execu.n_dispatches - before)
    assert len(eng.done) == 24, "ladder workload did not complete"
    bad = [d for d in dispatches_per_step if d != 1]
    assert not bad, f"steps with != 1 dispatch: {bad[:5]}"
    assert execu.n_dispatches == len(eng.steps)
    # the sweep must genuinely cross bucket cells, warm steps included:
    # every key compiled once, later steps in the same cell reused it
    fused_keys = {k for k in execu.compile_keys if k[0] == "fused"}
    assert len(fused_keys) >= 3, \
        f"ladder not exercised: {sorted(fused_keys)}"
    assert len(dispatches_per_step) > len(fused_keys), \
        "no warm (cache-hit) steps ran"


def greedy_oracle(model, params, prompt, n_new):
    toks = jnp.asarray(prompt, jnp.int32)[None]
    logits, cache = model.prefill(params, toks, max_len=256)
    out = [int(jnp.argmax(logits, -1)[0])]
    for _ in range(n_new - 1):
        logits, cache = model.decode_step(
            params, jnp.asarray([out[-1]], jnp.int32), cache)
        out.append(int(jnp.argmax(logits, -1)[0]))
    return out


@pytest.mark.parametrize("mode", ["fused", "sequential"])
def test_decode_defers_when_out_of_blocks(setup, mode):
    """Regression (satellite of DESIGN.md §11): exhausting the page pool
    mid-decode must defer the decode item — not write K/V through a short
    block table — and the request must finish correctly once pages free."""
    cfg, model, params = setup
    # 6 pages minus trash = 5 usable, page_size 4. Prefills take 2+2 pages;
    # req 0's first decode (pos 8) takes the last free page, so req 1's
    # page-crossing decode (pos 8, one step later) finds the pool dry and
    # must DEFER. req 0 needs no further page, finishes, releases 3 —
    # req 1 retries, completes alone with exactly the 5 usable pages.
    execu = PagedTransformerExecutor(cfg, params, num_pages=6, page_size=4,
                                     max_pages_per_seq=5, mode=mode)
    eng = _engine(execu)
    rng = jax.random.PRNGKey(21)
    prompts = {0: [int(x) for x in jax.random.randint(
                   jax.random.fold_in(rng, 0), (8,), 0, cfg.vocab)],
               1: [int(x) for x in jax.random.randint(
                   jax.random.fold_in(rng, 1), (7,), 0, cfg.vocab)]}
    eng.submit(Request(0, arrival=0.0, prompt_len=8, max_new_tokens=4,
                       ttft_slo=5.0, tpot_slo=5.0, tokens=prompts[0]))
    eng.submit(Request(1, arrival=0.0, prompt_len=7, max_new_tokens=12,
                       ttft_slo=5.0, tpot_slo=5.0, tokens=prompts[1]))
    deferred_seen, n = False, 0
    while eng.has_work and n < 200:
        eng.step()
        n += 1
        deferred_seen |= bool(execu.last_deferred)
    assert deferred_seen, "pool never exhausted: regression test is inert"
    assert not eng.has_work, "deferred request never completed"
    for rid, prm in prompts.items():
        want = greedy_oracle(model, params, prm,
                             eng.requests[rid].max_new_tokens)
        assert eng.requests[rid].generated_tokens == want, f"req {rid}"
    # deferral must not leak pages
    assert execu.alloc.free_blocks == execu.alloc.num_blocks - 1


def test_fused_hypothesis_ragged_workloads(executors, setup):
    """Random request mixes (single-token prompts, 0-prefill / 0-decode
    steps, prompts at the max_pages boundary) keep fused == sequential."""
    hyp = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    cfg, _, _ = setup
    max_ctx = MAX_PAGES * PAGE    # 128

    @st.composite
    def workloads(draw):
        n_req = draw(st.integers(1, 4))
        reqs = []
        for i in range(n_req):
            plen = draw(st.sampled_from(
                [1, 2, 5, 17, 40, max_ctx - 4]))       # incl. boundary
            n_new = draw(st.integers(1, min(4, max_ctx - plen)))
            stagger = draw(st.booleans())
            reqs.append((plen, n_new, 0.003 * i if stagger else 0.0))
        return draw(st.integers(0, 2 ** 16)), reqs

    @given(workloads())
    @settings(max_examples=12, deadline=None)
    def check(wl):
        seed, reqs = wl
        outs = {}
        for mode in ("fused", "sequential"):
            execu = executors[mode]
            _reset(execu)
            eng = _engine(execu)
            rng = jax.random.PRNGKey(seed)
            for i, (plen, n_new, arr) in enumerate(reqs):
                toks = [int(x) for x in jax.random.randint(
                    jax.random.fold_in(rng, i), (plen,), 0, cfg.vocab)]
                eng.submit(Request(i, arrival=arr, prompt_len=plen,
                                   max_new_tokens=n_new, ttft_slo=5.0,
                                   tpot_slo=5.0, tokens=toks))
            n = 0
            while eng.has_work and n < 300:
                eng.step()
                n += 1
            outs[mode] = {rid: list(r.generated_tokens)
                          for rid, r in eng.requests.items()}
        assert outs["fused"] == outs["sequential"]

    check()
