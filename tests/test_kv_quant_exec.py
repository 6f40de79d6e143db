"""End-to-end contracts of the quantized paged-KV data plane (DESIGN.md §14).

Four layers, each pinned against an oracle:

* fused vs sequential — with int8 KV the quantization error lives in the
  *shared* pages, not in the execution strategy, so both modes must still
  produce equal token streams on identical plans (the §11 parity contract
  survives quantization);
* KV parity — ``kv_parity_report`` compares a quantized executor's
  dequantized pages against an fp32 twin that ran the identical teacher-
  forced prefill: layer 0 within the exact ``row_error_bound``, deeper
  layers within a documented compounding slack;
* scheduling bit-identity — two engines differing only in ``kv_dtype``
  (equal page counts, deterministic ``ModelTimedExecutor`` clock) must
  form byte-identical plans, deferral sets, and VTC billing counters:
  token *values* drift within the §14 bound, token *counts* never;
* equal-HBM capacity — sizing both pools from ``kv_page_budget`` at the
  same byte budget, int8's extra pages must translate into equal-or-fewer
  preemptions and an equal-or-better prefix-cache hit rate.
"""
import dataclasses

import pytest

jax = pytest.importorskip("jax")
jnp = jax.numpy

from repro.core import LinearCostModel, make_scheduler
from repro.core.cost_model import kv_bytes_per_token, kv_page_budget
from repro.core.types import BatchItem, BatchPlan, TaskKind
from repro.engine import (Engine, EngineConfig, PagedTransformerExecutor,
                          Request)
from repro.engine.numerics import (ModelTimedExecutor, assert_same_decisions,
                                   capture_schedule, kv_parity_report,
                                   vtc_counters)
from repro.engine.request import RequestState
from repro.kernels import quant as kvq

PAGE = 8
MODEL = LinearCostModel(a=1e-3, b=1e-4, c=0.0)
# Compounding envelope for layers > 0 (see test_kv_parity_prefill_oracle):
# layer l's inputs already carry the previous layers' dequantization error
# through attention + MLP, so its K/V rows drift beyond the single-row
# bound. Empirically the reduced config stays under ~10x; 64x documents
# the order of magnitude while staying far from fp32-noise false passes.
DEEP_LAYER_SLACK = 64.0


@pytest.fixture(scope="module")
def setup():
    from repro.configs import get_reduced
    from repro.models import ModelOpts, build_model
    cfg = dataclasses.replace(get_reduced("stablelm-3b"), window=None)
    model = build_model(cfg, ModelOpts(attn_impl="dense"))
    params = model.init(jax.random.PRNGKey(0))
    return cfg, params


def _executor(cfg, params, *, kv_dtype="int8", mode="fused", num_pages=64,
              max_pages=16, **kw):
    return PagedTransformerExecutor(cfg, params, num_pages=num_pages,
                                    page_size=PAGE,
                                    max_pages_per_seq=max_pages,
                                    mode=mode, kv_dtype=kv_dtype, **kw)


def _requests(cfg, n_req, plen, n_new, seed=5, tenant=None):
    rng = jax.random.PRNGKey(seed)
    return [Request(i, arrival=0.0, prompt_len=plen, max_new_tokens=n_new,
                    ttft_slo=10.0, tpot_slo=10.0,
                    tenant=(tenant(i) if tenant else "default"),
                    tokens=[int(x) for x in jax.random.randint(
                        jax.random.fold_in(rng, i), (plen,), 0, cfg.vocab)])
            for i in range(n_req)]


def _drive(execs, worlds, chunk):
    """Deterministic fixed-chunk round-robin: every executor runs the SAME
    plan sequence (no scheduler feedback), mirroring the hybrid-step bench
    driver. Returns per-mode {req_id: generated_tokens}."""
    ref = worlds[next(iter(execs))]
    steps = 0
    while any(r.active for r in ref.values()):
        items = []
        for r in ref.values():
            if not r.active:
                continue
            if r.state is RequestState.DECODE:
                items.append(BatchItem(r.req_id, 1, TaskKind.DECODE))
            else:
                n = min(chunk, r.prompt_len - r.prefilled)
                items.append(BatchItem(r.req_id, n, TaskKind.PREFILL))
        if not items:
            break
        plan = BatchPlan(items, 0.0, 0.0, 0, 0)
        for m, execu in execs.items():
            requests = worlds[m]
            _, emitted = execu.execute(plan, requests, float(steps))
            assert not execu.last_deferred, "pool sized to never defer"
            for it in plan.items:
                req = requests[it.req_id]
                if it.req_id in emitted:
                    req.generated_tokens.append(emitted[it.req_id])
                req.advance(it.n_tokens, float(steps))
        steps += 1
    return {m: {rid: list(r.generated_tokens) for rid, r in worlds[m].items()}
            for m in execs}


# ---------------------------------------------------------------------------
# fused vs sequential under int8: the §11 parity contract survives
# ---------------------------------------------------------------------------


def test_int8_fused_matches_sequential_tokens(setup):
    """Quantization error is state, not noise: both modes round-trip the
    same int8 pages + scale pages, so identical plans must yield equal
    token streams — any divergence is a scatter/gather or scale-table bug,
    not 'expected quantization drift'."""
    cfg, params = setup
    execs = {m: _executor(cfg, params, kv_dtype="int8", mode=m)
             for m in ("fused", "sequential")}
    worlds = {m: {r.req_id: r for r in _requests(cfg, 4, plen=22, n_new=8)}
              for m in execs}
    tokens = _drive(execs, worlds, chunk=12)
    assert tokens["fused"] == tokens["sequential"], \
        "modes diverged on identical plans under int8 KV"
    assert all(len(t) == 8 for t in tokens["fused"].values())
    for m, execu in execs.items():
        for rid in worlds[m]:
            execu.release(rid)
        execu.alloc.check_invariants()


# ---------------------------------------------------------------------------
# KV parity oracle: dequantized pages vs the fp32 twin
# ---------------------------------------------------------------------------


def test_kv_parity_prefill_oracle(setup):
    """Teacher-forced chunked prefill on identical tokens: layer 0's K/V
    depend only on the embeddings, so its dequantized rows must sit within
    the *exact* row_error_bound; deeper layers compound through attention
    and MLP and are pinned by ``DEEP_LAYER_SLACK``."""
    cfg, params = setup
    exq = _executor(cfg, params, kv_dtype="int8")
    exr = _executor(cfg, params, kv_dtype="fp32")
    execs = {"q": exq, "ref": exr}
    # prompt crosses page boundaries and leaves a partial tail page
    worlds = {m: {r.req_id: r for r in _requests(cfg, 2, plen=37, n_new=1,
                                                 seed=7)}
              for m in execs}
    _drive(execs, worlds, chunk=16)
    for rid in worlds["q"]:
        report = kv_parity_report(exq, exr, rid)
        assert len(report) == cfg.n_layers
        lp0 = report[0]
        assert lp0.k_bound > 0 and lp0.v_bound > 0
        assert lp0.within(1.0), (
            f"layer 0 beyond the exact bound: k {lp0.k_err:.3e} vs "
            f"{lp0.k_bound:.3e}, v {lp0.v_err:.3e} vs {lp0.v_bound:.3e}")
        for lp in report[1:]:
            assert lp.within(DEEP_LAYER_SLACK), (
                f"layer {lp.layer} drifted beyond {DEEP_LAYER_SLACK}x the "
                f"row bound: k {lp.k_err:.3e}/{lp.k_bound:.3e}, "
                f"v {lp.v_err:.3e}/{lp.v_bound:.3e}")
    for m, execu in execs.items():
        for rid in worlds[m]:
            execu.release(rid)


def test_fp8_spec_gating_is_consistent():
    """`kv_quant_spec` resolves every storage format by name — fp8 to the
    e4m3fn dtype the installed JAX provides — and an unknown name is a
    clear ValueError, never a silent int8 fallback."""
    spec = kvq.kv_quant_spec("fp8_e4m3")
    assert spec.dtype == jnp.float8_e4m3fn and spec.qmax == 448.0
    assert kvq.kv_quant_spec("int8").dtype == jnp.int8
    with pytest.raises(ValueError):
        kvq.kv_quant_spec("int4")
    assert kvq.kv_quant_spec("fp32") is None


# ---------------------------------------------------------------------------
# scheduling bit-identity: fp32 vs int8 at equal page counts
# ---------------------------------------------------------------------------


def _sched_run(cfg, params, kv_dtype):
    execu = _executor(cfg, params, kv_dtype=kv_dtype, num_pages=48,
                      max_pages=8)
    eng = Engine(make_scheduler("fairbatching", MODEL, vtc=True,
                                calibrate=False),
                 ModelTimedExecutor(execu, MODEL),
                 EngineConfig(ttft_slo=0.5, tpot_slo=0.05))
    trace = capture_schedule(eng)
    rng = jax.random.PRNGKey(9)
    for i in range(10):
        plen = 10 + (7 * i) % 28
        eng.submit(Request(i, arrival=0.01 * i, prompt_len=plen,
                           max_new_tokens=6, ttft_slo=0.5, tpot_slo=0.05,
                           tenant="interactive" if i % 2 else "batch",
                           tokens=[int(x) for x in jax.random.randint(
                               jax.random.fold_in(rng, i), (plen,), 0,
                               cfg.vocab)]))
    eng.run(max_steps=3000)
    assert len(eng.done) == 10, "workload did not complete"
    execu.alloc.check_invariants()
    counts = {rid: r.generated for rid, r in eng.requests.items()}
    return trace, vtc_counters(eng), counts


@pytest.mark.slow
def test_scheduling_decisions_bit_identical_fp32_vs_int8(setup):
    """The §14 acceptance contract: token VALUES may drift within the
    quantization bound, but every *scheduling* decision — plan contents
    and order, deferral sets, per-tenant VTC billing — must be
    byte-identical between fp32 and int8 engines at equal page counts.
    ``ModelTimedExecutor`` supplies the deterministic clock that makes the
    two traces comparable (DESIGN.md §14)."""
    cfg, params = setup
    t32, c32, n32 = _sched_run(cfg, params, "fp32")
    t8, c8, n8 = _sched_run(cfg, params, "int8")
    assert len(t32.plans) > 10, "trace too short to be meaningful"
    assert_same_decisions(t32, t8, "fp32 vs int8")
    assert t32.fingerprint() == t8.fingerprint()
    assert c32 == c8, f"VTC counters diverged: {c32} vs {c8}"
    assert set(c32) == {"interactive", "batch"}, "both tenants billed"
    assert n32 == n8, "per-request generated counts diverged"


def _rebuild_prompt(cfg, prefixes, i):
    rng = jax.random.fold_in(jax.random.PRNGKey(21), i)
    # suffixes stay under one page so requests publish ONLY their group's
    # prefix pages — cache contention is purely between the two prefixes
    extra = 2 + (3 * i) % 6
    return prefixes[i % len(prefixes)] + [
        int(x) for x in jax.random.randint(rng, (extra,), 0, cfg.vocab)]


def _capacity_run(cfg, params, kv_dtype, hbm_bytes):
    """One end-to-end run with BOTH the KV pool and the prefix-cache
    capacity funded from the same HBM byte budget — the cache stores KV
    pages too, so quantization buys it headroom at the same rate."""
    from repro.cache import PrefixCache
    bpt = kv_bytes_per_token(cfg.n_layers, cfg.n_kv_heads, cfg.head_dim,
                             kv_dtype)
    pages = kv_page_budget(hbm_bytes, PAGE, bpt)
    execu = _executor(cfg, params, kv_dtype=kv_dtype, num_pages=pages,
                      max_pages=16)
    cache = PrefixCache(max(4, pages // 5), block_size=PAGE,
                        alloc=execu.alloc)
    execu.attach_cache(cache)
    eng = Engine(make_scheduler("fairbatching", MODEL, calibrate=False),
                 ModelTimedExecutor(execu, MODEL),
                 EngineConfig(ttft_slo=0.5, tpot_slo=0.05, preemption=True,
                              defer_age=0.005),
                 prefix_cache=cache)
    # two 24-token (3-page) prefix groups, interleaved arrivals: retaining
    # BOTH groups takes 6 cache pages — above the fp32 budget's cache,
    # within the int8 budget's
    prefixes = [[int(x) for x in
                 jax.random.randint(jax.random.PRNGKey(20 + g), (24,),
                                    0, cfg.vocab)] for g in range(2)]
    n_req = 8
    for i in range(n_req):
        prompt = _rebuild_prompt(cfg, prefixes, i)
        eng.submit(Request(i, arrival=0.002 * i, prompt_len=len(prompt),
                           max_new_tokens=16, ttft_slo=0.5, tpot_slo=0.05,
                           tokens=prompt))
    eng.run(max_steps=8000)
    assert len(eng.done) == n_req, f"{kv_dtype}: workload did not complete"
    # probe wave: one fresh request per group, pressure-free, AFTER the
    # pressure wave — its ``cached_context`` counts exactly the prompt
    # tokens served from what the cache *retained* (the raw hit-rate ratio
    # is confounded: preemption victims re-look-up prefixes they just
    # published, inflating the pressured run's hits)
    for g in range(2):
        rng = jax.random.fold_in(jax.random.PRNGKey(33), g)
        prompt = prefixes[g] + [int(x) for x in
                                jax.random.randint(rng, (4,), 0, cfg.vocab)]
        eng.submit(Request(100 + g, arrival=eng.now, prompt_len=len(prompt),
                           max_new_tokens=2, ttft_slo=0.5, tpot_slo=0.05,
                           tokens=prompt))
    eng.run(max_steps=2000)
    assert len(eng.done) == n_req + 2
    probe_cached = sum(eng.requests[100 + g].cached_context
                       for g in range(2))
    execu.alloc.check_invariants()
    return eng, cache, pages, probe_cached


@pytest.mark.slow
def test_int8_capacity_outperforms_fp32_at_equal_hbm(setup):
    """Equal HBM byte budget (via ``kv_page_budget``) for BOTH the KV pool
    and the prefix cache: int8 funds ~4x the pages, which must show up end
    to end as equal-or-fewer preemptions under pressure and equal-or-better
    prefix retention (probe-wave cached tokens — see ``_capacity_run`` for
    why the raw hit-rate ratio can't be compared) — with every request
    completing and the allocator invariants (scale pages included) intact."""
    cfg, params = setup
    bpt32 = kv_bytes_per_token(cfg.n_layers, cfg.n_kv_heads, cfg.head_dim,
                               "fp32")
    hbm = 22 * PAGE * bpt32                    # fp32 gets exactly 22 pages
    e32, cache32, p32, probe32 = _capacity_run(cfg, params, "fp32", hbm)
    e8, cache8, p8, probe8 = _capacity_run(cfg, params, "int8", hbm)
    assert p32 == 22 and p8 > p32, f"int8 must fund more pages ({p8} vs {p32})"
    # the fp32 pool must genuinely feel the pressure the int8 pool escapes
    assert e32.defer_events + e32.preemptions > 0, \
        "fp32 run felt no page pressure — capacity comparison is vacuous"
    assert e8.preemptions <= e32.preemptions, \
        f"int8 preempted more ({e8.preemptions} vs {e32.preemptions})"
    # under pool pressure the fp32 run's cache yields pages (evict_for), so
    # later same-prefix admissions miss; the int8 budget never evicts
    assert cache8.stats.hit_rate >= cache32.stats.hit_rate, (
        f"int8 hit rate {cache8.stats.hit_rate:.3f} fell below fp32 "
        f"{cache32.stats.hit_rate:.3f}")
    assert cache8.stats.hit_rate > 0.0
    # retention floor: probes must find both 3-page prefixes still cached
    assert probe8 >= probe32, (
        f"int8 retained fewer cached prefix tokens ({probe8} vs {probe32})")
    assert probe8 >= 2 * 2 * PAGE, \
        f"int8 cache lost the shared prefixes (probe served {probe8} tokens)"
