"""Live KV-page migration on the real data plane (DESIGN.md §15).

The §15 correctness contract: a request migrated mid-decode from a source
executor to a destination executor must continue its token stream
**bit-identically** to a single-executor oracle that never migrated — for
fp32 and int8 KV (quantized pages move values + scale rows verbatim,
never requantizing), for shared-prefix installs where leading blocks
transfer as references into the destination's warm radix cache, and for
the recompute fallback (re-prefill of the full known prefix). Allocator
invariants (scale-page bijection included) must hold on BOTH allocators
after every migration, pinned here after each one and by a randomized
interleaving sweep over a tiny fake executor.
"""
import dataclasses

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.cache import PrefixCache  # noqa: E402
from repro.core.types import BatchItem, BatchPlan, TaskKind  # noqa: E402
from repro.data.traces import make_scenario  # noqa: E402
from repro.disagg.migration import (capture_kv,  # noqa: E402
                                    install_kv_pages)
from repro.engine import PagedTransformerExecutor, Request  # noqa: E402
from repro.engine.executor import gather_pages  # noqa: E402
from repro.engine.kv_manager import BlockAllocator  # noqa: E402
from repro.engine.request import RequestState  # noqa: E402

PAGE = 8
CHUNK = 12


@pytest.fixture(scope="module")
def setup():
    from repro.configs import get_reduced
    from repro.models import ModelOpts, build_model
    cfg = dataclasses.replace(get_reduced("stablelm-3b"), window=None)
    model = build_model(cfg, ModelOpts(attn_impl="dense"))
    params = model.init(jax.random.PRNGKey(0))
    return cfg, params


def _executor(cfg, params, *, kv_dtype="fp32", num_pages=96, max_pages=16):
    return PagedTransformerExecutor(cfg, params, num_pages=num_pages,
                                    page_size=PAGE,
                                    max_pages_per_seq=max_pages,
                                    mode="fused", kv_dtype=kv_dtype)


def _scenario_requests(cfg, name, n_req, n_new, seed):
    """Requests whose prompts come from a real scenario trace (the trace's
    own token ids where it carries them, seeded fill otherwise), truncated
    to keep the reduced model fast."""
    trace = sorted(make_scenario(name, rps=8.0, duration=2.0, seed=seed),
                   key=lambda t: t.arrival)
    rng = jax.random.PRNGKey(seed)
    out = []
    for i, tr in enumerate(trace[:n_req]):
        plen = max(10, min(tr.prompt_len, 30 + 3 * i))
        if tr.tokens:
            toks = [t % cfg.vocab for t in tr.tokens[:plen]]
            plen = len(toks)
        else:
            toks = [int(x) for x in jax.random.randint(
                jax.random.fold_in(rng, i), (plen,), 0, cfg.vocab)]
        out.append(Request(i, 0.0, plen, n_new, ttft_slo=10.0, tpot_slo=10.0,
                           tokens=toks))
    return out


def _step(ex, requests, owned, steps):
    """One teacher-forced step over ``owned`` active requests (the quant
    suite's fixed-chunk driver, restricted to one executor's residents)."""
    items = []
    for rid in owned:
        r = requests[rid]
        if not r.active:
            continue
        if r.state is RequestState.DECODE:
            items.append(BatchItem(rid, 1, TaskKind.DECODE))
        else:
            items.append(BatchItem(rid, min(CHUNK, r.prompt_len - r.prefilled),
                                   TaskKind.PREFILL))
    if not items:
        return False
    plan = BatchPlan(items, 0.0, 0.0, 0, 0)
    _, emitted = ex.execute(plan, requests, float(steps))
    assert not ex.last_deferred, "pool sized to never defer"
    for it in plan.items:
        req = requests[it.req_id]
        if it.req_id in emitted:
            req.generated_tokens.append(emitted[it.req_id])
        req.advance(it.n_tokens, float(steps))
    return True


def _oracle(cfg, params, kv_dtype, requests):
    """Single-executor run that never migrates — the parity reference."""
    ex = _executor(cfg, params, kv_dtype=kv_dtype)
    world = {r.req_id: r for r in requests}
    steps = 0
    while any(r.active for r in world.values()):
        _step(ex, world, list(world), steps)
        steps += 1
    out = {rid: list(r.generated_tokens) for rid, r in world.items()}
    for rid in world:
        ex.release(rid)
    ex.alloc.check_invariants()
    return out


def _migrating_run(cfg, params, kv_dtype, requests, migrate_at,
                   dst_cache_pages=0, warm_tokens=None):
    """Drive on src; migrate each request at its ``migrate_at`` decode
    count; finish on dst. Returns (streams, ref_pages_total)."""
    src = _executor(cfg, params, kv_dtype=kv_dtype)
    dst = _executor(cfg, params, kv_dtype=kv_dtype)
    cache = None
    if dst_cache_pages:
        cache = PrefixCache(dst_cache_pages, block_size=PAGE, alloc=dst.alloc)
        dst.attach_cache(cache)
    world = {r.req_id: r for r in requests}
    owner = {rid: "src" for rid in world}
    steps = 0
    if warm_tokens is not None:
        # destination computes the shared prompt once and publishes it to
        # its radix cache — the §15 reference-transfer target
        wid = 10_000
        warm = Request(wid, 0.0, len(warm_tokens), 1, ttft_slo=10.0,
                       tpot_slo=10.0, tokens=list(warm_tokens))
        wworld = {wid: warm}
        while warm.active:
            _step(dst, wworld, [wid], steps)
            steps += 1
        cache.insert_request(wid, list(warm_tokens), float(steps))
        cache.end_request(wid)
        dst.release(wid)
        dst.alloc.check_invariants()
    nref_total = 0
    while any(r.active for r in world.values()):
        _step(src, world, [rid for rid, o in owner.items() if o == "src"],
              steps)
        _step(dst, world, [rid for rid, o in owner.items() if o == "dst"],
              steps)
        for rid, r in world.items():
            if (owner[rid] != "src" or r.state is not RequestState.DECODE
                    or r.generated < migrate_at[rid] or not r.active):
                continue
            payload = capture_kv(src, rid)
            assert payload is not None
            assert payload.n_tokens == src.alloc.lens[rid]
            src.release(rid)
            nref = install_kv_pages(dst, cache, r, payload, float(steps))
            assert nref is not None, "destination sized to host the table"
            nref_total += nref
            owner[rid] = "dst"
            src.alloc.check_invariants()
            dst.alloc.check_invariants()
            tbl = dst.alloc.tables[rid]
            assert len(tbl) == payload.n_pages
            assert dst.alloc.lens[rid] == payload.n_tokens
            # reference-transferred pages are shared (pinned by the radix
            # tree AND this request); their *values* are validated by the
            # stream-parity assertion downstream
            for p in tbl[:nref]:
                assert dst.alloc.refcount[p] >= 2
            # materialized pages are a bitwise scatter of the payload —
            # values AND (for quantized KV) the verbatim scale rows
            if len(tbl) > nref:
                sel = tbl[nref:]
                np.testing.assert_array_equal(
                    gather_pages(dst.k_pages, sel), payload.k[:, nref:])
                np.testing.assert_array_equal(
                    gather_pages(dst.v_pages, sel), payload.v[:, nref:])
                if payload.k_scales is not None:
                    ssel = dst.alloc.scale_table(rid)[nref:]
                    np.testing.assert_array_equal(
                        gather_pages(dst.k_scales, ssel),
                        payload.k_scales[:, nref:])
                    np.testing.assert_array_equal(
                        gather_pages(dst.v_scales, ssel),
                        payload.v_scales[:, nref:])
        steps += 1
    streams = {rid: list(r.generated_tokens) for rid, r in world.items()}
    for rid, o in owner.items():
        (src if o == "src" else dst).release(rid)
        if cache is not None and o == "dst":
            cache.end_request(rid)
    src.alloc.check_invariants()
    dst.alloc.check_invariants()
    return streams, nref_total


# ---------------------------------------------------------------------------
# the §15 acceptance matrix: scenario × kv_dtype bit-parity
# ---------------------------------------------------------------------------


CASES = [
    pytest.param("multi-turn", "fp32", id="multi-turn-fp32"),
    pytest.param("multi-turn", "int8", id="multi-turn-int8"),
    pytest.param("bursty-gamma", "fp32", id="bursty-gamma-fp32",
                 marks=pytest.mark.slow),
    pytest.param("bursty-gamma", "int8", id="bursty-gamma-int8",
                 marks=pytest.mark.slow),
    pytest.param("multi-tenant-adversarial", "fp32", id="adversarial-fp32",
                 marks=pytest.mark.slow),
    pytest.param("multi-tenant-adversarial", "int8", id="adversarial-int8",
                 marks=pytest.mark.slow),
]


@pytest.mark.parametrize("scenario,kv_dtype", CASES)
def test_migration_stream_bit_identical_to_oracle(setup, scenario, kv_dtype):
    """Each request migrates at a different mid-decode point; its full
    token stream must equal the never-migrated single-executor oracle's,
    token for token."""
    cfg, params = setup
    n_new = 8
    mk = lambda: _scenario_requests(cfg, scenario, n_req=3, n_new=n_new,
                                    seed=13)
    oracle = _oracle(cfg, params, kv_dtype, mk())
    migrate_at = {0: 1, 1: 3, 2: 5}          # staggered handoff points
    streams, _ = _migrating_run(cfg, params, kv_dtype, mk(), migrate_at)
    assert streams == oracle, \
        f"{scenario}/{kv_dtype}: migrated streams diverged from oracle"
    assert all(len(s) == n_new for s in streams.values())


@pytest.mark.parametrize("kv_dtype", [
    "fp32", pytest.param("int8", marks=pytest.mark.slow)])
def test_shared_prefix_migrates_by_reference(setup, kv_dtype):
    """Mid-multi-turn handoff: the destination's radix cache already holds
    request 0's full prompt and the 2-block prefix request 1 shares with
    it, so those blocks transfer as refcounted references (zero wire
    bytes) — and both streams still match the oracle."""
    cfg, params = setup
    prefix = [int(x) for x in jax.random.randint(
        jax.random.PRNGKey(2), (2 * PAGE,), 0, cfg.vocab)]

    def mk():
        reqs = _scenario_requests(cfg, "multi-turn", n_req=2, n_new=6,
                                  seed=21)
        for r in reqs:
            # shared 2-block prefix, then a forced divergence token so the
            # radix match for request 1 stops at exactly 2 blocks
            r.tokens = prefix + [(100 + r.req_id) % cfg.vocab] \
                + list(r.tokens)[:12]
            r.prompt_len = len(r.tokens)
        return reqs

    reqs = mk()
    oracle = _oracle(cfg, params, kv_dtype, mk())
    streams, nref = _migrating_run(cfg, params, kv_dtype, reqs,
                                   migrate_at={0: 2, 1: 4},
                                   dst_cache_pages=16,
                                   warm_tokens=list(reqs[0].tokens))
    # request 0: every full prompt block by reference; request 1: the
    # shared prefix only (divergence token breaks block 2's hash)
    assert nref == reqs[0].prompt_len // PAGE + len(prefix) // PAGE
    assert streams == oracle


def test_recompute_fallback_matches_oracle(setup):
    """No payload crosses the wire: the migrated request re-prefills its
    full known prefix (prompt + generated so far) on the destination via
    ``preempt_requeue`` and the continuation still matches the oracle."""
    cfg, params = setup
    mk = lambda: _scenario_requests(cfg, "multi-turn", n_req=2, n_new=8,
                                    seed=17)
    oracle = _oracle(cfg, params, "fp32", mk())
    src = _executor(cfg, params)
    dst = _executor(cfg, params)
    world = {r.req_id: r for r in mk()}
    owner = {rid: "src" for rid in world}
    migrate_at = {0: 2, 1: 4}
    steps = 0
    while any(r.active for r in world.values()):
        _step(src, world, [r for r, o in owner.items() if o == "src"], steps)
        _step(dst, world, [r for r, o in owner.items() if o == "dst"], steps)
        for rid, r in world.items():
            if (owner[rid] == "src" and r.state is RequestState.DECODE
                    and r.active and r.generated >= migrate_at[rid]):
                src.release(rid)             # pages dropped, nothing shipped
                r.preempt_requeue()
                assert r.state is RequestState.PREFILL and r.prefilled == 0
                owner[rid] = "dst"
                src.alloc.check_invariants()
        steps += 1
    streams = {rid: list(r.generated_tokens) for rid, r in world.items()}
    assert streams == oracle
    for rid, o in owner.items():
        (src if o == "src" else dst).release(rid)
    src.alloc.check_invariants()
    dst.alloc.check_invariants()


def test_install_rejects_unhostable_table_and_rolls_back(setup):
    """A destination whose per-seq table cap, page pool, or KV dtype cannot
    host the payload returns None (→ recompute fallback) with no leaked
    pages."""
    cfg, params = setup
    src = _executor(cfg, params, num_pages=96, max_pages=16)
    reqs = _scenario_requests(cfg, "bursty-gamma", n_req=1, n_new=4, seed=5)
    world = {r.req_id: r for r in reqs}
    steps = 0
    while world[0].state is not RequestState.DECODE:
        _step(src, world, [0], steps)
        steps += 1
    payload = capture_kv(src, 0)
    assert payload.n_pages >= 2
    src.release(0)
    # cap smaller than the table → refuse
    tiny = _executor(cfg, params, num_pages=96,
                     max_pages=payload.n_pages - 1)
    free0 = len(tiny.alloc._free)
    assert install_kv_pages(tiny, None, world[0], payload, 0.0) is None
    assert 0 not in tiny.alloc.tables and len(tiny.alloc._free) == free0
    tiny.alloc.check_invariants()
    # pool exhausted mid-extend → roll back the already-extended pages too
    small = _executor(cfg, params, num_pages=payload.n_pages - 1,
                      max_pages=16)
    free0 = len(small.alloc._free)
    assert install_kv_pages(small, None, world[0], payload, 0.0) is None
    assert 0 not in small.alloc.tables and len(small.alloc._free) == free0
    small.alloc.check_invariants()
    # cross-dtype pools → refuse (values are never requantized in flight)
    other = _executor(cfg, params, kv_dtype="int8")
    assert install_kv_pages(other, None, world[0], payload, 0.0) is None
    other.alloc.check_invariants()
    src.alloc.check_invariants()


# ---------------------------------------------------------------------------
# randomized interleavings on a tiny fake executor (allocator contract)
# ---------------------------------------------------------------------------


class _TinyExec:
    """Minimal data plane: BlockAllocator + one-layer jnp page pools (+
    scale pools in quantized trim) — enough surface for capture/install to
    run."""

    def __init__(self, num_pages, quant, block=4):
        self.alloc = BlockAllocator(num_pages, block)
        shape = (num_pages, 1, 2, block)
        dt = jnp.int8 if quant else jnp.float32
        self.k_pages = (jnp.zeros(shape, dt),)
        self.v_pages = (jnp.zeros(shape, dt),)
        if quant:
            sshape = (num_pages, 1, block)
            self.k_scales = (jnp.zeros(sshape, jnp.float32),)
            self.v_scales = (jnp.zeros(sshape, jnp.float32),)
        self.max_pages = num_pages
        self.block = block

    def grow(self, rid, n, fill):
        if self.alloc.extend(rid, n) is None:
            return False
        idx = jnp.asarray(self.alloc.tables[rid])
        self.k_pages = (self.k_pages[0].at[idx].set(fill),)
        self.v_pages = (self.v_pages[0].at[idx].set(-fill),)
        if hasattr(self, "k_scales"):
            stbl = jnp.asarray(self.alloc.scale_table(rid))
            self.k_scales = (self.k_scales[0].at[stbl].set(float(fill)),)
            self.v_scales = (self.v_scales[0].at[stbl].set(float(fill) + 0.5),)
        return True

    def release(self, rid):
        self.alloc.release(rid)


@dataclasses.dataclass
class _FakeReq:
    req_id: int
    tokens: list


COMMON = list(range(400, 480))          # shared token pool → cache overlap


def _run_migration_program(program, num_pages, quant):
    """Interpret (op, rid, n) triples against a src/dst executor pair,
    asserting BOTH allocators' invariants after every op — the §15
    acceptance clause."""
    src = _TinyExec(num_pages, quant)
    dst = _TinyExec(num_pages, quant)
    cache = PrefixCache(max(2, num_pages // 2), block_size=dst.block,
                        alloc=dst.alloc)
    toks = {}
    for op, rid, n in program:
        if op == "grow":
            if rid not in dst.alloc.tables and src.grow(rid, n, rid + 1):
                toks[rid] = COMMON[:src.alloc.lens[rid]]
        elif op == "migrate" and rid in src.alloc.tables \
                and rid not in dst.alloc.tables:
            payload = capture_kv(src, rid)
            assert payload is not None
            src.release(rid)
            req = _FakeReq(rid, toks[rid])
            nref = install_kv_pages(dst, cache, req, payload, 0.0)
            if nref is not None:
                tbl = dst.alloc.tables[rid]
                assert dst.alloc.lens[rid] == payload.n_tokens
                if len(tbl) > nref:          # materialized tail is bitwise
                    np.testing.assert_array_equal(
                        gather_pages(dst.k_pages, tbl[nref:]),
                        payload.k[:, nref:])
        elif op == "release_dst" and rid in dst.alloc.tables:
            cache.end_request(rid)
            dst.release(rid)
        elif op == "evict":
            cache.evict_for(n)
        src.alloc.check_invariants()
        dst.alloc.check_invariants()
    for rid in list(src.alloc.tables):
        src.release(rid)
    for rid in list(dst.alloc.tables):
        cache.end_request(rid)
        dst.release(rid)
    cache.evict_for(10 ** 9)            # drop every tree-adopted page
    src.alloc.check_invariants()
    dst.alloc.check_invariants()
    assert len(src.alloc._free) == num_pages
    assert len(dst.alloc._free) == num_pages


OPS = ("grow", "migrate", "migrate", "release_dst", "evict")


def test_migration_interleavings_seeded():
    """Deterministic seeded sweep (runs even without hypothesis)."""
    import random
    for seed in range(30):
        rng = random.Random(seed)
        program = [(rng.choice(OPS), rng.randrange(4), rng.randint(1, 9))
                   for _ in range(rng.randint(1, 30))]
        _run_migration_program(program, rng.randint(8, 24), seed % 2 == 0)


def test_migration_interleavings_random():
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")

    @st.composite
    def programs(draw):
        n = draw(st.integers(1, 30))
        return [(draw(st.sampled_from(OPS)), draw(st.integers(0, 3)),
                 draw(st.integers(1, 9))) for _ in range(n)]

    @hyp.given(programs(), st.integers(8, 24), st.booleans())
    @hyp.settings(max_examples=100, deadline=None)
    def run(program, num_pages, quant):
        _run_migration_program(program, num_pages, quant)

    run()
