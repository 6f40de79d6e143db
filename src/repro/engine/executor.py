"""Step executors: the engine's data plane.

* ``SimExecutor`` — discrete-event world model: step time from a ground-truth
  linear cost model (+ lognormal jitter + optional GC pauses, reproducing the
  paper's §4 observation). The scheduler under test never sees these true
  coefficients — it calibrates its own online (exactly the paper's setup).

* ``PagedTransformerExecutor`` — real JAX execution of the FairBatching
  hybrid step for dense-GQA archs at smoke scale: paged KV cache
  (kv_manager) driven through the paged-attention kernel contract (ref
  backend on CPU, Pallas on TPU). The default ``mode="fused"`` packs the
  whole BatchPlan — every prefill chunk and decode token — into ONE padded
  token stream and launches a single forward per step (DESIGN.md §11), so
  the wall-clock step times feeding the scheduler's online calibration
  (paper §3.2) measure the unified batch the fairness math reasons about.
  ``mode="sequential"`` keeps the per-item launch loop as the parity oracle.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
import time
import weakref
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from ..configs.base import ArchConfig
from ..core.cost_model import LinearCostModel
from ..core.types import BatchPlan, TaskKind
from ..distributed.sharding import (constrain, param_specs, serving_rules,
                                    use_rules)
from ..kernels import quant as kvq
from ..kernels.ops import (paged_attention_op, paged_attention_quant_op,
                           paged_attention_ragged_op,
                           paged_attention_ragged_quant_op)
from ..kernels.paged_attention import get_ragged_tiling, mesh_tiling_key
from ..models.layers import attn_qkv, mlp_apply
from ..models.moe import moe_capacity, moe_dense_exact
from ..models.module import rmsnorm
from . import trace
from .kv_manager import BlockAllocator

# the executor's phase spans (engine/trace.py), one of each, reused
_EXTEND, _STAGE, _H2D, _DISPATCH, _SYNC = map(
    trace.span, ("extend", "stage", "h2d", "dispatch", "sync"))


@dataclasses.dataclass
class SimExecutor:
    """True step-time generator (the 'GPU')."""
    true_model: LinearCostModel
    noise_sigma: float = 0.02          # lognormal jitter on step time
    gc_pause_every: float = 0.0        # seconds of sim time between GC STWs
    gc_pause_len: float = 0.25
    seed: int = 0
    # speculative decode world model (DESIGN.md §18): per-draft acceptance
    # probability and the draft pass's cost as a fraction of a target-pass
    # token (self-speculative ≈ truncated-layer depth / full depth)
    spec_acceptance: float = 0.7
    spec_draft_frac: float = 0.3

    def __post_init__(self):
        self._rng = np.random.default_rng(self.seed)
        self._next_gc = self.gc_pause_every or math.inf

    def execute(self, plan: BatchPlan, requests, now: float) -> tuple[float, dict]:
        nt = plan.total_new_tokens
        if nt == 0:
            return 0.0, {}
        ctx = sum(requests[it.req_id].to_sched_task().cost_context()
                  for it in plan.items)
        t = self.true_model.step_time(nt, ctx)
        t *= float(self._rng.lognormal(0.0, self.noise_sigma))
        if now + t >= self._next_gc:
            t += self.gc_pause_len          # stop-the-world GC (paper §4)
            self._next_gc = now + t + self.gc_pause_every
        return t, {}

    def execute_spec(self, plan: BatchPlan, requests, now: float,
                     gamma: int) -> tuple[float, dict]:
        """ONE speculative round: γ drafts + one γ+1-wide verify pass.

        Returns ``(dt, accepted)`` where ``accepted[req_id]`` is the round's
        emitted-token count (1 verified fallback + leading accepted drafts,
        a truncated-geometric draw at ``spec_acceptance``). The verify pass
        prices like a Tq=γ+1 target step; drafting adds
        ``spec_draft_frac × step_time(n·γ, ctx)``. RNG draw order is fixed
        (jitter, then per-item acceptance in plan order) so lock-step and
        pipelined engines replay identical worlds (DESIGN.md §18).
        """
        items = plan.decode_items
        n = len(items)
        if n == 0:
            return 0.0, {}
        ctx = sum(requests[it.req_id].to_sched_task().cost_context()
                  for it in items)
        t = (self.true_model.step_time(n * (gamma + 1), ctx)
             + self.spec_draft_frac * self.true_model.step_time(n * gamma,
                                                                ctx))
        t *= float(self._rng.lognormal(0.0, self.noise_sigma))
        if now + t >= self._next_gc:
            t += self.gc_pause_len          # stop-the-world GC (paper §4)
            self._next_gc = now + t + self.gc_pause_every
        accepted = {}
        for it in items:
            a = 0
            while a < gamma and self._rng.random() < self.spec_acceptance:
                a += 1
            accepted[it.req_id] = a + 1     # +1: the verified fallback token
        return t, accepted


def _bucket(n: int, lo: int = 8) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


def _ladder(n: int, lo: int) -> int:
    """1.5-step bucket ladder: lo, 1.5·lo, 2·lo, 3·lo, 4·lo, … — finer than
    powers of two (≤ 33% padding waste) at ~2× the compile-key count, which
    the two-axis compile guard still bounds (DESIGN.md §11)."""
    b = lo
    while b < n:
        b = b * 3 // 2 if b % 3 else b * 4 // 3
    return b


def scatter_rows(k_pools: tuple, v_pools: tuple, layer: int, k, v, pages,
                 slots) -> tuple:
    """Write token rows k, v (N, Hkv[, D]) at (pages, slots) of layer
    ``layer``'s pools — (P, Hkv, D, page) K/V pages or (P, Hkv, page) scale
    pages. Returns the updated (k_pools, v_pools)."""
    def put(pools, rows):
        idx = (pages, slice(None)) + (slice(None),) * (rows.ndim - 2) \
            + (slots,)
        new = pools[layer].at[idx].set(rows)
        return pools[:layer] + (new,) + pools[layer + 1:]
    return put(k_pools, k), put(v_pools, v)


@functools.partial(jax.jit, donate_argnums=0)
def copy_pages(pools: tuple, src, dst) -> tuple:
    """Copy pages ``src`` onto pages ``dst`` in every layer's pool, in place
    (the pools are donated; callers keep the returned tuple)."""
    return tuple(p.at[dst].set(p[src]) for p in pools)


@functools.partial(jax.jit, donate_argnums=0)
def set_pages(pools: tuple, dst, values) -> tuple:
    """Write ``values`` (layers, len(dst), ...) into pages ``dst`` of every
    layer's pool, in place."""
    return tuple(p.at[dst].set(values[l]) for l, p in enumerate(pools))


def gather_pages(pools: tuple, idx) -> np.ndarray:
    """Pages ``idx`` of every layer's pool as one host array (layers,
    len(idx), ...)."""
    return np.stack([np.asarray(p[jnp.asarray(idx, jnp.int32)])
                     for p in pools])


@dataclasses.dataclass
class _PackedSeq:
    """Host-side view of one sequence in the packed step (DESIGN.md §11)."""
    req_id: int
    tokens: list            # new tokens this step (chunk, or [fed-back token])
    pos0: int               # global position of tokens[0]
    ctx: int                # context_len incl. this step's tokens
    emits: bool             # produces an output token this step


class PagedTransformerExecutor:
    """Real hybrid-step executor over a paged KV cache (dense / MoE GQA).

    With ``mesh`` given, the whole step shards over the ``(data, model)``
    mesh via the logical-axis rule tables (DESIGN.md §17): params are
    device_put per ``DecoderLM.axes()``, the paged K/V pools (and quant
    scale pages) shard on their kv-head dim over ``model``, and the step
    bodies trace under ``use_rules`` so GSPMD partitions QKV/attention/
    o-proj with one all-reduce per layer (plus the MoE combine). The
    host-side ``BlockAllocator`` is untouched — page IDs are global and
    replicated; only each page's head slice is local to a shard — so COW,
    prefix reuse, and the scale-page bijection survive the split as-is.
    ``mesh=None`` is byte-for-byte the old single-device executor.
    """

    def __init__(self, cfg: ArchConfig, params, *, num_pages: int = 256,
                 page_size: int = 128, max_pages_per_seq: int = 16,
                 mode: str = "fused",
                 ragged_attention: Optional[bool] = None,
                 capture_logits: bool = False,
                 kv_dtype: str = "fp32",
                 trim_page_tables: bool = True,
                 mesh=None,
                 moe_impl: str = "exact"):
        assert cfg.family in ("dense", "moe") and cfg.ssm is None
        assert mode in ("fused", "sequential")
        # MoE FFN path: "exact" (dense per-token oracle) keeps the fused ==
        # sequential bit-parity contract — per-token math is independent of
        # how the step packs tokens. "capacity" opts into the production
        # dispatch (expert-parallel all-to-all under the rules table), whose
        # per-chunk capacity depends on chunk size, so token drops — and
        # hence parity — vary with packing (DESIGN.md §17).
        assert moe_impl in ("exact", "capacity")
        self.moe_impl = moe_impl
        self.cfg = cfg
        self.params = params
        self.page_size = page_size
        self.mode = mode
        # -- mesh sharding (DESIGN.md §17) -----------------------------
        self.mesh = mesh
        self.rules = None
        self._mesh_key = mesh_tiling_key(mesh)
        # model-axis shards this data plane divides per-token compute over:
        # the scale factor for per-shard scheduler budgets
        # (cost_model.per_shard_model)
        self.n_shards = 1 if mesh is None else int(mesh.shape.get("model", 1))
        if mesh is not None:
            from ..models.lm import DecoderLM
            self.rules = serving_rules(mesh, cfg)
            self.params = jax.device_put(
                params, param_specs(DecoderLM(cfg).axes(), self.rules))
        # quantized paged KV (DESIGN.md §14): values stored int8/fp8 in the
        # data pages, per-(token, kv-head) f32 scales in the allocator's
        # scale pages; None = unquantized fp32 storage
        self.kv_dtype = kv_dtype
        self.qspec = kvq.kv_quant_spec(kv_dtype)
        # pages-bucket trim (DESIGN.md §14): stage fused block tables at the
        # ladder over the step's widest table instead of max_pages_per_seq.
        # Shrinking the gathered context reorders the fp reduction, so the
        # §11 bitwise fused==sequential invariant is verified with the trim
        # pinned off (values agree to fp reassociation either way).
        self.trim_page_tables = trim_page_tables
        # fused-step attention backend (DESIGN.md §11): on TPU the packed
        # stream feeds the ragged Pallas kernel directly; elsewhere the
        # jnp oracle would re-gather each token's whole context, so the step
        # routes q through a host-staged per-sequence padded view into the
        # same batched paged-attention op the sequential path uses
        self._ragged_attn = (jax.default_backend() == "tpu"
                             if ragged_attention is None else ragged_attention)
        self.alloc = BlockAllocator(num_pages, page_size)
        # Optional repro.cache.PrefixCache sharing this allocator
        # (DESIGN.md §10): cache-hit requests arrive with forked block
        # tables, and under memory pressure we evict its unpinned leaves.
        self.prefix_cache = None
        # page 0 is the trash page: bucket-padding tokens write there so
        # they can never clobber a live slot (attention masks them anyway)
        reserved = self.alloc.extend(-1, page_size)
        assert reserved == [0]
        self.max_pages = max_pages_per_seq
        # one pool per layer, (page, kv head, head dim, slot): one page of
        # one kv head is the (head_dim, page) tile the attention kernel
        # streams (DESIGN.md §3). Separate per-layer arrays let each step
        # update a layer's pool in place; slices of one stacked pool made
        # XLA copy the whole pool around every kernel launch.
        shape = (num_pages, cfg.n_kv_heads, cfg.head_dim, page_size)
        kv_store = jnp.float32 if self.qspec is None else self.qspec.dtype
        # page pools shard on the kv-head dim over `model` (a no-op when the
        # rules table replicated kv_heads for indivisible head counts);
        # page/slot dims stay replicated so the host-global page IDs of the
        # allocator index every shard identically (DESIGN.md §17)
        self._kv_sharding = (None if mesh is None else self.rules.sharding(
            (None, "kv_heads", None, None)))
        self._scale_sharding = (None if mesh is None else self.rules.sharding(
            (None, "kv_heads", None)))
        # mesh axis the attention kernels split kv heads (and their query
        # heads) over; None when unsharded or when the heads do not divide
        self._head_axis = None
        if mesh is not None and self.rules.table["kv_heads"] is not None \
                and self.rules.table["q_heads"] == self.rules.table["kv_heads"]:
            self._head_axis = self.rules.table["kv_heads"]
        def pools(shape, dtype):
            return tuple(jnp.zeros(shape, dtype) for _ in range(cfg.n_layers))
        self.k_pages = self._shard_kv(pools(shape, kv_store))
        self.v_pages = self._shard_kv(pools(shape, kv_store))
        if self.qspec is None:
            self.k_scales = self.v_scales = None
        else:
            sshape = (num_pages, cfg.n_kv_heads, page_size)
            self.k_scales = self._shard_scale(pools(sshape, jnp.float32))
            self.v_scales = self._shard_scale(pools(sshape, jnp.float32))
            # pad tokens redirect scales to the trash page's scale page,
            # which the construction order above pins to id 0
            assert self.alloc.scale_of[0] == 0
        self._chunk_fn = self._jit_step(self._chunk_step, "n_tok")
        self._decode_fn = self._jit_step(self._decode_step, "bsz")
        self._fused_fn = self._jit_step(self._fused_step, "t_bucket",
                                        "s_bucket", "tq_bucket", "pg_bucket")
        self._multi_fn = self._jit_step(self._multi_decode_step, "bsz",
                                        "horizon")
        # speculative decode (DESIGN.md §18): a draft adapter installed via
        # set_draft() enables execute_multi(speculate=γ); force_reject
        # zeroes every acceptance in-graph (the parity edge-case switch)
        self.draft = None
        self._spec_fn = None
        self.spec_force_reject = False
        self.last_spec_accepted = 0
        self.last_spec_drafted = 0
        # items the last execute() could not serve (out of KV blocks); the
        # engine skips their progress so the scheduler retries them
        self.last_deferred: frozenset[int] = frozenset()
        # opt-in test/bench introspection: req_id -> np logits of the last
        # step. Off by default — the extra device→host logits copy would
        # land inside the wall-clock the §3.2 calibration observes.
        self.capture_logits = capture_logits
        self.last_logits: dict[int, np.ndarray] = {}
        # dispatch / compile-ladder accounting (DESIGN.md §11): steady-state
        # serving must hit a warm jit cache — benches and the regression
        # guard in tests/test_fused_executor.py read these
        self.n_dispatches = 0
        self.compile_keys: set = set()
        self._staging: dict[tuple, dict[str, np.ndarray]] = {}
        self._zero_table = jnp.zeros(self.max_pages, jnp.int32)

    # ------------------------------------------------------------------
    # mesh plumbing (DESIGN.md §17)
    # ------------------------------------------------------------------

    def _shard_kv(self, pages):
        return pages if self._kv_sharding is None else jax.device_put(
            pages, self._kv_sharding)

    def _shard_scale(self, scales):
        return scales if self._scale_sharding is None else jax.device_put(
            scales, self._scale_sharding)

    def _jit_step(self, fn, *static_argnames):
        """jit a step body with the weights passed as its first argument.
        The bodies read ``self.params``; closed over instead, the weights
        would be embedded in every compiled program as literal constants
        (gigabytes at published widths). The returned callable binds them,
        so call sites pass only the step's own operands. The K/V pools and
        scale pages (the body's first three operands) are donated: every
        caller replaces them with the step's outputs, and without donation
        the step would hold a second copy of the pools. The body reaches
        the executor through a weak reference: JAX's trace caches outlive
        the executor, and a strong one would keep its pools alive."""
        owner, method = weakref.ref(self), fn.__func__

        def body(params, *args, **kwargs):
            ex = owner()
            saved, ex.params = ex.params, params
            try:
                return method(ex, *args, **kwargs)
            finally:
                ex.params = saved
        jitted = jax.jit(body, static_argnames=static_argnames,
                         donate_argnums=(1, 2, 3))
        step = functools.partial(jitted, self.params)
        step._cache_size = jitted._cache_size
        return step

    def _head_parallel(self, op, q, pools, *meta, **kw):
        """Run an attention backend on each shard's heads. ``pools`` are
        (P, Hkv[, D], page) layer pools sharded on their kv-head dim, q has
        its head dim second-minor, ``meta`` (tables, lengths) is replicated.
        A Pallas kernel cannot be partitioned by GSPMD, so under a mesh
        that splits the heads the call runs inside ``shard_map``, where
        each device attends its own head group with no communication."""
        if self._head_axis is None:
            return op(q, *pools, *meta, **kw)
        ax = self._head_axis
        q_spec = PartitionSpec(*([None] * (q.ndim - 2)), ax, None)
        pool_specs = tuple(PartitionSpec(None, ax, *([None] * (p.ndim - 2)))
                           for p in pools)
        return jax.shard_map(
            lambda q_, *rest: op(q_, *rest, **kw), mesh=self.mesh,
            in_specs=(q_spec,) + pool_specs + (PartitionSpec(),) * len(meta),
            out_specs=q_spec, check_vma=False)(q, *pools, *meta)

    @contextlib.contextmanager
    def _step_ctx(self):
        """Trace/launch context for the jitted step bodies: activates the
        mesh and the logical-axis rules so ``constrain`` lowers to sharding
        constraints. A plain no-op when ``mesh is None`` — the single-device
        graphs are unchanged."""
        if self.mesh is None:
            yield
        else:
            with self.mesh, use_rules(self.rules):
                yield

    # ------------------------------------------------------------------
    # jitted step bodies
    # ------------------------------------------------------------------

    def _embed(self, tokens):
        return self.params["embed"][tokens]

    def _head(self, h_last):
        p = self.params
        h = rmsnorm(h_last, p["ln_f"], self.cfg.norm_eps)
        logits = h @ p["head"]
        return constrain(logits, (None,) * (logits.ndim - 1) + ("vocab",))

    def _layer_ffn(self, lp, x):
        """Residual FFN block: gated MLP, or the MoE path for moe-family
        archs (``moe_impl``: exact per-token oracle vs capacity dispatch —
        the latter is the expert-parallel path, its `constrain` calls give
        the all-to-all dispatch/combine under the rules table)."""
        cfg = self.cfg
        h = rmsnorm(x, lp["ln2"], cfg.norm_eps)
        if cfg.moe is not None:
            b, t, d = h.shape
            moe_fn = (moe_capacity if self.moe_impl == "capacity"
                      else moe_dense_exact)
            y = moe_fn(h.reshape(b * t, d), lp["moe"], cfg.moe)
            return x + y.reshape(b, t, d)
        return x + mlp_apply(lp["mlp"], h)

    def _constrain_qkv(self, q, k, v):
        """Pin the packed stream's activation layout: q on the (sharded)
        query-head dim, k/v on the kv-head dim matching the page pools —
        identity when no rules context is active."""
        q = constrain(q, (None, None, "q_heads", None))
        k = constrain(k, (None, None, "kv_heads", None))
        v = constrain(v, (None, None, "kv_heads", None))
        return q, k, v

    def _write_pages(self, k_pages, v_pages, scales, layer, k, v, table,
                     stable, positions, valid=None):
        """k, v: (B, T, Hkv, D); positions: (B, T) global; table/stable:
        (B, n_pages) data/scale page ids. When quantized, values quantize
        on scatter and their per-(token, kv-head) scales land in the scale
        pages (DESIGN.md §14); ``scales`` is () in fp32 mode."""
        b, t = positions.shape
        page_ids = jnp.take_along_axis(
            table, positions // self.page_size, axis=1)       # (B, T)
        slots = positions % self.page_size
        if valid is not None:
            page_ids = jnp.where(valid, page_ids, 0)          # → trash page
        flat_pg = page_ids.reshape(-1)
        flat_sl = slots.reshape(-1)
        if self.qspec is not None:
            k, ks = kvq.quantize_kv(k, self.qspec)
            v, vs = kvq.quantize_kv(v, self.qspec)
            spage_ids = jnp.take_along_axis(
                stable, positions // self.page_size, axis=1)
            if valid is not None:
                spage_ids = jnp.where(valid, spage_ids, 0)    # → trash scales
            flat_sp = spage_ids.reshape(-1)
            scales = scatter_rows(*scales, layer, ks.reshape(b * t, -1),
                                  vs.reshape(b * t, -1), flat_sp, flat_sl)
        k_pages, v_pages = scatter_rows(
            k_pages, v_pages, layer, k.reshape(b * t, *k.shape[2:]),
            v.reshape(b * t, *v.shape[2:]), flat_pg, flat_sl)
        return k_pages, v_pages, scales

    def _attend(self, q, k_pages, v_pages, scales, layer, table, stable,
                ctx_lens, q_starts):
        """Batched paged attention over layer ``layer``'s pages, routed to
        the fp32 or the dequantizing quantized backend."""
        if self.qspec is None:
            return self._head_parallel(
                paged_attention_op, q, (k_pages[layer], v_pages[layer]),
                table, ctx_lens, q_starts, window=self.cfg.window)
        return self._head_parallel(
            paged_attention_quant_op, q,
            (k_pages[layer], v_pages[layer], scales[0][layer],
             scales[1][layer]), table, stable, ctx_lens, q_starts,
            window=self.cfg.window)

    def _forward(self, k_pages, v_pages, scales, x, positions, table, stable,
                 ctx_lens, valid=None, n_layers=None):
        """Paged forward. ``n_layers`` truncates the stack (early-exit
        draft pass, DESIGN.md §18); None runs the full model."""
        cfg = self.cfg
        for l in range(cfg.n_layers if n_layers is None else n_layers):
            lp = jax.tree.map(lambda a: a[l], self.params["layers"])
            h = rmsnorm(x, lp["ln1"], cfg.norm_eps)
            q, k, v = self._constrain_qkv(*attn_qkv(lp["attn"], h, positions,
                                                    cfg))
            k_pages, v_pages, scales = self._write_pages(
                k_pages, v_pages, scales, l, k, v, table, stable, positions,
                valid)
            o = self._attend(q, k_pages, v_pages, scales, l, table, stable,
                             ctx_lens, positions[:, 0])
            x = x + o.reshape(*x.shape[:2], cfg.q_dim) @ lp["attn"]["wo"]
            x = constrain(x, (None, None, "embed"))
            x = self._layer_ffn(lp, x)
        return k_pages, v_pages, scales, x

    def _chunk_step(self, k_pages, v_pages, scales, tokens, pos0, table,
                    stable, n_valid, *, n_tok):
        """One prefill chunk, B=1. tokens: (n_tok,) padded; n_valid real.

        Sequential-mode (and parity-test) body; the serving path is
        ``_fused_step`` below.
        """
        x = self._embed(tokens)[None]                      # (1, T, d)
        positions = (pos0 + jnp.arange(n_tok))[None]
        valid = (jnp.arange(n_tok)[None] < n_valid)
        # pad tokens keep monotone positions (causal mask stays exact) but
        # their K/V lands on the trash page and context_lens excludes them
        ctx = (pos0 + n_valid)[None]
        k_pages, v_pages, scales, x = self._forward(
            k_pages, v_pages, scales, x, positions, table[None],
            stable[None], ctx, valid)
        h_last = x[0, jnp.maximum(n_valid - 1, 0)]
        return k_pages, v_pages, scales, self._head(h_last)

    def _decode_step(self, k_pages, v_pages, scales, tokens, positions,
                     tables, stables, ctx_lens, *, bsz):
        x = self._embed(tokens)[:, None]                  # (B, 1, d)
        k_pages, v_pages, scales, x = self._forward(
            k_pages, v_pages, scales, x, positions[:, None], tables,
            stables, ctx_lens)
        return k_pages, v_pages, scales, self._head(x[:, 0])

    def _multi_decode_step(self, k_pages, v_pages, scales, tokens, positions,
                           tables, stables, ctx_lens, *, bsz, horizon):
        """``horizon`` greedy decode steps as ONE dispatch (DESIGN.md §12).

        Each unrolled iteration is exactly the ``_decode_step`` body — same
        shapes, same ops, so emitted tokens are bit-identical to running the
        steps one dispatch at a time — with the argmax token fed back and
        K/V writes advancing in-loop (the caller pre-reserved ``horizon``
        slots per sequence in the block tables). Returns the (horizon, B)
        token matrix.
        """
        emitted = []
        for h in range(horizon):
            x = self._embed(tokens)[:, None]              # (B, 1, d)
            k_pages, v_pages, scales, x = self._forward(
                k_pages, v_pages, scales, x, (positions + h)[:, None],
                tables, stables, ctx_lens + h)
            logits = self._head(x[:, 0])
            tokens = jnp.argmax(logits, -1).astype(jnp.int32)
            emitted.append(tokens)
        return k_pages, v_pages, scales, jnp.stack(emitted)

    def _spec_multi_step(self, k_pages, v_pages, scales, dstate, tokens,
                         positions, tables, stables, ctx_lens, max_emit,
                         *, bsz, rounds, gamma, force_reject):
        """``rounds`` speculative draft/verify rounds as ONE dispatch
        (DESIGN.md §18).

        Per round: γ draft steps (argmax fed forward) build the candidate
        run; one Tq=γ+1 target pass — the chunked-prefill ragged-attention
        contract — verifies the fed-back token plus every draft at once;
        ``n_acc`` leading draft/target matches accept, the verified argmax
        covers the rejection slot, and per-sequence state (token, position,
        context) advances by ``eff = min(n_acc+1, remaining)`` in-graph.
        A sequence whose emission budget (``max_emit``) is exhausted
        freezes: eff=0, its rewrites are byte-idempotent, its state holds.
        Emission is bit-identical to sequential greedy decode by
        construction — the emitted tokens are always target argmaxes over
        exactly the sequential pass's visible key set. ``force_reject``
        zeroes every match (parity edge case: pure verified fallback).

        Returns ``(k_pages, v_pages, scales, dstate, emitted (B, R·(γ+1)),
        counts (B,), accs (R, B))`` — ``emitted[i, :counts[i]]`` is sequence
        i's token stream, ``accs[r]`` its per-round emission.
        """
        draft = self.draft
        G = gamma + 1
        cur_tok, cur_pos, cur_ctx = tokens, positions, ctx_lens
        counts = jnp.zeros(bsz, jnp.int32)
        emitted = jnp.zeros((bsz, rounds * G), jnp.int32)
        rows = jnp.arange(bsz)
        accs = []
        for _ in range(rounds):
            feed = [cur_tok]
            tok = cur_tok
            for j in range(gamma):
                k_pages, v_pages, scales, dstate, logits = draft.step(
                    k_pages, v_pages, scales, dstate, tok, cur_pos + j,
                    tables, stables, cur_ctx + j)
                tok = jnp.argmax(logits, -1).astype(jnp.int32)
                feed.append(tok)
            if draft.needs_sync_pass:
                # write the last draft token's own draft-KV so a fully-
                # accepting sequence enters the next round with complete
                # draft context (logits discarded)
                k_pages, v_pages, scales, dstate, _ = draft.step(
                    k_pages, v_pages, scales, dstate, tok, cur_pos + gamma,
                    tables, stables, cur_ctx + gamma)
            feed = jnp.stack(feed, axis=1)                    # (B, G)
            vpos = cur_pos[:, None] + jnp.arange(G)[None]
            x = self._embed(feed)
            k_pages, v_pages, scales, x = self._forward(
                k_pages, v_pages, scales, x, vpos, tables, stables,
                cur_ctx + gamma)
            tgt = jnp.argmax(self._head(x), -1).astype(jnp.int32)  # (B, G)
            match = (feed[:, 1:] == tgt[:, :-1]).astype(jnp.int32)
            if force_reject:
                match = match * 0
            n_acc = jnp.sum(jnp.cumprod(match, axis=1), axis=1)
            eff = jnp.minimum(n_acc + 1, jnp.maximum(max_emit - counts, 0))
            idx = counts[:, None] + jnp.arange(G)[None]
            idx = jnp.where(jnp.arange(G)[None] < eff[:, None], idx,
                            rounds * G)                        # OOB → drop
            emitted = emitted.at[rows[:, None], idx].set(tgt, mode="drop")
            accs.append(eff)
            counts = counts + eff
            live = eff > 0
            cur_tok = jnp.where(live, tgt[rows, jnp.maximum(eff - 1, 0)],
                                cur_tok)
            cur_pos = cur_pos + eff
            cur_ctx = cur_ctx + eff
        return (k_pages, v_pages, scales, dstate, emitted, counts,
                jnp.stack(accs))

    def _scatter_packed(self, k_pages, v_pages, scales, layer, k, v,
                        tok_pages, tok_slots, tok_spages):
        """Packed-stream K/V scatter: k, v (T, Hkv, D) new rows. Quantizes
        on scatter when a kv quant spec is active (DESIGN.md §14)."""
        if self.qspec is not None:
            k, ks = kvq.quantize_kv(k, self.qspec)
            v, vs = kvq.quantize_kv(v, self.qspec)
            scales = scatter_rows(*scales, layer, ks, vs, tok_spages,
                                  tok_slots)
        k_pages, v_pages = scatter_rows(k_pages, v_pages, layer, k, v,
                                        tok_pages, tok_slots)
        return k_pages, v_pages, scales

    def _fused_step(self, k_pages, v_pages, scales, tokens, positions,
                    tok_pages, tok_slots, tok_spages, tables, stables,
                    ctx_lens, q_starts, q_lens, pos0, last_idx, seq_gather,
                    pack_gather,
                    *, t_bucket, s_bucket, tq_bucket, pg_bucket):
        """The whole BatchPlan as ONE forward (DESIGN.md §11).

        tokens/positions/tok_pages/tok_slots: (T,) packed stream — every
        prefill-chunk token and decode token of the step, padding → trash
        page. tables: (S, pg_bucket) — block tables trimmed to the step's
        pages bucket (padding columns would only add masked-out attention
        work); ctx_lens/q_starts/q_lens/pos0/last_idx: (S,). seq_gather
        (S, Tq)/pack_gather (T,) are the host-staged packed↔per-seq row
        index maps for the batched attention backend. When quantized,
        tok_spages (T,)/stables (S, pg_bucket) carry the scale-page routing
        and ``scales`` is the (k_scales, v_scales) pair — () in fp32 mode.
        Per layer: one K/V scatter for every sequence's writes, one
        attention launch; at the top: one head projection over each
        sequence's last-token hidden state. Returns (k_pages, v_pages,
        scales, logits (S, vocab)).
        """
        cfg = self.cfg
        x = self._embed(tokens)[None]                     # (1, T, d)
        pos2d = positions[None]
        # autotuned kernel tiling for this bucket (DESIGN.md §14), keyed by
        # the mesh shape too (§17) — single-device winners never silently
        # apply to sharded launches; install tilings before serving, the
        # jit cache keys on bucket, not tiling
        kb, tb = get_ragged_tiling(t_bucket, pg_bucket, mesh=self._mesh_key)
        # each phase is a named scope, so the device ops it lowers to carry
        # the phase in their metadata (read from a profiler trace)
        for l in range(cfg.n_layers):
            lp = jax.tree.map(lambda a: a[l], self.params["layers"])
            with jax.named_scope("qkv"):
                h = rmsnorm(x, lp["ln1"], cfg.norm_eps)
                q, k, v = self._constrain_qkv(*attn_qkv(lp["attn"], h, pos2d,
                                                        cfg))
            with jax.named_scope("kv_scatter"):
                k_pages, v_pages, scales = self._scatter_packed(
                    k_pages, v_pages, scales, l, k[0], v[0], tok_pages,
                    tok_slots, tok_spages)
            with jax.named_scope("attention"):
                if self._ragged_attn:
                    if self.qspec is None:
                        o = self._head_parallel(
                            paged_attention_ragged_op, q[0],
                            (k_pages[l], v_pages[l]), tables, ctx_lens,
                            q_starts, q_lens, pos0, window=cfg.window,
                            pages_per_block=kb, q_block=tb)
                    else:
                        o = self._head_parallel(
                            paged_attention_ragged_quant_op, q[0],
                            (k_pages[l], v_pages[l], scales[0][l],
                             scales[1][l]), tables, stables, ctx_lens,
                            q_starts, q_lens, pos0, window=cfg.window,
                            pages_per_block=kb, q_block=tb)
                else:
                    qv = q[0][seq_gather]                 # (S, Tq, H, D)
                    ov = self._attend(qv, k_pages, v_pages, scales, l,
                                      tables, stables, ctx_lens, pos0)
                    o = ov.reshape(s_bucket * tq_bucket,
                                   *ov.shape[2:])[pack_gather]
            with jax.named_scope("oproj_ffn"):
                x = x + o.reshape(1, t_bucket, cfg.q_dim) @ lp["attn"]["wo"]
                x = constrain(x, (None, None, "embed"))
                x = self._layer_ffn(lp, x)
        with jax.named_scope("head"):
            h_last = x[0][last_idx]                       # (S, d)
            return k_pages, v_pages, scales, self._head(h_last)

    # ------------------------------------------------------------------

    def attach_cache(self, prefix_cache) -> None:
        """Wire a ``PrefixCache`` built on this executor's allocator."""
        assert prefix_cache.alloc is self.alloc, \
            "prefix cache must share the executor's BlockAllocator"
        self.prefix_cache = prefix_cache

    def _extend(self, req_id: int, n_tokens: int, *,
                mirror_cow: bool = True) -> Optional[list]:
        """Allocator extend with prefix-cache eviction under pressure.

        COW page copies are mirrored into the device K/V (and scale) arrays
        per call unless ``mirror_cow=False`` (the fused path drains the
        whole step's events in one batched gather/scatter —
        ``_mirror_cow_batched``).
        """
        tbl = self.alloc.extend(req_id, n_tokens)
        if tbl is None and self.prefix_cache is not None:
            self.prefix_cache.evict_for(
                self.alloc.blocks_needed(req_id, n_tokens) + 1)
            tbl = self.alloc.extend(req_id, n_tokens)
        if mirror_cow:
            self._mirror_cow_batched()
        return tbl

    def _mirror_cow_batched(self) -> None:
        """Drain every pending COW event as one vectorized gather/scatter.

        Scale pages copy in the same drain (DESIGN.md §14): the allocator
        paired each COW'd data page with a fresh scale page, so values and
        their dequant scales stay in lock-step."""
        old, new, s_old, s_new = self.alloc.pop_cow_events_batched()
        if old:
            old, new = jnp.asarray(old, jnp.int32), jnp.asarray(new, jnp.int32)
            self.k_pages = copy_pages(self.k_pages, old, new)
            self.v_pages = copy_pages(self.v_pages, old, new)
            if self.qspec is not None:
                s_old = jnp.asarray(s_old, jnp.int32)
                s_new = jnp.asarray(s_new, jnp.int32)
                self.k_scales = copy_pages(self.k_scales, s_old, s_new)
                self.v_scales = copy_pages(self.v_scales, s_old, s_new)
            if self.draft is not None:
                # draft pools index the same global page ids (DESIGN.md §18)
                self.draft.mirror_cow(old, new)

    def execute(self, plan: BatchPlan, requests, now: float) -> tuple[float, dict]:
        with trace.execute() as rec:
            if self.mode == "sequential":
                out = self._execute_sequential(plan, requests, now)
            else:
                out = self._execute_fused(plan, requests, now)
            rec.count(plan, self.last_deferred)
        return out

    # ------------------------------------------------------------------
    # slack-bounded multi-step decode commitment (DESIGN.md §12)
    # ------------------------------------------------------------------

    def set_draft(self, draft) -> None:
        """Install a draft adapter (spec_decode) and build the jitted
        speculative round body; enables ``execute_multi(speculate=γ)``."""
        draft.bind(self)
        self.draft = draft
        self._spec_fn = self._jit_step(self._spec_multi_step, "bsz",
                                       "rounds", "gamma", "force_reject")

    def execute_multi(self, plan: BatchPlan, requests, now: float,
                      horizon: int, *, speculate: int = 0) -> tuple[list, dict]:
        """Run ``horizon`` committed decode steps as ONE device dispatch.

        The engine only commits all-decode plans (``capacity.commit_horizon``
        gates how deep). KV pages for all ``horizon`` tokens per sequence
        are reserved up front; the jitted loop feeds each step's argmax
        token back and advances K/V writes in-loop. Returns
        ``(steps, emitted_seq)`` where ``steps`` is one
        ``(dt, new_tokens, context)`` triple per internal step (the §3.2
        observation stream) and ``emitted_seq`` maps req_id to its
        ``horizon`` output tokens. Out-of-blocks sequences defer whole
        (``last_deferred``), exactly like the single-step paths.

        ``speculate=γ > 0`` routes to the speculative draft/verify path
        (``horizon`` becomes the round count; requires ``set_draft``); its
        second return value is then one dict PER ROUND mapping req_id to
        that round's emitted tokens (DESIGN.md §18).

        ``capture_logits`` is not supported on any multi-step path — the
        per-step logits never leave the device — and raises loudly rather
        than silently returning stale ``last_logits``.
        """
        if self.capture_logits:
            raise ValueError(
                "capture_logits is not supported on the multi-step/"
                "speculative decode path: per-step logits never leave the "
                "device (run with commit_horizon=1/speculate=0, or disable "
                "capture_logits)")
        with trace.execute() as rec:
            if speculate > 0:
                out = self._execute_spec(plan, requests, now, horizon,
                                         speculate)
            else:
                out = self._execute_horizon(plan, requests, horizon)
            rec.count(plan, self.last_deferred)
        return out

    def _execute_horizon(self, plan: BatchPlan, requests,
                         horizon: int) -> tuple[list, dict]:
        assert not plan.prefill_items, "multi-step commitment is decode-only"
        t0 = time.perf_counter()
        deferred: set[int] = set()
        ids = []
        with _EXTEND:
            for it in plan.decode_items:
                if self._extend(it.req_id, horizon) is None:
                    deferred.add(it.req_id)   # out of KV blocks: defer & retry
                    continue
                ids.append(it.req_id)
        self.last_deferred = frozenset(deferred)
        self.last_logits = {}
        if not ids:
            return [(time.perf_counter() - t0, 0, 0)], {}
        bsz = _bucket(len(ids), 4)
        toks, pos, tables, ctx = [], [], [], []
        for rid in ids:
            req = requests[rid]
            last = req.generated_tokens[-1] if req.generated_tokens else 0
            toks.append(last)
            # the fed-back token's position: context counts it as emitted,
            # but its K/V enters the cache only now
            pos.append(req.context - 1)
            tables.append(self._table(rid))
            ctx.append(req.context)
        pad = bsz - len(ids)
        toks += [0] * pad
        pos += [0] * pad
        ctx += [1] * pad
        tables += [tables[0] * 0] * pad
        stables = [self._stable(rid) for rid in ids]
        stables += [stables[0] * 0] * pad
        self.n_dispatches += 1
        self.compile_keys.add(("multi", bsz, horizon))
        with _DISPATCH, self._step_ctx():
            self.k_pages, self.v_pages, scales, out = self._multi_fn(
                self.k_pages, self.v_pages, self._scales_in(),
                jnp.asarray(toks, jnp.int32), jnp.asarray(pos, jnp.int32),
                jnp.stack(tables), jnp.stack(stables),
                jnp.asarray(ctx, jnp.int32), bsz=bsz, horizon=horizon)
        self._set_scales(scales)
        with _SYNC:
            toks_np = np.asarray(out)                      # (horizon, bsz)
        dt = time.perf_counter() - t0
        emitted_seq = {rid: [int(toks_np[h, i]) for h in range(horizon)]
                       for i, rid in enumerate(ids)}
        # per-internal-step accounting: contexts grow one token per step,
        # capped by the arch's attention window like SchedTask.cost_context
        base = [(requests[rid].context, requests[rid].window) for rid in ids]
        steps = [(dt / horizon, len(ids),
                  sum(min(c + h, w) if w else c + h for c, w in base))
                 for h in range(horizon)]
        return steps, emitted_seq

    def _execute_spec(self, plan: BatchPlan, requests, now: float,
                      rounds: int, gamma: int) -> tuple[list, list]:
        """``rounds`` speculative draft/verify rounds as ONE dispatch.

        Reserves the optimistic ``rounds·(γ+1)`` KV slots per sequence up
        front (a mid-run dispatch cannot defer), launches the jitted round
        loop, then reclaims every rejected slot with the slot-granular
        ``shrink_to`` — post-run each sequence holds exactly
        ``context - 1 + emitted`` slots, byte-identical to what a
        non-speculative run emitting the same stream would hold. Returns
        ``(steps, emitted_rounds)``: one §3.2 observation triple and one
        {req_id: [tokens]} dict per round.
        """
        assert not plan.prefill_items, "speculative rounds are decode-only"
        assert self.draft is not None, \
            "execute_multi(speculate=γ) requires set_draft()"
        t0 = time.perf_counter()
        G = gamma + 1
        deferred: set[int] = set()
        ids, pre_lens = [], {}
        with _EXTEND:
            for it in plan.decode_items:
                pre = self.alloc.context_len(it.req_id)
                if self._extend(it.req_id, rounds * G) is None:
                    deferred.add(it.req_id)   # out of KV blocks: defer & retry
                    continue
                ids.append(it.req_id)
                pre_lens[it.req_id] = pre
        self.last_deferred = frozenset(deferred)
        self.last_logits = {}
        self.last_spec_accepted = self.last_spec_drafted = 0
        if not ids:
            return [(time.perf_counter() - t0, 0, 0)], [{}]
        dstate = self.draft.prepare(ids, requests)
        bsz = _bucket(len(ids), 4)
        toks, pos, tables, ctx, memit = [], [], [], [], []
        for rid in ids:
            req = requests[rid]
            last = req.generated_tokens[-1] if req.generated_tokens else 0
            toks.append(last)
            # the fed-back token's position: context counts it as emitted,
            # but its K/V enters the cache only now
            pos.append(req.context - 1)
            tables.append(self._table(rid))
            ctx.append(req.context)
            memit.append(req.max_new_tokens - req.generated)
        pad = bsz - len(ids)
        toks += [0] * pad
        pos += [0] * pad
        ctx += [1] * pad
        memit += [0] * pad                # padded rows never emit
        tables += [tables[0] * 0] * pad
        stables = [self._stable(rid) for rid in ids]
        stables += [stables[0] * 0] * pad
        self.n_dispatches += 1
        self.compile_keys.add(("spec", bsz, rounds, gamma))
        with _DISPATCH, self._step_ctx():
            (self.k_pages, self.v_pages, scales, dstate, emitted, counts,
             accs) = self._spec_fn(
                self.k_pages, self.v_pages, self._scales_in(), dstate,
                jnp.asarray(toks, jnp.int32), jnp.asarray(pos, jnp.int32),
                jnp.stack(tables), jnp.stack(stables),
                jnp.asarray(ctx, jnp.int32), jnp.asarray(memit, jnp.int32),
                bsz=bsz, rounds=rounds, gamma=gamma,
                force_reject=self.spec_force_reject)
        self._set_scales(scales)
        self.draft.finish(dstate)
        with _SYNC:
            em = np.asarray(emitted)                      # (bsz, R·G)
            acc = np.asarray(accs)                        # (R, bsz)
            cnt = np.asarray(counts)
        dt = time.perf_counter() - t0
        emitted_rounds: list[dict] = [{} for _ in range(rounds)]
        for i, rid in enumerate(ids):
            e = int(cnt[i])
            off = 0
            for r in range(rounds):
                k = int(acc[r, i])
                emitted_rounds[r][rid] = [int(x) for x in em[i, off:off + k]]
                off += k
            # reclaim rejected reservation: keep exactly the accepted run
            self.alloc.shrink_to(rid, pre_lens[rid] + e)
            self.draft.note_progress(rid, pre_lens[rid] + e)
            self.last_spec_accepted += sum(
                max(int(acc[r, i]) - 1, 0) for r in range(rounds))
        self.last_spec_drafted = rounds * len(ids) * gamma
        # per-round §3.2 observations: the verify pass computes n·(γ+1)
        # target tokens per round (draft cost is folded into the measured
        # dt — the calibration absorbs it as per-token overhead) over
        # contexts grown by each round's actual acceptance, window-capped
        base = [(requests[rid].context, requests[rid].window) for rid in ids]
        steps, grown = [], np.zeros(len(ids), np.int64)
        for r in range(rounds):
            c = sum(min(b + int(g), w) if w else b + int(g)
                    for (b, w), g in zip(base, grown))
            steps.append((dt / rounds, len(ids) * G, c))
            grown += acc[r, :len(ids)]
        return steps, emitted_rounds

    def rollback_tokens(self, req_id: int, n_tokens: int) -> None:
        """Return a rolled-back dispatch's reserved KV slots (DESIGN.md §12).

        The stale K/V written beyond the request's committed length is
        unreachable — context lengths never covered it — so releasing the
        reservation is the whole rollback.
        """
        self.alloc.shrink(req_id, n_tokens)
        if self.draft is not None:
            self.draft.clamp(req_id, self.alloc.context_len(req_id))

    # ------------------------------------------------------------------
    # fused path: pack the whole plan, launch once
    # ------------------------------------------------------------------

    def _get_staging(self, t_bucket: int, s_bucket: int, tq_bucket: int,
                     pg_bucket: int) -> dict:
        """Preallocated numpy staging buffers, one set per bucket key.

        Block tables stage at ``pg_bucket`` columns — the step's pages
        bucket, not ``max_pages`` — so the attention backend never gathers
        or scores table padding the mask would discard anyway."""
        key = (t_bucket, s_bucket, tq_bucket, pg_bucket)
        st = self._staging.get(key)
        if st is None:
            st = {
                "tokens": np.zeros(t_bucket, np.int32),
                "positions": np.zeros(t_bucket, np.int32),
                "tok_pages": np.zeros(t_bucket, np.int32),
                "tok_slots": np.zeros(t_bucket, np.int32),
                "tok_spages": np.zeros(
                    t_bucket if self.qspec is not None else 0, np.int32),
                "tables": np.zeros((s_bucket, pg_bucket), np.int32),
                "stables": np.zeros(
                    (s_bucket if self.qspec is not None else 0, pg_bucket),
                    np.int32),
                "ctx": np.zeros(s_bucket, np.int32),
                "q_starts": np.zeros(s_bucket, np.int32),
                "q_lens": np.zeros(s_bucket, np.int32),
                "pos0": np.zeros(s_bucket, np.int32),
                "last_idx": np.zeros(s_bucket, np.int32),
                "seq_gather": np.zeros((s_bucket, tq_bucket), np.int32),
                "pack_gather": np.zeros(t_bucket, np.int32),
            }
            self._staging[key] = st
        else:
            for a in st.values():
                a.fill(0)
        return st

    def _execute_fused(self, plan: BatchPlan, requests,
                       now: float) -> tuple[float, dict]:
        t0 = time.perf_counter()
        seqs: list[_PackedSeq] = []
        deferred: set[int] = set()
        prefill_rids = set()
        with _EXTEND:
            for it in plan.prefill_items:
                req = requests[it.req_id]
                prefill_rids.add(it.req_id)
                if self._extend(it.req_id, it.n_tokens,
                                mirror_cow=False) is None:
                    deferred.add(it.req_id)   # out of KV blocks: defer & retry
                    continue
                chunk = req.tokens[req.prefilled:req.prefilled + it.n_tokens]
                seqs.append(_PackedSeq(
                    it.req_id, chunk, pos0=req.prefilled,
                    ctx=req.prefilled + len(chunk),
                    emits=req.prefilled + it.n_tokens == req.prompt_len))
            for it in plan.decode_items:
                req = requests[it.req_id]
                # a single launch computes every emission at once, so it
                # cannot feed a same-step prefill emission back into a
                # decode item
                assert it.req_id not in prefill_rids, \
                    "fused step: request cannot both prefill and decode in one plan"
                if self._extend(it.req_id, 1, mirror_cow=False) is None:
                    deferred.add(it.req_id)
                    continue
                last = req.generated_tokens[-1] if req.generated_tokens else 0
                # the fed-back token's position: context counts it as
                # emitted, but its K/V enters the cache only now
                seqs.append(_PackedSeq(it.req_id, [last],
                                       pos0=req.context - 1,
                                       ctx=req.context, emits=True))
            self.last_deferred = frozenset(deferred)
            self.last_logits = {}
            if not seqs:
                return time.perf_counter() - t0, {}
            self._mirror_cow_batched()

        with _STAGE:
            n_tok = sum(len(s.tokens) for s in seqs)
            t_bucket = _ladder(n_tok, 4)
            s_bucket = _ladder(len(seqs), 4)
            tq_bucket = _bucket(max(len(s.tokens) for s in seqs), 1)
            # pages bucket (DESIGN.md §14): trim staged block tables to the
            # ladder over the step's widest table — early steps attend over
            # a fraction of max_pages_per_seq instead of always paying for it
            if self.trim_page_tables:
                max_pg = max(len(self.alloc.tables[s.req_id]) for s in seqs)
                pg_bucket = min(self.max_pages, _ladder(max_pg, 2))
            else:
                pg_bucket = self.max_pages
            st = self._get_staging(t_bucket, s_bucket, tq_bucket, pg_bucket)
            quantized = self.qspec is not None
            off = 0
            for i, s in enumerate(seqs):
                n = len(s.tokens)
                pos = np.arange(s.pos0, s.pos0 + n, dtype=np.int32)
                tbl = np.asarray(self.alloc.tables[s.req_id], np.int32)
                assert len(tbl) <= pg_bucket, "pages bucket exceeded"
                st["tokens"][off:off + n] = s.tokens
                st["positions"][off:off + n] = pos
                st["tok_pages"][off:off + n] = tbl[pos // self.page_size]
                st["tok_slots"][off:off + n] = pos % self.page_size
                st["tables"][i, :len(tbl)] = tbl
                if quantized:
                    stbl = np.asarray(self.alloc.scale_table(s.req_id),
                                      np.int32)
                    st["tok_spages"][off:off + n] = stbl[pos // self.page_size]
                    st["stables"][i, :len(stbl)] = stbl
                st["ctx"][i] = s.ctx
                st["q_starts"][i] = off
                st["q_lens"][i] = n
                st["pos0"][i] = s.pos0
                st["last_idx"][i] = off + n - 1
                st["seq_gather"][i, :n] = np.arange(off, off + n)
                st["pack_gather"][off:off + n] = i * tq_bucket + np.arange(n)
                off += n

        self.n_dispatches += 1
        key = ("fused", t_bucket, s_bucket, tq_bucket, pg_bucket)
        rec = trace.current()                 # ``execute``'s record
        rec.buckets, rec.new_key = key[1:], key not in self.compile_keys
        self.compile_keys.add(key)
        with self._step_ctx():
            with _H2D:
                args = (jnp.asarray(st["tokens"]),
                        jnp.asarray(st["positions"]),
                        jnp.asarray(st["tok_pages"]),
                        jnp.asarray(st["tok_slots"]),
                        jnp.asarray(st["tok_spages"]),
                        jnp.asarray(st["tables"]), jnp.asarray(st["stables"]),
                        jnp.asarray(st["ctx"]),
                        jnp.asarray(st["q_starts"]),
                        jnp.asarray(st["q_lens"]),
                        jnp.asarray(st["pos0"]), jnp.asarray(st["last_idx"]),
                        jnp.asarray(st["seq_gather"]),
                        jnp.asarray(st["pack_gather"]))
            with _DISPATCH:
                self.k_pages, self.v_pages, scales, logits = self._fused_fn(
                    self.k_pages, self.v_pages, self._scales_in(), *args,
                    t_bucket=t_bucket, s_bucket=s_bucket,
                    tq_bucket=tq_bucket, pg_bucket=pg_bucket)
        self._set_scales(scales)
        emitted: dict[int, int] = {}
        if any(s.emits for s in seqs):
            # one device→host sync for the whole step
            with _SYNC:
                nxt = np.asarray(jnp.argmax(logits, -1))
                lg = np.asarray(logits) if self.capture_logits else None
            for i, s in enumerate(seqs):
                if s.emits:
                    emitted[s.req_id] = int(nxt[i])
                    if lg is not None:
                        self.last_logits[s.req_id] = lg[i].copy()
        return time.perf_counter() - t0, emitted

    # ------------------------------------------------------------------
    # sequential escape hatch: per-item launches (parity oracle / benches)
    # ------------------------------------------------------------------

    def _execute_sequential(self, plan: BatchPlan, requests,
                            now: float) -> tuple[float, dict]:
        t0 = time.perf_counter()
        emitted: dict[int, int] = {}
        deferred: set[int] = set()
        self.last_logits = {}
        decode_items = plan.decode_items
        for it in plan.prefill_items:
            req = requests[it.req_id]
            with _EXTEND:
                tbl = self._extend(it.req_id, it.n_tokens)
            if tbl is None:
                deferred.add(it.req_id)   # out of KV blocks: defer & retry
                continue
            chunk = req.tokens[req.prefilled:req.prefilled + it.n_tokens]
            n_tok = _bucket(len(chunk), 16)
            toks = jnp.asarray(chunk + [0] * (n_tok - len(chunk)), jnp.int32)
            table = self._table(it.req_id)
            self.n_dispatches += 1
            self.compile_keys.add(("chunk", n_tok))
            with _DISPATCH, self._step_ctx():
                self.k_pages, self.v_pages, scales, logits = self._chunk_fn(
                    self.k_pages, self.v_pages, self._scales_in(), toks,
                    jnp.int32(req.prefilled), table, self._stable(it.req_id),
                    jnp.int32(len(chunk)), n_tok=n_tok)
            self._set_scales(scales)
            if req.prefilled + it.n_tokens == req.prompt_len:
                with _SYNC:
                    emitted[it.req_id] = int(jnp.argmax(logits))
                    if self.capture_logits:
                        self.last_logits[it.req_id] = np.asarray(logits)
        ids = []
        with _EXTEND:
            for it in decode_items:
                if self._extend(it.req_id, 1) is None:
                    deferred.add(it.req_id)
                    continue
                ids.append(it.req_id)
        if ids:
            bsz = _bucket(len(ids), 4)
            toks, pos, tables, ctx = [], [], [], []
            for rid in ids:
                req = requests[rid]
                last = (req.generated_tokens[-1] if req.generated_tokens
                        else emitted.get(rid, 0))
                toks.append(last)
                # the fed-back token's position: context counts it as
                # emitted, but its K/V enters the cache only now
                pos.append(req.context - 1)
                tables.append(self._table(rid))
                ctx.append(req.context)
            pad = bsz - len(ids)
            toks += [0] * pad
            pos += [0] * pad
            ctx += [1] * pad
            tables += [tables[0] * 0] * pad
            stables = [self._stable(rid) for rid in ids]
            stables += [stables[0] * 0] * pad
            self.n_dispatches += 1
            self.compile_keys.add(("decode", bsz))
            with _DISPATCH, self._step_ctx():
                self.k_pages, self.v_pages, scales, logits = self._decode_fn(
                    self.k_pages, self.v_pages, self._scales_in(),
                    jnp.asarray(toks, jnp.int32), jnp.asarray(pos, jnp.int32),
                    jnp.stack(tables), jnp.stack(stables),
                    jnp.asarray(ctx, jnp.int32), bsz=bsz)
            self._set_scales(scales)
            with _SYNC:
                nxt = np.asarray(jnp.argmax(logits, -1))
                lg = np.asarray(logits) if self.capture_logits else None
            for i, rid in enumerate(ids):
                emitted[rid] = int(nxt[i])
                if lg is not None:
                    self.last_logits[rid] = lg[i].copy()
        self.last_deferred = frozenset(deferred)
        return time.perf_counter() - t0, emitted

    def stats(self) -> dict:
        """Dispatch/compile counters for benches and regression guards."""
        return {"dispatches": self.n_dispatches,
                "compile_keys": len(self.compile_keys)}

    def _table(self, req_id: int) -> jnp.ndarray:
        tbl = self.alloc.tables.get(req_id, [])
        pad = self.max_pages - len(tbl)
        assert pad >= 0, "max_pages_per_seq exceeded"
        return jnp.asarray(tbl + [0] * pad, jnp.int32)

    def _stable(self, req_id: int) -> jnp.ndarray:
        """Scale-page table parallel to ``_table`` (DESIGN.md §14); a cached
        zero table in fp32 mode, where the step bodies never read it."""
        if self.qspec is None:
            return self._zero_table
        stbl = self.alloc.scale_table(req_id)
        pad = self.max_pages - len(stbl)
        return jnp.asarray(stbl + [0] * pad, jnp.int32)

    def _scales_in(self):
        """The (k_scales, v_scales) jit operand — () when unquantized."""
        return () if self.qspec is None else (self.k_scales, self.v_scales)

    def _set_scales(self, scales) -> None:
        if self.qspec is not None:
            self.k_scales, self.v_scales = scales

    def release(self, req_id: int) -> None:
        self.alloc.release(req_id)
        if self.draft is not None:
            self.draft.release(req_id)
