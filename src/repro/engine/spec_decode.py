"""Self-speculative decode inside the commit horizon (DESIGN.md §18).

A speculative *round* drafts γ candidate tokens per sequence, then verifies
all γ+1 positions (the fed-back token plus the γ drafts) in ONE target pass
through the fused data plane — the same ragged Tq>1 paged-attention contract
chunked prefill already exercises. Greedy accept/reject is resolved in-loop:
the emitted tokens are the target argmaxes ``tgt[:n_acc+1]`` where ``n_acc``
is the number of leading drafts matching the target. Because a rejection
falls back to the *verified* argmax, the emitted stream is bit-identical to
non-speculative greedy decoding **by construction** — draft quality only
moves the acceptance rate, never the tokens.

Two draft adapters share one interface so the executor's jitted round body
(``PagedTransformerExecutor._spec_multi_step``) is draft-agnostic:

* ``TruncatedSelfDraft`` — early-exit self-speculation: the first ``n_layers``
  of the target model plus the target's own head. Its K/V writes land in the
  MAIN page pools; that is safe because the verify pass rewrites the same
  (layer, position) slots with byte-identical values (same tokens, same
  positions, same weights → same activations), and rejected positions are
  overwritten before any later pass can attend to them.
* ``SmallModelDraft`` — a separate (smaller) model with its OWN fp32 page
  pools, indexed by the SAME global page ids as the target's allocator so
  block tables are shared verbatim. It keeps a host-side coverage map and
  backfills draft-KV for any context it has not seen (admission after the
  target prefilled, rollback, migration) with a chunked prefill pass before
  the speculative dispatch.

``AcceptanceEWMA`` is the capacity layer's pessimistic acceptance estimator:
cold start sits at the floor, measured collapses are adopted *immediately*
(min with the raw rate), improvements smooth in — overstating acceptance is
the only way ``commit_horizon`` could bust a TPOT envelope, so the estimator
is one-sided by design.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from ..kernels.ops import paged_attention_op
from ..models.layers import attn_qkv, mlp_apply
from ..models.module import rmsnorm
from .executor import copy_pages, scatter_rows, table_dest


class AcceptanceEWMA:
    """Pessimistic one-sided EWMA of the per-draft acceptance rate.

    ``value`` is what ``commit_horizon`` prices emission with; it must never
    run ahead of reality, so updates are asymmetric: a measured rate BELOW
    the current estimate replaces it outright (``min``), a rate above it
    only pulls the estimate up at ``alpha`` speed. ``floor`` is the
    cold-start value (0.0 = fully pessimistic: speculative rounds earn no
    extra emission allowance until measured).
    """

    def __init__(self, floor: float = 0.0, alpha: float = 0.3):
        self.floor = floor
        self.alpha = alpha
        self._v: Optional[float] = None

    @property
    def value(self) -> float:
        return self.floor if self._v is None else max(self.floor, self._v)

    def update(self, accepted: int, drafted: int) -> None:
        if drafted <= 0:
            return
        m = accepted / drafted
        prev = m if self._v is None else self._v
        self._v = min(m, self.alpha * m + (1.0 - self.alpha) * prev)


class TruncatedSelfDraft:
    """Early-exit self-speculative draft: first ``n_layers`` of the target.

    State-free — drafts write (and read) the target's own page pools. Every
    draft write is later rewritten by the verify pass with identical values
    (layers < n_layers) or fresh correct values (layers >= n_layers), so no
    rollback hook is needed beyond the allocator's slot reclamation.
    """

    needs_sync_pass = False

    def __init__(self, n_layers: int):
        assert n_layers >= 1
        self.n_layers = n_layers
        self._ex = None
        self.n_backfill_dispatches = 0

    def bind(self, executor) -> None:
        assert self.n_layers <= executor.cfg.n_layers
        self._ex = executor

    # -- jit-traced round hooks ----------------------------------------

    def step(self, k_pages, v_pages, scales, dstate, tok, pos, tables,
             stables, ctx_lens):
        ex = self._ex
        x = ex._embed(tok)[:, None]
        k_pages, v_pages, scales, x = ex._forward(
            k_pages, v_pages, scales, x, pos[:, None], tables, stables,
            ctx_lens, n_layers=self.n_layers)
        return k_pages, v_pages, scales, dstate, ex._head(x[:, 0])

    # -- host-side lifecycle hooks (all no-ops: no private state) -------

    def prepare(self, ids, requests):
        return ()

    def finish(self, dstate) -> None:
        pass

    def note_progress(self, req_id: int, n_tokens: int) -> None:
        pass

    def clamp(self, req_id: int, n_tokens: int) -> None:
        pass

    def release(self, req_id: int) -> None:
        pass

    def mirror_cow(self, old, new) -> None:
        pass


def draft_forward(cfg: ArchConfig, params, dk, dv, x, positions, tables,
                  ctx_lens, page_size: int, valid=None):
    """Dense-family forward over the draft's own paged KV (fp32, unsharded).

    Mirrors ``PagedTransformerExecutor._forward`` minus quantization and
    mesh constraints: the draft pools are replicated device arrays indexed
    by the target allocator's global page ids.
    """
    assert cfg.family == "dense" and cfg.moe is None
    dest = table_dest(tables, positions, page_size, dk[0].shape[0], valid)
    for l in range(cfg.n_layers):
        lp = jax.tree.map(lambda a: a[l], params["layers"])
        h = rmsnorm(x, lp["ln1"], cfg.norm_eps)
        q, k, v = attn_qkv(lp["attn"], h, positions, cfg)
        dk, dv = scatter_rows(dk, dv, l, k.reshape(-1, *k.shape[2:]),
                              v.reshape(-1, *v.shape[2:]), dest)
        o = paged_attention_op(q, dk[l], dv[l], tables, ctx_lens,
                               positions[:, 0], window=cfg.window)
        x = x + o.reshape(*x.shape[:2], cfg.q_dim) @ lp["attn"]["wo"]
        x = x + mlp_apply(lp["mlp"], rmsnorm(x, lp["ln2"], cfg.norm_eps))
    return dk, dv, x


class SmallModelDraft:
    """Separate small draft model behind the same adapter interface.

    Owns fp32 page pools of the target allocator's cardinality, indexed by
    the SAME global page ids — the speculative round body passes the
    target's block tables straight through. A host-side coverage map tracks
    how many leading positions of each request have draft-KV; ``prepare``
    backfills gaps with chunked draft-prefill dispatches (counted in
    ``n_backfill_dispatches``, NOT the executor's ``n_dispatches`` — the
    one-dispatch-per-step serving invariant is about the target plane).

    ``needs_sync_pass``: after the γ in-round draft steps the last draft
    token's own draft-KV has not been written; one extra draft pass (logits
    discarded) writes it so a fully-accepting sequence enters the next round
    with complete draft context.
    """

    needs_sync_pass = True

    def __init__(self, cfg: ArchConfig, params):
        assert cfg.family == "dense" and cfg.moe is None, \
            "SmallModelDraft supports dense-family draft archs"
        self.cfg = cfg
        self.params = params
        self.page_size = 0
        self.dk = self.dv = None
        self._covered: dict[int, int] = {}
        self.n_backfill_dispatches = 0
        self._prefill_fn = None
        self._ex = None

    def bind(self, executor) -> None:
        cfg = self.cfg
        self._ex = executor
        self.page_size = executor.page_size
        shape = (executor.alloc.num_blocks, cfg.n_kv_heads, cfg.head_dim,
                 self.page_size)
        self.dk = tuple(jnp.zeros(shape, jnp.float32)
                        for _ in range(cfg.n_layers))
        self.dv = tuple(jnp.zeros(shape, jnp.float32)
                        for _ in range(cfg.n_layers))
        self._prefill_fn = jax.jit(self._prefill_step,
                                   static_argnames=("n_tok",))

    # -- jit-traced round hooks ----------------------------------------

    def step(self, k_pages, v_pages, scales, dstate, tok, pos, tables,
             stables, ctx_lens):
        dk, dv = dstate
        x = self.params["embed"][tok][:, None]
        dk, dv, x = draft_forward(self.cfg, self.params, dk, dv, x,
                                  pos[:, None], tables, ctx_lens,
                                  self.page_size)
        h = rmsnorm(x[:, 0], self.params["ln_f"], self.cfg.norm_eps)
        return k_pages, v_pages, scales, (dk, dv), h @ self.params["head"]

    def _prefill_step(self, dk, dv, tokens, pos0, table, n_valid, *, n_tok):
        x = self.params["embed"][tokens][None]
        positions = (pos0 + jnp.arange(n_tok))[None]
        valid = jnp.arange(n_tok)[None] < n_valid
        ctx = (pos0 + n_valid)[None]
        dk, dv, _ = draft_forward(self.cfg, self.params, dk, dv, x,
                                  positions, table[None], ctx,
                                  self.page_size, valid)
        return dk, dv

    # -- host-side lifecycle -------------------------------------------

    def prepare(self, ids, requests):
        """Backfill draft-KV coverage up to each request's fed-back token
        position (``context - 1``), then hand the pools to the jit body."""
        for rid in ids:
            req = requests[rid]
            need = req.context - 1
            have = self._covered.get(rid, 0)
            if have >= need:
                continue
            stream = list(req.tokens or []) + list(req.generated_tokens)
            assert len(stream) >= need, \
                f"draft backfill: request {rid} token stream too short"
            table = self._ex._table(rid)
            while have < need:
                chunk = stream[have:need]
                n_tok = _chunk_bucket(len(chunk))
                toks = jnp.asarray(chunk + [0] * (n_tok - len(chunk)),
                                   jnp.int32)
                self.n_backfill_dispatches += 1
                self.dk, self.dv = self._prefill_fn(
                    self.dk, self.dv, toks, jnp.int32(have), table,
                    jnp.int32(len(chunk)), n_tok=n_tok)
                have += len(chunk)
            self._covered[rid] = need
        return (self.dk, self.dv)

    def finish(self, dstate) -> None:
        self.dk, self.dv = dstate

    def note_progress(self, req_id: int, n_tokens: int) -> None:
        self._covered[req_id] = n_tokens

    def clamp(self, req_id: int, n_tokens: int) -> None:
        if req_id in self._covered:
            self._covered[req_id] = min(self._covered[req_id], n_tokens)

    def release(self, req_id: int) -> None:
        self._covered.pop(req_id, None)

    def mirror_cow(self, old, new) -> None:
        """Mirror the target allocator's COW page copies: draft pools share
        the global page-id space, so a copied data page's draft-KV must
        follow it or the surviving holders would read the wrong rows."""
        self.dk = copy_pages(self.dk, old, new)
        self.dv = copy_pages(self.dv, old, new)


def _chunk_bucket(n: int, lo: int = 16) -> int:
    b = lo
    while b < n:
        b *= 2
    return b
