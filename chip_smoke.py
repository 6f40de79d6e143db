"""Chip smoke test: serve H2O-Danube-1.8B at its full published width on a TPU
through the FairBatching engine, and check the served logits.

    python chip_smoke.py [--seed N]          # one chip: served vs a plain f32 reference
    python chip_smoke.py --tp 4 [--seed N]   # four chips: tensor-parallel vs one chip

The path is the one a user drives: ``Engine`` → ``make_scheduler
("fairbatching")`` → ``PagedTransformerExecutor(mode="fused")`` → the ragged
Pallas attention kernel. All 24 layers run at d_model 2560; the weights are
random, drawn from ``--seed``, so nothing is downloaded. Everything runs in
this one process, which holds the chip(s).

The script exits non-zero and prints no result line when JAX finds no TPU or
any check fails. On success its last line is one JSON object naming the
device. The compile cache goes where ``JAX_COMPILATION_CACHE_DIR`` says, or
to ``<repo>/.jax_cache`` when that is unset.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

MODEL = "h2o-danube-1.8b"
# each length is served twice (so the reference can batch equal lengths);
# 8504 prompt tokens exceed the 8192-token cap of one step
# (FormationConfig.max_token_budget), so at least one prefill is chunked
PROMPT_LENS = (1500, 448, 1344, 960)
NEW_TOKENS = 16
NUM_PAGES, PAGE, MAX_PAGES_PER_SEQ = 256, 128, 16
MAX_ENGINE_STEPS = 400

# Pass bound on |served logit − reference logit|. The reference runs every
# matmul at "highest" precision (f32-accurate); the served path runs at the
# TPU's default precision, which rounds f32 matmul operands to bf16
# (relative error 2^-9 per operand). Each of the ~7 matmuls per layer then
# carries a relative error of about 2^-9·√2 ≈ 0.3%, and over 24 residual
# layers these add in quadrature to about 0.3%·√(24·7) ≈ 4% of the hidden
# state. The random head maps the unit-RMS final state to logits of standard
# deviation about 1, so a logit's error has σ ≈ 0.04, and the largest of
# 32000 such errors is about 4.5σ ≈ 0.18. 0.5 leaves margin for that
# estimate; a wrong mask, page or position moves logits by O(1) and fails.
LOGIT_ATOL = 0.5
# the one-chip and the four-chip runs are each within LOGIT_ATOL of exact
# arithmetic by the argument above, so they differ by at most twice that
TP_LOGIT_ATOL = 2 * LOGIT_ATOL


def log(msg: str) -> None:
    print(msg, flush=True)


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


class CompileCounter:
    """Counts XLA backend compiles and their seconds, and persistent-cache
    hits, through JAX's monitoring events."""

    def __init__(self):
        import jax.monitoring as mon

        self.n = self.secs = self.cache_hits = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.secs += secs

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def line(self) -> str:
        return (f"compiles: {self.n} backend compiles, {self.secs:.1f} s; "
                f"persistent-cache hits: {self.cache_hits}")


def make_requests(cfg, seed: int):
    import numpy as np

    from repro.engine import EngineConfig, Request

    ecfg = EngineConfig()
    rng = np.random.default_rng(seed)
    lens = [n for n in PROMPT_LENS for _ in range(2)]
    return [Request(i, arrival=0.0, prompt_len=n, max_new_tokens=NEW_TOKENS,
                    ttft_slo=ecfg.ttft_slo, tpot_slo=ecfg.tpot_slo,
                    tokens=[int(t) for t in rng.integers(0, cfg.vocab, n)])
            for i, n in enumerate(lens)]


def cost_prior(cfg):
    """Roofline prior for the scheduler's step-cost model, priced with the
    running chip's published peaks (an unknown chip kind is an error). The
    engine recalibrates it online from measured steps."""
    from benchmarks.roofline_report import device_peaks
    from repro.core import LinearCostModel
    from repro.core.cost_model import kv_bytes_per_token

    peaks = device_peaks()
    return LinearCostModel(
        a=1e-3,
        b=2.0 * cfg.active_param_count() / peaks["bf16_flops"],
        c=kv_bytes_per_token(cfg.n_layers, cfg.n_kv_heads, cfg.head_dim,
                             "fp32") / peaks["hbm_bytes_per_s"])


def serve(cfg, params, requests, mesh=None) -> dict:
    """Serve ``requests`` through the engine; return each request's emitted
    tokens and the logits of every emission, plus what the run counted."""
    import copy

    import jax
    import numpy as np

    from repro.core import make_scheduler
    from repro.engine import Engine, EngineConfig, PagedTransformerExecutor

    ex = PagedTransformerExecutor(
        cfg, params, num_pages=NUM_PAGES, page_size=PAGE,
        max_pages_per_seq=MAX_PAGES_PER_SEQ, capture_logits=True, mesh=mesh)
    if not ex._ragged_attn:
        fail("the fused step did not select the ragged Pallas kernel")
    # record the operand shapes of the last fused-step call, so the compiled
    # program can be inspected afterwards (the pools themselves are donated);
    # weights and pools keep their placement, the host-staged operands none
    fused, last_call = ex._fused_fn, {}

    def spy(*args, **kw):
        last_call["args"] = [jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=a.sharding if i < 4 else None), x)
            for i, x in enumerate(fused.args + args)]
        last_call["kw"] = kw
        return fused(*args, **kw)

    ex._fused_fn = spy
    eng = Engine(make_scheduler("fairbatching", cost_prior(cfg)), ex,
                 EngineConfig())
    for r in requests:
        eng.submit(copy.deepcopy(r))
    logits = {r.req_id: [] for r in requests}
    prefill_steps = {r.req_id: 0 for r in requests}
    prefilled = {r.req_id: 0 for r in requests}
    t0 = time.time()
    n_steps = 0
    while eng.has_work:
        n_steps += 1
        if n_steps > MAX_ENGINE_STEPS:
            fail(f"engine still busy after {MAX_ENGINE_STEPS} steps")
        before = ex.n_dispatches
        eng.step()
        if ex.n_dispatches == before:
            continue
        for rid, lg in ex.last_logits.items():
            logits[rid].append(lg)
        for rid, req in eng.requests.items():
            if req.prefilled > prefilled[rid]:
                prefill_steps[rid] += 1
                prefilled[rid] = req.prefilled
    wall = time.time() - t0
    tokens = {rid: list(eng.requests[rid].generated_tokens) for rid in logits}
    for rid, seq in tokens.items():
        if len(seq) != NEW_TOKENS or len(logits[rid]) != NEW_TOKENS:
            fail(f"request {rid} emitted {len(seq)} tokens and "
                 f"{len(logits[rid])} logit rows, expected {NEW_TOKENS}")
        if [int(np.argmax(lg)) for lg in logits[rid]] != seq:
            fail(f"request {rid}: emitted tokens are not the argmax of the "
                 "captured logits")
    with ex._step_ctx():
        hlo = fused.func.lower(*last_call["args"],
                               **last_call["kw"]).compile().as_text()
    return {"tokens": tokens, "logits": logits, "wall": wall,
            "steps": len(eng.steps), "dispatches": ex.n_dispatches,
            "buckets": sorted(k[1:] for k in ex.compile_keys),
            "chunked": sum(n > 1 for n in prefill_steps.values()),
            "deferrals": eng.defer_events, "done": len(eng.done),
            "custom_calls": hlo.count("tpu_custom_call")}


def report_serve(tag: str, run: dict, cfg) -> None:
    n_req = len(run["tokens"])
    log(f"{tag}: served {run['done']}/{n_req} requests, "
        f"{sum(PROMPT_LENS) * 2} prompt tokens, "
        f"{sum(len(t) for t in run['tokens'].values())} new tokens in "
        f"{run['wall']:.1f} s wall (compiles included), {run['steps']} engine "
        f"steps, {run['dispatches']} dispatches, {run['chunked']} chunked "
        f"prefills, {run['deferrals']} deferrals")
    log(f"{tag}: fused-step buckets (tokens, seqs, tq, pages): "
        f"{run['buckets']}")
    log(f"{tag}: last compiled fused step holds {run['custom_calls']} "
        f"tpu_custom_call (Pallas) launches")
    if run["done"] != n_req:
        fail(f"{tag}: {n_req - run['done']} requests not served")
    if run["chunked"] < 1:
        fail(f"{tag}: no prefill was chunked")
    if run["custom_calls"] < cfg.n_layers:
        fail(f"{tag}: the fused step holds {run['custom_calls']} "
             f"tpu_custom_call, expected one per layer ({cfg.n_layers})")


def reference(cfg, params, requests, served_tokens) -> dict:
    """Logits of the plain model (``DecoderLM``, dense attention, highest
    matmul precision) at the prompt and after each served token, fed back
    teacher-forced from the served streams."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import DecoderLM, ModelOpts

    model = DecoderLM(cfg, ModelOpts(attn_impl="dense"))
    max_len = max(PROMPT_LENS) + NEW_TOKENS
    out = {}
    with jax.default_matmul_precision("highest"):
        prefill = jax.jit(model.prefill, static_argnames="max_len")
        decode = jax.jit(model.decode_step)
        for n in PROMPT_LENS:
            rids = [r.req_id for r in requests if r.prompt_len == n]
            toks = jnp.asarray([requests[i].tokens for i in rids], jnp.int32)
            lg, cache = prefill(params, toks, max_len=max_len)
            rows = [np.asarray(lg)]
            for k in range(NEW_TOKENS - 1):
                fed = jnp.asarray([served_tokens[i][k] for i in rids],
                                  jnp.int32)
                lg, cache = decode(params, fed, cache)
                rows.append(np.asarray(lg))
            for j, rid in enumerate(rids):
                out[rid] = [row[j] for row in rows]
    return out


def compare(tag: str, got: dict, want: dict, atol: float,
            prefix_of=None) -> None:
    """Max |got − want| over the first-token logits and over the decode
    logits. ``prefix_of`` limits decode rows to emissions whose fed-back
    tokens agree in both runs (rows past a divergence see other inputs)."""
    import numpy as np

    first, dec, agree, n_dec, rel = 0.0, 0.0, [0, 0], 0, 0.0
    for rid in want:
        rows = len(want[rid]) if prefix_of is None else prefix_of[rid]
        for k in range(rows):
            g, w = got[rid][k], want[rid][k]
            if not (np.isfinite(g).all() and np.isfinite(w).all()):
                fail(f"{tag}: request {rid} row {k} holds non-finite logits")
            err = float(np.max(np.abs(g - w)))
            rel = max(rel, float(np.linalg.norm(g - w) / np.linalg.norm(w)))
            same = int(np.argmax(g) == np.argmax(w))
            if k == 0:
                first = max(first, err)
                agree[0] += same
            else:
                dec = max(dec, err)
                agree[1] += same
                n_dec += 1
    scale = float(np.std(np.stack([want[r][0] for r in want])))
    log(f"{tag}: max |Δlogit| first token {first:.4g}, decode {dec:.4g} "
        f"over {n_dec} decode rows (tolerance {atol}); max relative L2 "
        f"{rel:.3g}; reference logit std {scale:.3g}")
    log(f"{tag}: argmax agreement first token {agree[0]}/{len(want)}, "
        f"decode {agree[1]}/{n_dec} (reported, not required)")
    if not (first <= atol and dec <= atol):
        fail(f"{tag}: logit error {max(first, dec):.4g} exceeds {atol}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel degree; >1 serves the requests "
                         "sharded over that many chips and compares them "
                         "with the one-chip run, and runs nothing else")
    args = ap.parse_args()
    if not (ROOT / "src" / "repro").is_dir():
        sys.exit(f"chip_smoke.py: no src/repro package beside "
                 f"{Path(__file__).name} — run it from a checkout of the "
                 "repository")
    sys.path.insert(0, str(ROOT / "src"))

    from repro.launch.compile_cache import setup_compile_cache

    cache_dir = setup_compile_cache()
    import jax

    if jax.default_backend() != "tpu":
        fail(f"no TPU: JAX found platform {jax.default_backend()!r} "
             f"({jax.devices()[0].device_kind}); this script runs only on "
             "the chip and never falls back to the CPU")
    devs = jax.devices()
    dev = devs[0]
    log(f"device: {dev.platform} {dev.device_kind} x{len(devs)}; "
        f"jax {jax.__version__}; compile cache {cache_dir}")
    if len(devs) < args.tp:
        fail(f"--tp {args.tp} needs {args.tp} chips, found {len(devs)}")
    counter = CompileCounter()

    from repro import configs
    from repro.models import ModelOpts, build_model

    cfg = configs.get(MODEL)
    t0 = time.time()
    # one program: initialised op by op, the weights take over a minute
    params = jax.jit(build_model(cfg, ModelOpts(attn_impl="dense")).init)(
        jax.random.PRNGKey(args.seed))
    jax.block_until_ready(params)
    n_params = sum(a.size for a in jax.tree.leaves(params))
    log(f"model: {cfg.name} ({cfg.source}), {cfg.n_layers} layers, d_model "
        f"{cfg.d_model}, {cfg.n_heads} heads / {cfg.n_kv_heads} kv heads, "
        f"head_dim {cfg.head_dim}, d_ff {cfg.d_ff}, vocab {cfg.vocab}, "
        f"window {cfg.window}; {n_params} params (f32, seed {args.seed}, "
        f"random), built in {time.time() - t0:.1f} s")
    requests = make_requests(cfg, args.seed)
    log(f"traffic: {len(requests)} requests, prompts {sorted(PROMPT_LENS)} "
        f"tokens (each twice), {NEW_TOKENS} new tokens each, all arriving at "
        f"t=0; {NUM_PAGES} KV pages of {PAGE} tokens")

    one = serve(cfg, params, requests)
    gc.collect()                  # drop the executor's pools before going on
    report_serve("one chip", one, cfg)
    if args.tp > 1:
        from repro.launch.mesh import make_mesh

        # stage the weights through the host, so that each chip receives
        # only its shard instead of chip 0 keeping a whole second copy
        params = jax.device_get(params)
        gc.collect()
        mesh = make_mesh((1, args.tp), ("data", "model"))
        tp = serve(cfg, params, requests, mesh=mesh)
        report_serve(f"tp={args.tp}", tp, cfg)
        # decode rows compare only while both runs fed back the same tokens
        prefix = {}
        for rid, seq in one["tokens"].items():
            k = 0
            while k < NEW_TOKENS - 1 and seq[k] == tp["tokens"][rid][k]:
                k += 1
            prefix[rid] = k + 1
        log(f"tp={args.tp}: token streams identical for "
            f"{sum(one['tokens'][r] == tp['tokens'][r] for r in prefix)}"
            f"/{len(prefix)} requests")
        compare(f"tp={args.tp} vs one chip", tp["logits"], one["logits"],
                TP_LOGIT_ATOL, prefix_of=prefix)
    else:
        t0 = time.time()
        ref = reference(cfg, params, requests, one["tokens"])
        log(f"reference: DecoderLM dense attention at highest matmul "
            f"precision, teacher-forced, {time.time() - t0:.1f} s")
        compare("served vs reference", one["logits"], ref, LOGIT_ATOL)
    log(counter.line())
    for d in devs[:args.tp]:
        stats = d.memory_stats() or {}
        log(f"peak_bytes_in_use {d.id}: {stats.get('peak_bytes_in_use')} "
            f"of {stats.get('bytes_limit')}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
