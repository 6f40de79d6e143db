"""Quickstart: serve a small model with batched requests, end to end, REAL
execution (paged KV cache + FairBatching scheduler), on the CPU or a TPU.

    PYTHONPATH=src python examples/quickstart.py

At a model's published width on the chip, run ``chip_smoke.py`` instead.
"""
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core import LinearCostModel, make_scheduler
from repro.engine import (Engine, EngineConfig, PagedTransformerExecutor,
                          Request)
from repro.engine.metrics import summarize
from repro.launch.compile_cache import setup_compile_cache
from repro.models import ModelOpts, build_model

# A ~4M-param llama-style model (real weights, random init).
CFG = ArchConfig(name="demo-4m", family="dense", n_layers=4, d_model=256,
                 n_heads=8, n_kv_heads=4, d_ff=688, vocab=2048)


def main() -> None:
    setup_compile_cache()
    dev = jax.devices()[0]
    print(f"model: {CFG.name} ({CFG.param_count()/1e6:.1f}M params) on "
          f"{dev.platform} {dev.device_kind} x{len(jax.devices())}")
    model = build_model(CFG, ModelOpts(attn_impl="dense"))
    params = model.init(jax.random.PRNGKey(0))
    executor = PagedTransformerExecutor(CFG, params, num_pages=128,
                                        page_size=16, max_pages_per_seq=16)
    # FairBatching with a rough initial cost model; calibrates online.
    sched = make_scheduler("fairbatching",
                           LinearCostModel(a=5e-3, b=1e-4, c=1e-9))
    eng = Engine(sched, executor, EngineConfig(ttft_slo=30.0, tpot_slo=10.0))

    rng = jax.random.PRNGKey(7)
    t0 = time.time()
    for i in range(8):
        plen = 8 + 11 * i % 64
        prompt = [int(x) for x in
                  jax.random.randint(jax.random.fold_in(rng, i), (plen,),
                                     0, CFG.vocab)]
        eng.submit(Request(i, arrival=0.05 * i, prompt_len=plen,
                           max_new_tokens=12, ttft_slo=30.0, tpot_slo=10.0,
                           tokens=prompt))
    done = eng.run(max_steps=2000)
    wall = time.time() - t0
    print(f"served {len(done)} requests in {wall:.1f}s wall, "
          f"{len(eng.steps)} engine steps")
    for i in range(3):
        print(f"  req {i}: generated {eng.requests[i].generated_tokens}")
    s = summarize(done, duration=max(eng.now, 1e-9))
    print(f"SLO attainment: {s['slo_attainment']:.2f}  "
          f"ttft_p95={s['ttft_p95']*1e3:.0f}ms")
    m = eng.sched.model
    print(f"calibrated cost model: a={m.a*1e3:.2f}ms "
          f"b={m.b*1e6:.1f}us/tok c={m.c*1e9:.2f}ns/ctx-tok")


if __name__ == "__main__":
    main()
