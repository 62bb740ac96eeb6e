"""End-to-end serving driver: batched requests through prefill + the SPMD
piped-ring decode on a multi-device mesh (deliverable b's serve driver).

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/ring_serving.py
"""
import os
import sys

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.data import ByteTokenizer, RequestGenerator
from repro.models import init_cache, init_params, prefill
from repro.runtime import serve


def main():
    cfg = dataclasses.replace(get_config("mixtral-8x7b").reduced(),
                              n_layers=8)   # 2 layers/stage -> k in {1,2}
    stages, tp = 4, 2
    mesh = make_mesh((stages, tp), ("data", "model"))
    B, ctx, new_tokens = 8, 64, 12

    params = init_params(cfg, jax.random.PRNGKey(0))
    gen = RequestGenerator(cfg.vocab, prompt_len=(12, 13), seed=7)
    reqs = gen.generate(B)
    prompts = jnp.asarray(np.stack([r.prompt for r in reqs]))

    cache = init_cache(cfg, B, ctx, dtype=jnp.float32)
    t0 = time.time()
    logits, cache = prefill(params, cfg, prompts, cache)
    print(f"prefill {B}x{prompts.shape[1]}: {time.time() - t0:.2f}s")

    plan = serve.RingPlan.make(cfg, stages, k=2)
    pr = serve.pad_vocab(dict(params), cfg, tp)
    pr["blocks"] = serve.pad_and_permute(params["blocks"], cfg, stages,
                                         plan.k)
    # int4 weight bank + dequant-in-kernel compute (the §Perf HC2 path)
    pr, skipped = serve.quantize_ring_params(pr, cfg, tp=tp)
    if skipped:
        print(f"warning: {len(skipped)} leaves left bf16: {skipped}")
    cache["layers"] = serve.pad_and_permute(cache["layers"], cfg, stages,
                                            plan.k)
    step = serve.build_ring_serve_step(cfg, mesh, plan)(pr, cache)

    ln = cache["len"]
    tok = jnp.argmax(logits[:, -1, :cfg.vocab], -1)[:, None]
    outs = [tok]
    t0 = time.time()
    for _ in range(new_tokens):
        logits, cache = step(tok, ln, pr, cache)
        ln = ln + 1
        tok = jnp.argmax(logits[:, 0, :cfg.vocab], -1)[:, None]
        outs.append(tok)
    dt = time.time() - t0
    print(f"ring decode (M={stages}, TP={tp}, k={plan.k}, int4 weights): "
          f"{new_tokens} steps in {dt:.2f}s "
          f"({dt / new_tokens * 1e3:.0f} ms/step for {B} seqs)")
    ids = jnp.concatenate(outs, 1)
    print("first sequence ids:", ids[0].tolist())


if __name__ == "__main__":
    main()
