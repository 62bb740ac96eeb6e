"""Fault tolerance end to end: checkpoint/restart + stage failure ->
Halda re-plan -> ring remap -> continue decoding with identical results.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/elastic_failover.py
"""
import os
import sys

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import dataclasses
import tempfile

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.models import init_cache, init_params, prefill
from repro.runtime import elastic, serve
from repro.runtime.checkpoint import CheckpointManager


def decode_on_ring(cfg, params, cache, tok0, mesh, plan, steps):
    """Permute the logical cache for this ring plan and decode."""
    stages = mesh.shape["data"]
    tp = mesh.shape["model"]
    pr = serve.pad_vocab(dict(params), cfg, tp)
    pr["blocks"] = serve.pad_and_permute(params["blocks"], cfg, stages,
                                         plan.k)
    rc = dict(cache)
    rc["layers"] = serve.pad_and_permute(cache["layers"], cfg, stages,
                                         plan.k)
    step = serve.build_ring_serve_step(cfg, mesh, plan)(pr, rc)
    ln = rc["len"]
    tok = tok0
    out = []
    for _ in range(steps):
        logits, rc = step(tok, ln, pr, rc)
        ln = ln + 1
        tok = jnp.argmax(logits[:, 0, :cfg.vocab], -1)[:, None]
        out.append(tok)
    return out


def main():
    cfg = dataclasses.replace(get_config("qwen2.5-14b").reduced(),
                              n_layers=8)
    key = jax.random.PRNGKey(0)
    params = init_params(cfg, key)
    B, ctx = 8, 64
    prompt = jax.random.randint(key, (B, 8), 0, cfg.vocab)

    cache = init_cache(cfg, B, ctx, dtype=jnp.float32)
    logits, cache = prefill(params, cfg, prompt, cache)
    tok0 = jnp.argmax(logits[:, -1], -1)[:, None]

    # checkpoint the logical (un-permuted) decode state
    ckpt_dir = tempfile.mkdtemp(prefix="elastic_")
    mgr = CheckpointManager(ckpt_dir)
    mgr.save(0, (cache, tok0))
    print(f"checkpointed post-prefill state -> {ckpt_dir}")

    # ---- healthy ring: 4 stages ----------------------------------------
    st = elastic.initial_state(cfg, 4, k=2)
    print(f"gen-{st.generation}: {len(st.stages)} stages, k={st.plan.k}, "
          f"w={st.plan.w}")
    mesh4 = make_mesh((4, 2), ("data", "model"))
    toks_healthy = decode_on_ring(cfg, params, cache, tok0, mesh4,
                                  st.plan, steps=3)
    print("tokens (healthy)  :",
          jnp.concatenate(toks_healthy, 1)[0].tolist())

    # ---- two stages die -> re-plan on 2 stages, restore, replay ---------
    st = elastic.fail_stages(st, cfg, [2, 3])
    print(f"gen-{st.generation}: {len(st.stages)} stages survive, "
          f"k={st.plan.k}, w={st.plan.w}")
    mesh2 = make_mesh((2, 4), ("data", "model"))
    _, (cache_r, tok_r) = mgr.restore_latest(
        (jax.tree.map(jnp.zeros_like, cache), tok0))
    toks_failover = decode_on_ring(cfg, params, cache_r, tok_r, mesh2,
                                   st.plan, steps=3)
    print("tokens (failover) :",
          jnp.concatenate(toks_failover, 1)[0].tolist())

    same = all(bool((a == b).all())
               for a, b in zip(toks_healthy, toks_failover))
    print("failover reproduces the pre-failure stream:", same)
    assert same


if __name__ == "__main__":
    main()
