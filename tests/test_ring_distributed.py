"""SPMD piped-ring serving on an 8-device CPU mesh: partition invariance
(the ring must produce byte-identical-to-tolerance logits vs the plain
single-device decode for every (w, k) split), plus the multi-pod replica
path."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.models import decode_step, init_cache, init_params
from repro.runtime import serve

KEY = jax.random.PRNGKey(0)

needs_8_devices = pytest.mark.skipif(
    jax.device_count() < 8, reason="needs 8 CPU devices (conftest sets flag)")


def _reference(cfg, params, toks, B, Smax, steps):
    cache = init_cache(cfg, B, Smax, dtype=jnp.float32)
    out = []
    for t in range(steps):
        lg, cache = decode_step(params, cfg, cache, toks[:, t:t + 1])
        out.append(lg)
    return out


def _ring(cfg, params, toks, B, Smax, steps, mesh, n_stages, tp, k):
    plan = serve.RingPlan.make(cfg, n_stages, k=k)
    pr = serve.pad_vocab(dict(params), cfg, tp)
    pr["blocks"] = serve.pad_and_permute(params["blocks"], cfg, n_stages, k)
    cache = init_cache(cfg, B, Smax, dtype=jnp.float32)
    cache["layers"] = serve.pad_and_permute(cache["layers"], cfg,
                                            n_stages, k)
    step = serve.build_ring_serve_step(cfg, mesh, plan)(pr, cache)
    ln = jnp.zeros((B,), jnp.int32)
    out = []
    for t in range(steps):
        logits, cache = step(toks[:, t:t + 1], ln, pr, cache)
        ln = ln + 1
        out.append(logits[:, :, :cfg.vocab])
    return out


def _run(arch, *, n_layers=8, k=1, B=8, Smax=32, steps=3, tol=2e-4,
         mesh_shape=(4, 2), axis_names=("data", "model"), **cfg_over):
    cfg = dataclasses.replace(get_config(arch).reduced(),
                              n_layers=n_layers, **cfg_over)
    params = init_params(cfg, KEY)
    toks = jax.random.randint(KEY, (B, steps + 1), 0, cfg.vocab)
    refs = _reference(cfg, params, toks, B, Smax, steps)
    mesh = make_mesh(mesh_shape, axis_names)
    n_stages = dict(zip(axis_names, mesh_shape))["data"]
    tp = dict(zip(axis_names, mesh_shape))["model"]
    outs = _ring(cfg, params, toks, B, Smax, steps, mesh, n_stages, tp, k)
    scale = float(jnp.max(jnp.abs(refs[-1])))
    for t, (a, b) in enumerate(zip(outs, refs)):
        rel = float(jnp.max(jnp.abs(a - b))) / scale
        assert rel < tol, (arch, k, t, rel)


@needs_8_devices
@pytest.mark.parametrize("k", [1, 2])
def test_ring_dense(k):
    _run("qwen2.5-14b", k=k)


@needs_8_devices
@pytest.mark.parametrize("k", [1, 2])
def test_ring_moe(k):
    _run("phi3.5-moe-42b-a6.6b", k=k)


@needs_8_devices
def test_ring_swa_rolling():
    _run("mixtral-8x7b", k=2, Smax=32)     # window == Smax: rolling buffer


@needs_8_devices
def test_ring_mla_absorbed():
    _run("minicpm3-4b", k=2)


@needs_8_devices
def test_ring_ssm():
    _run("mamba2-780m", k=2, tol=1e-5)


@needs_8_devices
def test_ring_int8_kv():
    _run("qwen1.5-32b", k=2, tol=2e-2)


@needs_8_devices
def test_ring_mrope():
    _run("qwen2-vl-2b", k=2)


@needs_8_devices
def test_ring_layer_padding():
    _run("minitron-8b", n_layers=6, k=1)   # L=6 on 4 stages -> 2 pad layers


@needs_8_devices
def test_ring_multi_pod_replicas():
    """(pod=2, data=2, model=2): each pod runs its own ring over its half
    of the batch; logits must still match the reference."""
    _run("qwen2.5-14b", n_layers=8, k=2, B=8, mesh_shape=(2, 2, 2),
         axis_names=("pod", "data", "model"))


@needs_8_devices
@pytest.mark.parametrize("arch", ["qwen2.5-14b", "minicpm3-4b"])
def test_ring_verify_multi_token(arch):
    """T=4 speculative verify through the ring == 4 sequential reference
    decode steps (per-position logit parity), then rollback + T=1 decode
    matches the never-rejected prefix."""
    cfg = dataclasses.replace(get_config(arch).reduced(), n_layers=8)
    params = init_params(cfg, KEY)
    B, Smax, T = 8, 32, 4
    toks = jax.random.randint(KEY, (B, T + 1), 0, cfg.vocab)

    # reference: sequential single-token decode
    cache = init_cache(cfg, B, Smax, dtype=jnp.float32)
    refs = []
    for t in range(T):
        lg, cache = decode_step(params, cfg, cache, toks[:, t:t + 1])
        refs.append(lg[:, 0])
    ref = jnp.stack(refs, 1)                             # (B, T, V)

    mesh = make_mesh((4, 2), ("data", "model"))
    plan = serve.RingPlan.make(cfg, 4, k=1)
    pr = serve.pad_vocab(dict(params), cfg, 2)
    pr["blocks"] = serve.pad_and_permute(params["blocks"], cfg, 4, 1)
    rcache = init_cache(cfg, B, Smax, dtype=jnp.float32)
    rcache["layers"] = serve.pad_and_permute(rcache["layers"], cfg, 4, 1)
    vstep = serve.build_ring_serve_step(cfg, mesh, plan,
                                        n_tokens=T)(pr, rcache)
    ln = jnp.zeros((B,), jnp.int32)
    logits, rcache = vstep(toks[:, :T], ln, pr, rcache)
    logits = logits[:, :, :cfg.vocab]
    scale = float(jnp.max(jnp.abs(ref)))
    assert float(jnp.max(jnp.abs(logits - ref))) / scale < 2e-4

    # rollback: keep 2 of the 4 positions, then decode token 2 again with
    # a T=1 ring step — must match the sequential reference at that point.
    keep = 2
    c_ref = init_cache(cfg, B, Smax, dtype=jnp.float32)
    for t in range(keep):
        _, c_ref = decode_step(params, cfg, c_ref, toks[:, t:t + 1])
    lg_ref, _ = decode_step(params, cfg, c_ref, toks[:, keep:keep + 1])
    step1 = serve.build_ring_serve_step(cfg, mesh, plan)(pr, rcache)
    lg_rb, _ = step1(toks[:, keep:keep + 1], jnp.full((B,), keep,
                                                      jnp.int32),
                     pr, rcache)
    rel = float(jnp.max(jnp.abs(lg_rb[:, :, :cfg.vocab] - lg_ref))) / float(
        jnp.max(jnp.abs(lg_ref)))
    assert rel < 2e-4


@needs_8_devices
def test_gspmd_decode_matches_reference():
    cfg = dataclasses.replace(get_config("recurrentgemma-9b").reduced(),
                              n_layers=6)
    params = init_params(cfg, KEY)
    B, Smax, steps = 8, 32, 3
    toks = jax.random.randint(KEY, (B, steps + 1), 0, cfg.vocab)
    refs = _reference(cfg, params, toks, B, Smax, steps)
    mesh = make_mesh((4, 2), ("data", "model"))
    cache = init_cache(cfg, B, Smax, dtype=jnp.float32)
    step = serve.gspmd_decode_step(cfg, mesh, params, cache)
    for t in range(steps):
        lg, cache = step(params, cache, toks[:, t:t + 1])
        rel = float(jnp.max(jnp.abs(lg - refs[t]))) / float(
            jnp.max(jnp.abs(refs[t])))
        assert rel < 2e-4, (t, rel)
