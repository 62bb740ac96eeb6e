"""Per-arch smoke tests (deliverable f): every assigned architecture, at a
reduced same-family config, runs one forward and one train step on CPU with
shape assertions and no NaNs; plus prefill+decode vs teacher-forced forward
consistency."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ASSIGNED_ARCHS, get_config
from repro.models import (decode_step, forward, forward_blocked,
                          init_cache, init_params, prefill)
from repro.runtime.optim import AdamW
from repro.runtime.train import lm_loss, make_train_step

KEY = jax.random.PRNGKey(0)


def _inputs(cfg, B=2, S=16, extra=0):
    toks = jax.random.randint(KEY, (B, S + extra), 0, cfg.vocab)
    emb = None
    if cfg.frontend:
        emb = jax.random.normal(
            KEY, (B, cfg.n_frontend_tokens, cfg.d_model)) * 0.02
    return toks, emb


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_smoke_forward(arch):
    cfg = get_config(arch).reduced()
    params = init_params(cfg, KEY)
    toks, emb = _inputs(cfg)
    logits = forward(params, cfg, toks, embeds=emb)
    S_tot = toks.shape[1] + (cfg.n_frontend_tokens
                             if cfg.frontend and cfg.family != "audio" else 0)
    assert logits.shape == (2, S_tot, cfg.vocab)
    assert np.isfinite(np.asarray(logits)).all()


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_smoke_train_step(arch):
    cfg = get_config(arch).reduced()
    params = init_params(cfg, KEY)
    toks, emb = _inputs(cfg)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    if emb is not None:
        batch["embeds"] = emb
    step = make_train_step(cfg, AdamW(lr=1e-3), grad_dtype=None,
                           remat=False, has_embeds=emb is not None)
    opt = AdamW(lr=1e-3).init(params)
    new_params, new_opt, metrics = step(params, opt, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["grad_norm"]))
    assert int(metrics["step"]) == 1
    # parameters actually moved
    moved = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))),
                         params, new_params)
    assert max(jax.tree.leaves(moved)) > 0


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_decode_matches_forward(arch):
    cfg = get_config(arch).reduced()
    params = init_params(cfg, KEY)
    B, S, extra = 2, 12, 3
    toks, emb = _inputs(cfg, B, S, extra)
    full = forward(params, cfg, toks, embeds=emb)
    off = cfg.n_frontend_tokens if (cfg.frontend
                                    and cfg.family != "audio") else 0
    cache = init_cache(cfg, B, 48, dtype=jnp.float32)
    lg, cache = prefill(params, cfg, toks[:, :S], cache, embeds=emb)
    errs = [float(jnp.max(jnp.abs(lg[:, 0] - full[:, off + S - 1])))]
    for t in range(S, S + extra):
        lg, cache = decode_step(params, cfg, cache, toks[:, t:t + 1])
        errs.append(float(jnp.max(jnp.abs(lg[:, 0] - full[:, off + t]))))
    rel = max(errs) / float(jnp.max(jnp.abs(full)))
    tol = 2e-2 if cfg.kv_dtype == "int8" else 2e-4
    assert rel < tol, (arch, rel)


@pytest.mark.parametrize("arch", ["qwen2.5-14b", "mixtral-8x7b"])
def test_forward_blocked_is_upcast_forward(arch):
    """The layer-at-a-time float32 reference equals ``forward`` on the
    whole upcast parameter tree, vocabulary blocks included."""
    cfg = get_config(arch).reduced()
    params = init_params(cfg, KEY, dtype=jnp.bfloat16)
    toks, _ = _inputs(cfg)
    up = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        ref = forward(up, cfg, toks)
    got = forward_blocked(params, cfg, toks, vocab_block=48)
    assert got.dtype == jnp.float32 and got.shape == ref.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_loss_decreases_dense():
    cfg = dataclasses.replace(get_config("qwen2.5-14b").reduced(),
                              n_layers=2)
    params = init_params(cfg, KEY)
    opt_def = AdamW(lr=3e-3, warmup_steps=5)
    opt = opt_def.init(params)
    step = make_train_step(cfg, opt_def, grad_dtype=None, remat=False)
    step = jax.jit(step)
    toks = jax.random.randint(KEY, (4, 33), 0, 64)   # learnable: tiny vocab
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    losses = []
    for _ in range(25):
        params, opt, m = step(params, opt, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] * 0.7, losses[:3] + losses[-3:]


def test_swa_rolling_buffer_consistency():
    """SWA decode with a full rolling buffer matches a fresh full-context
    prefill truncated to the window."""
    cfg = dataclasses.replace(get_config("mixtral-8x7b").reduced(),
                              n_layers=2, attn_window=8)
    params = init_params(cfg, KEY)
    B, S = 1, 20
    toks = jax.random.randint(KEY, (B, S + 1), 0, cfg.vocab)
    full = forward(params, cfg, toks)       # SWA causal over all positions
    cache = init_cache(cfg, B, 64, dtype=jnp.float32)
    lg, cache = prefill(params, cfg, toks[:, :S], cache)
    err = float(jnp.max(jnp.abs(lg[:, 0] - full[:, S - 1])))
    lg2, cache = decode_step(params, cfg, cache, toks[:, S:S + 1])
    err2 = float(jnp.max(jnp.abs(lg2[:, 0] - full[:, S])))
    scale = float(jnp.max(jnp.abs(full)))
    assert max(err, err2) / scale < 2e-5


def test_grad_accumulation_equivalence():
    cfg = dataclasses.replace(get_config("minitron-8b").reduced(),
                              n_layers=2)
    params = init_params(cfg, KEY)
    opt_def = AdamW(lr=1e-3)
    toks = jax.random.randint(KEY, (8, 17), 0, cfg.vocab)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    full = make_train_step(cfg, opt_def, grad_dtype=None, remat=False)
    micro = make_train_step(cfg, opt_def, grad_dtype=None, remat=False,
                            microbatch=2)
    p1, _, m1 = full(params, opt_def.init(params), batch)
    p2, _, m2 = micro(params, opt_def.init(params), batch)
    assert float(m1["loss"]) == pytest.approx(float(m2["loss"]), rel=1e-5)
    diff = max(jax.tree.leaves(jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))), p1, p2)))
    assert diff < 5e-5, diff
