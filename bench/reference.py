"""The plain reference: the published forward pass in float32, and the
comparison of served tokens against it.

A model family (``bench/families/<model_type>.py``) computes ``hidden``,
the final-normed hidden states, from the benchmark's weight tree with
the helpers here; this module puts the output head over them and reads
each served token's gap. Matrix products run at the highest precision;
nothing here imports the serving program. The head runs over blocks of
the vocabulary, so the reference fits beside the bf16 weights on one
chip.

``mode="fp8"`` is the control: every operand of every matrix product is
rounded to float8 e4m3 with a per-tensor scale, the precision one step
below the bf16 the configurations state.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from . import spec

F32 = jnp.float32
V_BLOCK = 16384


def operand(x, mode):
    """``x`` in float32, rounded first to float8 e4m3 (per-tensor scale)
    where ``mode`` is ``fp8``."""
    if mode != "fp8":
        return x.astype(F32)
    x = x.astype(F32)
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def mm(a, b, mode):
    return jnp.matmul(operand(a, mode), operand(b, mode),
                      precision=jax.lax.Precision.HIGHEST)


def rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(F32)


def layer_slice(tree, i):
    """Layer ``i`` of every leaf of a tree stacked over layers."""
    return jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, False),
                        tree)


def hidden(params, dm, tokens, mode: str = "f32"):
    """(B, S) tokens -> (B, S, d) final-normed hidden states, by
    ``dm``'s model family."""
    return spec.family(dm.model_type).hidden(params, dm, tokens, mode)


@partial(jax.jit, static_argnames=("mode",))
def _head_block(h, w, mode):
    return mm(h, w, mode)


def logit_stats(params, h, targets):
    """Per position: the largest logit and the logit of ``targets``
    (both (B, S)), over the whole vocabulary in blocks."""
    w = params["unembed"]
    best = at = None
    tg = jnp.asarray(targets)
    for lo in range(0, w.shape[1], V_BLOCK):
        lg = _head_block(h, w[:, lo:lo + V_BLOCK], "f32")
        m = lg.max(-1)
        best = m if best is None else jnp.maximum(best, m)
        inside = (tg >= lo) & (tg < lo + lg.shape[-1])
        got = jnp.take_along_axis(lg, jnp.clip(tg - lo, 0, lg.shape[-1] - 1)
                                  [..., None], -1)[..., 0]
        at = jnp.where(inside, got, -jnp.inf if at is None else at)
    return np.asarray(best), np.asarray(at)


def argmax_tokens(params, h, mode: str):
    """The token each position puts first, computed in ``mode``."""
    w = params["unembed"]
    best = arg = None
    for lo in range(0, w.shape[1], V_BLOCK):
        lg = _head_block(h, w[:, lo:lo + V_BLOCK], mode)
        m, a = lg.max(-1), lg.argmax(-1) + lo
        if best is None:
            best, arg = m, a
        else:
            arg = jnp.where(m > best, a, arg)
            best = jnp.maximum(best, m)
    return np.asarray(arg)


def pack(seqs, length: int) -> np.ndarray:
    """Right-pad token sequences to one fixed length (causal attention
    leaves earlier positions untouched), so every run uses one shape."""
    out = np.zeros((len(seqs), length), np.int32)
    for i, s in enumerate(seqs):
        out[i, :len(s)] = s
    return out


def served_gaps(params, dm, samples, length: int,
                control: bool = False):
    """Per served token of ``samples`` (``[(prompt, served tokens)]``):
    the gap by which its logit lies below the reference's best at its
    position. With ``control``, also the same gap for the token the fp8
    computation puts first at each of those positions. Returns
    ``(gaps, control gaps or None)``, 1-D arrays."""
    seqs, targets, rows = [], np.zeros((len(samples), length), np.int64), []
    for b, (prompt, served) in enumerate(samples):
        S, n = len(prompt), len(served)
        seqs.append(np.concatenate([prompt, np.asarray(served[:-1], np.int32)]))
        targets[b, S - 1:S - 1 + n] = served
        rows.append((b, S - 1, S - 1 + n))
    toks = pack(seqs, length)
    h = hidden(params, dm, toks)

    def gaps_of(tg):
        best, at = logit_stats(params, h, tg)
        return np.concatenate([best[b, lo:hi] - at[b, lo:hi]
                               for b, lo, hi in rows])

    ctrl = None
    if control:
        hc = hidden(params, dm, toks, "fp8")
        tc = argmax_tokens(params, hc, "fp8")
        del hc
        ctrl = gaps_of(tc)
    return gaps_of(targets), ctrl


def statistic(gaps: np.ndarray, name: str) -> float:
    """``max``, or ``p<q>``: the q-th percentile (nearest rank)."""
    if not len(gaps):
        return float("inf")
    if name == "max":
        return float(gaps.max())
    v = np.sort(gaps)
    q = float(name[1:]) / 100.0
    return float(v[min(max(int(np.ceil(q * len(v))) - 1, 0), len(v) - 1)])
