#!/usr/bin/env python3
"""Smoke run of the serving path on a TPU at qwen2.5-14b's published widths.

    python3 chip_smoke.py            # one chip: paged continuous batching
    python3 chip_smoke.py --chips 4  # four chips: the pipelined ring only

One chip. Random bf16 weights from ``--seed``, depth cut from 48 to 12
layers, every width as published. A page pool sized so that weights and
pool fill ``POOL_FILL`` of HBM. Sixteen seeded requests (prompts of 256 to
1024 tokens, 32 new tokens each) go through 8 slots of
``make_paged_engine`` with chunked admission, so that both paged Pallas
kernels run: ``paged_prefill`` for admission, ``paged_verify`` for decode.
The run fails unless every request is answered, the lowered decode and
prefill-chunk steps both hold a ``tpu_custom_call``, and the logits of
one request's prefill and first decode steps agree with a float32
reference (``models.forward_blocked``).

Four chips. ``build_ring_serve_step`` on a (data=4, model=1) mesh, 8
layers (2 per stage), a few teacher-forced decode steps; the logits are
compared with ``models.decode_step`` on one chip and with the float32
reference, and per-device memory shows the stages on all four chips. The
same comparisons must fail for the ring with its stages out of order and
for the stack computed in float8.

Exits non-zero, with no result line, where JAX finds no TPU or the
kernels would not be compiled. The last line of a passing run is one
JSON object naming the device. Times printed are those of one smoke run,
compilation included: they are not benchmark results.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.data import RequestGenerator  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.models import (decode_step, forward_blocked, init_cache,  # noqa
                          init_params)
from repro.runtime import kvcache  # noqa: E402
from repro.runtime import serve as RS  # noqa: E402

ARCH = "qwen2.5-14b"
#: weights plus page pool, as a share of HBM. The decode step does not
#: donate its cache, so a second pool is alive during a step; 0.65 leaves
#: room for it and for the step's temporaries.
POOL_FILL = 0.65
#: Relative L2 error of a position's logit vector against the float32
#: reference. Weights are bf16 in both; the served path also keeps its
#: activations and KV in bf16 (unit roundoff 2**-8). On a v5e the same
#: 12-layer stack computed plainly in bf16 is off by 6.9e-3 and in
#: float8 e4m3 (2**-4) by 1.1. The bound sits about 3x above the first
#: and far below the second; each run recomputes both and fails if
#: float8 would pass.
REL_TOL = 2e-2

_compile = {"s": 0.0, "n": 0, "hits": 0}


def log(msg: str) -> None:
    print(msg, flush=True)


def fail(msg: str) -> None:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(1)


def _on_duration(event: str, secs: float, **_) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _compile["s"] += secs
        _compile["n"] += 1


def _on_event(event: str, **_) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        _compile["hits"] += 1


def compile_line() -> str:
    return (f"smoke compile: {_compile['s']:.1f} s over {_compile['n']} "
            f"compiles ({_compile['hits']} from the persistent cache)")


def require_tpu(n_chips: int):
    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"no TPU: JAX found platform {devices[0].platform!r}; this "
             "run has no CPU fallback")
    if not ops.kernels_active():
        fail("Pallas kernels would be interpreted or replaced by the jnp "
             "reference (kernels.ops.kernels_active() is false)")
    if len(devices) < n_chips:
        fail(f"needs {n_chips} TPU devices, found {len(devices)}")
    return devices


def rel_err(got, ref) -> np.ndarray:
    """Per-position relative L2 error over the vocabulary axis."""
    got = jnp.asarray(got, jnp.float32)
    ref = jnp.asarray(ref, jnp.float32)
    num = jnp.linalg.norm(got - ref, axis=-1)
    return np.asarray(num / jnp.linalg.norm(ref, axis=-1))


def tree_bytes(tree) -> int:
    return sum(a.nbytes for a in jax.tree.leaves(tree))


def mem_line(dev) -> str:
    st = dev.memory_stats() or {}
    gb = 1e9
    return (f"in use {st.get('bytes_in_use', 0) / gb:.2f} GB, peak "
            f"{st.get('peak_bytes_in_use', 0) / gb:.2f} GB of "
            f"{st.get('bytes_limit', 0) / gb:.2f} GB")


def init_weights(cfg, seed: int):
    """Random bf16 weights made on the device under jit, so the init's
    temporaries never hold a second float32 copy of the model."""
    init = jax.jit(init_params, static_argnums=(0, 2))
    return jax.block_until_ready(
        init(cfg, jax.random.PRNGKey(seed), jnp.bfloat16))


def check_kernels(lowered, name: str) -> None:
    if "tpu_custom_call" not in lowered.as_text():
        fail(f"{name}: no tpu_custom_call in the lowered step — a Pallas "
             "kernel was interpreted or replaced by the reference")
    log(f"{name}: tpu_custom_call present")


def check_logits(name: str, got, ref) -> float:
    err = rel_err(got, ref)
    worst = float(err.max())
    log(f"{name}: max relative L2 logit error {worst:.3e} over "
        f"{err.size} positions (tolerance {REL_TOL:.1e})")
    if not np.isfinite(np.asarray(got, np.float32)).all():
        fail(f"{name}: non-finite logits")
    if not worst <= REL_TOL:
        fail(f"{name}: logits off the float32 reference by {worst:.3e}")
    return worst


def must_miss(name: str, got, ref) -> None:
    """A control: logits of a deliberately wrong computation, which must
    fail ``REL_TOL`` or the bound would not catch that fault."""
    err = float(rel_err(got, ref).max())
    log(f"control: {name} is off by {err:.3e}")
    if not err > REL_TOL:
        fail(f"tolerance {REL_TOL:.1e} would pass {name}")


def check_tolerance(params, cfg, toks, ref) -> None:
    """The same stack computed plainly in bf16 and in float8: the first
    shows the rounding the served path cannot avoid, the second must fail
    ``REL_TOL``, or the bound would not tell bf16 from lower precision."""
    got = forward_blocked(params, cfg, toks, dtype=jnp.bfloat16)
    log(f"for scale: the same stack computed in bf16 is off by "
        f"{float(rel_err(got, ref).max()):.3e}")
    must_miss("the same stack computed in float8 e4m3",
              forward_blocked(params, cfg, toks, dtype=jnp.float8_e4m3fn),
              ref)


# --------------------------------------------------------------------------- #
#  one chip: paged continuous batching
# --------------------------------------------------------------------------- #

def serve_phase(cfg, params, *, n_requests: int = 16, slots: int = 8,
                lengths=(256, 384, 512, 640, 768, 896, 1024),
                max_new: int = 32, prefill_chunk: int = 64,
                page_tokens: int = 16, n_pages=None, n_probe: int = 8,
                seed: int = 0) -> dict:
    """Serve seeded requests through the paged engine and compare one
    request's logits with the float32 reference. ``n_pages=None`` sizes
    the pool from the device's HBM (``POOL_FILL``)."""
    dev = jax.devices()[0]
    ctx = max(lengths) + max_new
    gen = RequestGenerator(cfg.vocab, seed=seed, lengths=tuple(lengths),
                           max_new=max_new)
    reqs = [dataclasses.replace(r, max_new_tokens=max_new)
            for r in gen.generate(n_requests)]

    kv_line = 2 * cfg.kv_heads * cfg.head_dim * 2           # bf16 K and V
    page_bytes = cfg.n_layers * page_tokens * kv_line
    if n_pages is None:
        limit = dev.memory_stats()["bytes_limit"]
        n_pages = int((POOL_FILL * limit - tree_bytes(params))
                      // page_bytes)
    need = slots * (-(-ctx // page_tokens) + 1) + 1
    if n_pages < need:
        fail(f"page pool of {n_pages} pages cannot hold {slots} slots "
             f"of {ctx} tokens ({need} pages)")
    log(f"page pool: {n_pages} pages of {page_tokens} tokens "
        f"({n_pages * page_bytes / 1e9:.2f} GB bf16 KV; {slots} slots "
        f"need at most {need})")

    eng, kv = kvcache.make_paged_engine(
        params, cfg, slots, ctx, n_pages=n_pages, page_tokens=page_tokens,
        cache_dtype=jnp.bfloat16, prefill_chunk=prefill_chunk)

    # the steps the engine runs must hold the compiled Pallas kernels
    cache = kv.init_cache()
    lowered_decode = kvcache._decode_paged_jit.lower(
        params, cfg, cache, jnp.zeros((slots, 1), jnp.int32))
    view = {"pages": cache["pages"],
            "block_table": jnp.zeros((1, kv.max_pages), jnp.int32),
            "len": jnp.zeros((1,), jnp.int32)}
    lowered_chunk = kvcache._prefill_chunk_jit.lower(
        params, cfg, view, jnp.zeros((1, eng.prefill_chunk), jnp.int32),
        True)
    del cache, view
    check_kernels(lowered_decode, "decode step (paged_verify)")
    check_kernels(lowered_chunk, "prefill-chunk step (paged_prefill)")

    # capture the first request's logits on the served path itself: its
    # prompt chunks are the only ones run while no slot is active, and
    # its decode rows are read from its slot while it is in flight
    probe = reqs[0].uid
    prefill_logits, decode_logits = [], []
    inner_decode, inner_chunk = eng.decode, eng.chunk_step

    def decode(cache, tokens):
        logits, cache = inner_decode(cache, tokens)
        for i, st in enumerate(eng.slots):
            if st.uid == probe and len(decode_logits) < n_probe:
                decode_logits.append(logits[i, 0])
        return logits, cache

    def chunk_step(view, tokens, write=True):
        logits, view = inner_chunk(view, tokens, write)
        if not eng.active() and not decode_logits:
            prefill_logits.append(logits[0])
        return logits, view

    eng.decode, eng.chunk_step = decode, chunk_step

    t0 = time.perf_counter()
    finished, steps = eng.run(kv.init_cache(), reqs)
    wall = time.perf_counter() - t0
    live_kv = kv.stats().highwater_bytes
    kv.close()
    done = {f.uid: f.tokens for f in finished}
    if eng.rejected or len(done) != len(reqs):
        fail(f"{len(done)} of {len(reqs)} requests answered; shed "
             f"{[r.reason for r in eng.rejected]}")
    short = [u for u, t in done.items() if len(t) != max_new]
    if short:
        fail(f"requests {short} ended early")
    n_prompt = sum(len(r.prompt) for r in reqs)
    log(f"served {len(done)}/{len(reqs)} requests through {slots} slots: "
        f"{n_prompt} prompt tokens in chunks of {eng.prefill_chunk}, "
        f"{len(done) * max_new} generated tokens, {steps} scheduler loops; "
        f"smoke wall {wall:.1f} s (compilation included)")
    stats = dev.memory_stats()
    if stats:
        log(f"HBM after serving: {mem_line(dev)}")

    # float32 reference on the probe's prompt and its first decode inputs
    prompt = reqs[0].prompt
    gen_toks = done[probe][:n_probe]
    toks = np.concatenate([prompt, np.asarray(gen_toks, np.int32)])[None]
    t0 = time.perf_counter()
    toks = jnp.asarray(toks)
    ref = jax.block_until_ready(forward_blocked(params, cfg, toks))
    log(f"float32 reference over {toks.shape[1]} positions: smoke "
        f"{time.perf_counter() - t0:.1f} s")
    S = len(prompt)
    got_prefill = jnp.concatenate(prefill_logits, axis=0)
    if got_prefill.shape[0] != S or len(decode_logits) != n_probe:
        fail(f"captured {got_prefill.shape[0]} prefill and "
             f"{len(decode_logits)} decode logit rows for a {S}-token "
             f"prompt and {n_probe} decode steps")
    err_prefill = check_logits(f"paged prefill ({S} positions)",
                               got_prefill, ref[0, :S])
    err_decode = check_logits(f"paged decode (first {n_probe} steps)",
                              jnp.stack(decode_logits),
                              ref[0, S:S + n_probe])
    check_tolerance(params, cfg, toks, ref)
    return {"requests": len(done), "err_prefill": err_prefill,
            "err_decode": err_decode, "live_bytes":
            tree_bytes(params) + live_kv, "peak_bytes":
            (stats or {}).get("peak_bytes_in_use", 0),
            "bytes_limit": (stats or {}).get("bytes_limit", 0)}


def run_one_chip(dev, seed: int) -> None:
    cfg = dataclasses.replace(get_config(ARCH), n_layers=12)
    log(f"config: {ARCH} at published widths (d_model {cfg.d_model}, "
        f"{cfg.n_heads}/{cfg.kv_heads} heads of {cfg.head_dim}, d_ff "
        f"{cfg.d_ff}, vocab {cfg.vocab}, QKV bias); depth cut 48 -> "
        f"{cfg.n_layers} layers; bf16 weights and KV; seed {seed}")
    params = init_weights(cfg, seed)
    log(f"weights: {tree_bytes(params) / 1e9:.2f} GB bf16; HBM "
        f"{mem_line(dev)}")
    res = serve_phase(cfg, params, seed=seed)
    share = res["peak_bytes"] / res["bytes_limit"]
    log(f"HBM peak {res['peak_bytes'] / 1e9:.2f} GB = {share:.0%} of the "
        f"chip; weights plus the KV pages the requests held at most "
        f"{res['live_bytes'] / 1e9:.2f} GB = "
        f"{res['live_bytes'] / res['bytes_limit']:.0%} (the rest is the "
        f"unfilled pool, its per-step copies and temporaries)")
    if share < 0.6:
        fail(f"HBM peak {share:.0%} of the chip, below the 60% floor")


# --------------------------------------------------------------------------- #
#  four chips: the pipelined ring
# --------------------------------------------------------------------------- #

def ring_phase(cfg, params, mesh, *, batch: int = 8, ctx: int = 64,
               steps: int = 6, seed: int = 0) -> dict:
    """Teacher-forced decode through ``build_ring_serve_step`` against
    ``models.decode_step`` on one device and the float32 reference; then
    the same step with every stage holding the next stage's layers, a
    ring fault that the comparison must catch."""
    n_stages, tp = mesh.shape["data"], mesh.shape["model"]
    toks = jax.random.randint(jax.random.PRNGKey(seed + 1),
                              (batch, steps), 3, cfg.vocab, jnp.int32)

    # single-device reference: the plain decode step, then float32
    step1 = jax.jit(decode_step, static_argnums=(1,))
    cache = init_cache(cfg, batch, ctx, dtype=jnp.bfloat16)
    ref_bf16 = []
    for t in range(steps):
        lg, cache = step1(params, cfg, cache, toks[:, t:t + 1])
        ref_bf16.append(lg[:, 0])
    ref_bf16 = jnp.stack(ref_bf16, 1)                 # (B, steps, V)
    del cache
    ref32 = forward_blocked(params, cfg, toks)

    # ring layout, placed one leaf at a time so that device 0 never holds
    # a second permuted copy of the whole layer stack
    def ring_order(tree):
        return RS.pad_and_permute(tree, cfg, n_stages, 1)

    def place(tree, specs, prep=lambda a: a):
        return jax.tree.map(lambda a, s: jax.device_put(
            prep(a), jax.sharding.NamedSharding(mesh, s)), tree, specs)

    def ring_cache():
        rc = init_cache(cfg, batch, ctx, dtype=jnp.bfloat16)
        rc["layers"] = ring_order(rc["layers"])
        return place(rc, RS.ring_cache_specs(cfg, mesh, rc))

    plan = RS.RingPlan.make(cfg, n_stages, k=1)
    pr = RS.pad_vocab(dict(params), cfg, tp)
    shapes = dict(pr, blocks=jax.eval_shape(ring_order, pr["blocks"]))
    pr = place(pr, RS.ring_param_specs(cfg, mesh, shapes),
               prep=lambda a: ring_order(a) if a.ndim and
               a.shape[0] == cfg.n_layers else a)
    step = RS.build_ring_serve_step(cfg, mesh, plan)(pr, ring_cache())

    def decode(ring_params):
        rc, ln, out = ring_cache(), jnp.zeros((batch,), jnp.int32), []
        for t in range(steps):
            logits, rc = step(toks[:, t:t + 1], ln, ring_params, rc)
            ln = ln + 1
            out.append(logits[:, 0, :cfg.vocab])
        return jnp.stack(out, 1)

    got = decode(pr)
    # the fault: the layer axis rolled by one stage, so that the stages
    # run their layers in the wrong order (one stage's worth of extra
    # weights per device, no recompile)
    w = plan.L_pad // n_stages
    roll = jax.jit(lambda b: jax.tree.map(lambda a: jnp.roll(a, w, 0), b),
                   out_shardings=jax.tree.map(lambda a: a.sharding,
                                              pr["blocks"]))
    misordered = decode(dict(pr, blocks=roll(pr["blocks"])))
    return {"ring": got, "misordered": misordered, "ref_bf16": ref_bf16,
            "ref32": ref32, "toks": toks, "ring_params": pr}


def run_four_chips(devices, seed: int) -> None:
    cfg = dataclasses.replace(get_config(ARCH), n_layers=8)
    log(f"config: {ARCH} at published widths; depth cut 48 -> "
        f"{cfg.n_layers} layers, 2 per stage on a 4-stage ring; bf16")
    params = init_weights(cfg, seed)
    res = ring_phase(cfg, params, make_mesh((4, 1)), seed=seed)
    for i, d in enumerate(devices[:4]):
        held = sum(s.data.nbytes for a in jax.tree.leaves(res["ring_params"])
                   for s in a.addressable_shards if s.device == d)
        log(f"device {i} ({d.device_kind}): ring weights held "
            f"{held / 1e9:.2f} GB; HBM {mem_line(d)}")
    check_logits("ring vs float32 reference", res["ring"], res["ref32"])
    check_logits("single-chip decode_step vs float32 reference",
                 res["ref_bf16"], res["ref32"])
    check_logits("ring vs single-chip decode_step", res["ring"],
                 res["ref_bf16"])
    must_miss("the ring with its stages out of order, vs float32",
              res["misordered"], res["ref32"])
    must_miss("the ring with its stages out of order, vs decode_step",
              res["misordered"], res["ref_bf16"])
    check_tolerance(params, cfg, res["toks"], res["ref32"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the pipelined ring over four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    devices = require_tpu(args.chips)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)
    log(f"compile cache: {enable_compile_cache()}")
    t0 = time.perf_counter()
    if args.chips == 4:
        run_four_chips(devices, args.seed)
    else:
        run_one_chip(devices[0], args.seed)
    log(compile_line())
    log(f"smoke total: {time.perf_counter() - t0:.1f} s")
    d = jax.devices()
    print(json.dumps({"ok": True, "device": {
        "platform": d[0].platform, "kind": d[0].device_kind,
        "count": len(d)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
