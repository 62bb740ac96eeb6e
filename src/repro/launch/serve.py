"""Serving driver: ``python -m repro.launch.serve --arch <id> --smoke``.

Runs batched requests through prefill + piped-ring decode on a
(data=stages, model=tp) mesh over the devices present: the four chips of
a TPU v5e host, or forced host devices on the CPU (set
``XLA_FLAGS=--xla_force_host_platform_device_count=8``).
"""
from __future__ import annotations

import argparse
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import get_config
from ..data import RequestGenerator
from ..models import init_cache, init_params, prefill
from ..runtime import serve as RS
from ..runtime.telemetry import clock
from .compile_cache import enable_compile_cache
from .mesh import make_mesh


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-14b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--ring-k", type=int, default=1)
    ap.add_argument("--verify-tokens", type=int, default=0,
                    help="T>1: also time a T-token speculative verify "
                         "pass through the ring (weights streamed once "
                         "per pass) against T single-token steps")
    ap.add_argument("--ctx", type=int, default=64)
    ap.add_argument("--stages", type=int, default=0,
                    help="ring stages (0 = the largest divisor of --batch "
                         "that neither the devices present nor the layer "
                         "count exceed)")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel chips inside each stage")
    ap.add_argument("--stream-window", type=int, default=0,
                    help="W>0: also run weight-streaming decode (mmap "
                         "layer store + async prefetcher keeping W layers "
                         "resident) and, on the ring path, the streamed "
                         "ring driver; reports TPOT and peak resident "
                         "parameter bytes vs the fully-resident run")
    ap.add_argument("--store-quant", choices=("none", "q4"), default="none",
                    help="q4: persist the layer store with packed int4 "
                         "weights + bf16 group scales (v2 manifest) and "
                         "stream the packed bytes through the prefetch "
                         "window, dequantizing per layer at use — ~4x "
                         "fewer streamed bytes/layer than bf16")
    ap.add_argument("--chaos", choices=("none", "transient", "failover"),
                    default="none",
                    help="fault-injection smoke: 'transient' injects "
                         "retryable disk faults into the streamed "
                         "layer-wise decode and requires byte-identical "
                         "recovery; 'failover' kills a ring stage "
                         "mid-decode and requires the elastic re-solve "
                         "to resume with zero tokens lost (both exit "
                         "nonzero on a failed recovery)")
    ap.add_argument("--chaos-faults", type=int, default=3,
                    help="consecutive transient faults to inject "
                         "(capped at --io-retries: retries re-hit the "
                         "fault window)")
    ap.add_argument("--io-retries", type=int, default=3,
                    help="IOPolicy: max retries per I/O op before the "
                         "error is classified fatal")
    ap.add_argument("--io-backoff-ms", type=float, default=10.0,
                    help="IOPolicy: base exponential-backoff delay")
    ap.add_argument("--io-deadline-s", type=float, default=30.0,
                    help="IOPolicy: per-op deadline; a stalled read "
                         "surfaces as StallTimeout instead of hanging")
    ap.add_argument("--paged-kv", action="store_true",
                    help="also run continuous batching over the paged KV "
                         "cache (block-pool allocator + prefix reuse + "
                         "host offload) against the dense-cache engine "
                         "on the same requests; fails on any token "
                         "mismatch and reports KV high-water vs the "
                         "dense envelope")
    ap.add_argument("--prefill-chunk", type=int, default=0, metavar="N",
                    help="with --paged-kv: admit prompts in N-token "
                         "chunks computed straight into the block pool, "
                         "interleaving one decode step for the active "
                         "slots between chunks so a long admit never "
                         "stalls decode for the whole prompt (0 = "
                         "whole-prompt scratch prefill); tokens must stay "
                         "byte-identical to the unchunked run")
    ap.add_argument("--kv-quant-kernel", action="store_true",
                    help="with --paged-kv: store KV pages int8 with "
                         "per-vector scales and attend through the fused "
                         "dequant-in-kernel paged flash kernels (pages "
                         "are read packed, never inflated to bf16 in "
                         "HBM; jnp dequant oracle off-TPU)")
    ap.add_argument("--device-budget", type=float, default=0.0,
                    metavar="MB",
                    help="with --paged-kv: cap device-tier KV bytes; the "
                         "paged pool sizes itself to the budget and the "
                         "tier manager audits that the high-water never "
                         "exceeds it (0 = unbounded)")
    ap.add_argument("--host-budget", type=float, default=0.0,
                    metavar="MB",
                    help="with --paged-kv: cap host-tier bytes (offloaded"
                         " + parked pages); refusals spill the coldest "
                         "pages to the disk tier (0 = unbounded)")
    ap.add_argument("--park-idle-s", type=float, default=None,
                    metavar="S",
                    help="with --paged-kv: enable session parking — "
                         "finished sessions keep their KV on host, "
                         "demote to per-session disk files after S idle "
                         "seconds, and restore byte-identically on the "
                         "next admit; runs a split-run parity check")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="capture a unified runtime trace (spans from "
                         "prefetchers, offloader, decode steps, faults, "
                         "failovers) and write Chrome-trace JSON here — "
                         "open it at https://ui.perfetto.dev")
    ap.add_argument("--metrics-interval", type=int, default=0,
                    metavar="N",
                    help="print a rolling metrics line every N decode "
                         "tokens: stall attribution (with --trace) and "
                         "request/step percentiles (with --metrics-out)")
    ap.add_argument("--metrics-out", default=None, metavar="OUT.json",
                    help="collect serving metrics (request lifecycle "
                         "percentiles, engine counters, subsystem "
                         "gauges) in a MetricsRegistry and write the "
                         "JSON snapshot here — check it with `python -m "
                         "repro.runtime.metrics --validate OUT.json`")
    args = ap.parse_args(argv)

    from ..runtime.telemetry import NULL_TRACER, Tracer
    tracer = Tracer() if args.trace else NULL_TRACER
    metrics = None
    if args.metrics_out:
        from ..runtime.metrics import MetricsRegistry
        metrics = MetricsRegistry()

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.reduced()
    B, tp = args.batch, args.tp
    most = min(len(jax.devices()) // tp, cfg.n_layers)
    stages = args.stages or max(m for m in range(1, most + 1) if B % m == 0)
    mesh = make_mesh((stages, tp))
    print(f"mesh: {stages} stages x {tp} tp over "
          f"{jax.devices()[0].platform} devices")

    if not RS.ring_supported(cfg, B, stages):
        print(f"{cfg.name}: ring unsupported for B={B}, M={stages} "
              f"(family={cfg.family}) — GSPMD decode path")
        ring = False
    else:
        ring = True

    key = jax.random.PRNGKey(0)
    params = init_params(cfg, key)
    gen = RequestGenerator(cfg.vocab, seed=1,
                           prompt_len=(args.prompt_len,
                                       args.prompt_len + 1))
    reqs = gen.generate(B)
    prompts = jnp.asarray(np.stack([r.prompt for r in reqs]))

    # prefill on the plain path (batch prompts, same length)
    cache = init_cache(cfg, B, args.ctx, dtype=jnp.float32)
    t0 = clock()
    logits, cache = prefill(params, cfg, prompts, cache)
    nxt = jnp.argmax(logits[:, -1], -1)[:, None]
    ttft = clock() - t0
    print(f"prefill: {B}×{args.prompt_len} tokens in {ttft*1e3:.0f} ms")
    if metrics is not None:
        metrics.observe("request/ttft_s", ttft)

    if ring:
        plan = RS.RingPlan.make(cfg, stages, k=args.ring_k)
        pr = RS.pad_vocab(dict(params), cfg, tp)
        pr["blocks"] = RS.pad_and_permute(params["blocks"], cfg, stages,
                                          plan.k)
        cache["layers"] = RS.pad_and_permute(cache["layers"], cfg, stages,
                                             plan.k)
        step = RS.build_ring_serve_step(cfg, mesh, plan)(pr, cache)
        ln = cache["len"]
        out_tokens = [nxt]
        t0 = clock()
        for t in range(args.new_tokens):
            ts = clock()
            with tracer.token_step(t, track="decode"):
                with tracer.phase("compute"):
                    logits, cache = step(nxt, ln, pr, cache)
                    ln = ln + 1
                    nxt = jnp.argmax(logits[:, 0, :cfg.vocab],
                                     -1)[:, None]
                    nxt = jax.block_until_ready(nxt)
            if metrics is not None:
                metrics.observe("decode/step_s", clock() - ts)
                metrics.inc("tokens/generated", B)
            out_tokens.append(nxt)
            _metrics_tick(tracer, args, t, metrics)
        dt = clock() - t0
        print(f"ring decode (k={plan.k}, w={plan.w}, M={stages}, TP={tp}): "
              f"{args.new_tokens} tokens × {B} seqs in {dt:.2f}s "
              f"-> {dt / args.new_tokens * 1e3:.1f} ms/token/batch")

        T = args.verify_tokens
        if T > 1 and cfg.family != "ssm":
            vstep = RS.build_ring_serve_step(cfg, mesh, plan,
                                             n_tokens=T)(pr, cache)
            vt = jnp.tile(nxt, (1, T))
            logits, cache = vstep(vt, ln, pr, cache)   # compile + warm
            jax.block_until_ready(logits)
            iters = 3
            t0 = clock()
            for _ in range(iters):
                logits, cache = vstep(vt, ln, pr, cache)
                jax.block_until_ready(logits)
            dtv = (clock() - t0) / iters
            per_tok = dt / args.new_tokens
            print(f"verify pass (T={T}): {dtv * 1e3:.1f} ms vs "
                  f"{T}×{per_tok * 1e3:.1f} ms single steps -> "
                  f"amortization {T * per_tok / dtv:.2f}x")
    else:
        step = RS.gspmd_decode_step(cfg, mesh, params, cache)
        t0 = clock()
        for t in range(args.new_tokens):
            logits, cache = step(params, cache, nxt)
            nxt = jnp.argmax(logits[:, 0], -1)[:, None]
        dt = clock() - t0
        print(f"gspmd decode: {args.new_tokens} × {B} in {dt:.2f}s")

    if args.stream_window > 0:
        if cfg.family not in ("dense", "moe", "vlm", "ssm"):
            raise SystemExit(f"stream-window: unsupported family "
                             f"{cfg.family}")
        _stream_smoke(cfg, params, prompts, args,
                      ring_ctx=(mesh, stages, tp) if ring else None,
                      tracer=tracer)
    if args.paged_kv:
        pcfg = cfg
        if args.kv_quant_kernel and cfg.kv_dtype != "int8":
            pcfg = dataclasses.replace(cfg, kv_dtype="int8")
        if cfg.family not in ("dense", "moe", "vlm"):
            raise SystemExit(f"paged-kv: unsupported family {cfg.family}")
        if pcfg.kv_dtype == "int8" and pcfg.mla:
            raise SystemExit("paged-kv: int8 MLA latent pages unsupported")
        _paged_smoke(pcfg, params, args, tracer=tracer, metrics=metrics)
    if args.chaos != "none":
        if cfg.family not in ("dense", "moe", "vlm", "ssm"):
            raise SystemExit(f"chaos: unsupported family {cfg.family}")
        _chaos_smoke(cfg, params, prompts, args,
                     ring_ctx=(mesh, stages, tp) if ring else None,
                     tracer=tracer)
    print("sample token ids:", np.asarray(nxt).ravel()[:8].tolist())
    if args.trace:
        from ..runtime.telemetry import format_summary
        tracer.export_chrome_trace(args.trace)
        summ = tracer.summary()
        if summ.get("n"):
            print("stall attribution:", format_summary(summ))
        print(f"trace: {len(tracer.events())} events on "
              f"{len(tracer.tracks())} tracks -> {args.trace} "
              f"(open at https://ui.perfetto.dev)")
    if metrics is not None:
        from ..runtime.metrics import validate_metrics_snapshot
        path = metrics.export_json(args.metrics_out)
        info = validate_metrics_snapshot(path)
        print(f"metrics: {info['counters']} counters, "
              f"{info['gauges']} gauges, {info['histograms']} "
              f"histograms -> {path}")
        print(_percentile_line(metrics) or "metrics: no samples yet")
    return 0


def _percentile_line(metrics) -> str:
    """One line of request/step percentiles for the console."""
    pcts = metrics.percentile_summary()
    parts = []
    for key, label in (("request/ttft_s", "ttft"),
                       ("request/tpot_s", "tpot"),
                       ("request/queue_wait_s", "queue"),
                       ("decode/step_s", "step")):
        if f"{key}/p50" in pcts:
            parts.append(f"{label} p50/p99 "
                         f"{pcts[f'{key}/p50'] * 1e3:.1f}/"
                         f"{pcts[f'{key}/p99'] * 1e3:.1f} ms")
    if "request/prefill_chunks/p50" in pcts:
        parts.append(f"prefill chunks p50/p99 "
                     f"{pcts['request/prefill_chunks/p50']:.0f}/"
                     f"{pcts['request/prefill_chunks/p99']:.0f}")
    stall = metrics._counters.get("decode/interleave_stall_s")
    if stall is not None and stall.value > 0:
        parts.append(f"interleave stall {stall.value * 1e3:.1f} ms")
    return "; ".join(parts)


def _metrics_tick(tracer, args, t: int, metrics=None) -> None:
    """Print a periodic rolling line (--metrics-interval): stall
    attribution when tracing, request/step percentiles when metering."""
    n = args.metrics_interval
    if n <= 0 or (t + 1) % n != 0:
        return
    if args.trace:
        from ..runtime.telemetry import format_summary
        summ = tracer.summary(last_n=n)
        if summ.get("n"):
            print(f"[token {t + 1}] {format_summary(summ)}")
    if metrics is not None:
        line = _percentile_line(metrics)
        if line:
            print(f"[token {t + 1}] {line}")


def _io_policy(args):
    from ..runtime.iopolicy import IOPolicy

    return IOPolicy(max_retries=args.io_retries,
                    backoff_base_s=args.io_backoff_ms / 1e3,
                    backoff_max_s=max(args.io_backoff_ms / 1e3, 0.1),
                    op_deadline_s=args.io_deadline_s,
                    get_timeout_s=2 * args.io_deadline_s)


def _chaos_smoke(cfg, params, prompts, args, *, ring_ctx=None,
                 tracer=None) -> None:
    """Fault-injection smoke: recovery is the pass criterion."""
    import shutil
    import tempfile

    from ..models import decode_step_layerwise
    from ..runtime.faults import FaultInjector, FaultSpec, FaultyStore
    from ..runtime.paramstore import ParamStore, save_param_store
    from ..runtime.streaming import StreamingParamSource

    policy = _io_policy(args)
    B = prompts.shape[0]
    sdir = tempfile.mkdtemp(prefix="chaos_store_")
    try:
        save_param_store(params, cfg, sdir)
        if args.chaos == "transient":
            def decode(store, pol=None):
                with StreamingParamSource(store, window=2,
                                          policy=pol) as src:
                    c = init_cache(cfg, B, args.ctx, dtype=jnp.float32)
                    lg, c = prefill(params, cfg, prompts, c)
                    tok = jnp.argmax(lg[:, -1], -1)[:, None]
                    out = [np.asarray(tok)]
                    for _ in range(args.new_tokens):
                        lg, c = decode_step_layerwise(src, cfg, c, tok)
                        tok = jnp.argmax(lg[:, 0], -1)[:, None]
                        out.append(np.asarray(tok))
                    return np.concatenate(out, 1), src.stats()

            clean, _ = decode(ParamStore(sdir))
            n = min(args.chaos_faults, policy.max_retries)
            inj = FaultInjector([FaultSpec(op="layer_read", after=4,
                                           times=n)])
            chaos, st = decode(FaultyStore(ParamStore(sdir), inj),
                               policy)
            if not np.array_equal(clean, chaos):
                raise SystemExit("chaos transient: tokens DIVERGED "
                                 "after retry recovery")
            print(f"chaos transient: {len(inj.fired)} injected disk "
                  f"faults absorbed by retry/backoff "
                  f"({st.retries} retries in PrefetchStats); tokens "
                  f"byte-identical to the clean run")
        else:   # failover
            from ..runtime.failover import ElasticRingServer

            if ring_ctx is None:
                raise SystemExit("chaos failover: needs the ring path")
            _, stages, tp = ring_ctx

            class Counting:
                def __init__(self, store):
                    self.store, self.reads = store, 0

                def layer(self, i):
                    self.reads += 1
                    return self.store.layer(i)

                def __getattr__(self, name):
                    return getattr(self.store, name)

            counting = Counting(ParamStore(sdir))
            srv = ElasticRingServer(cfg, counting, params, batch=B,
                                    ctx=args.ctx, n_stages=stages,
                                    tp=tp, k=args.ring_k, policy=policy)
            try:
                srv.generate(np.asarray(prompts, np.int32), 2)
            finally:
                srv.close()
                counting.close()

            inj = FaultInjector([FaultSpec(
                op="layer_read", mode="stage_failure", stage=1,
                after=counting.reads, times=1)], tracer=tracer)
            store = FaultyStore(ParamStore(sdir), inj)
            srv = ElasticRingServer(cfg, store, params, batch=B,
                                    ctx=args.ctx, n_stages=stages,
                                    tp=tp, k=args.ring_k, policy=policy,
                                    tracer=tracer)
            try:
                toks = srv.generate(np.asarray(prompts, np.int32),
                                    args.new_tokens)
            finally:
                srv.close()
                store.close()
            if not srv.events:
                raise SystemExit("chaos failover: injected stage death "
                                 "never surfaced")
            ev = srv.events[0]
            if ev.tokens_lost or toks.shape[1] != args.new_tokens:
                raise SystemExit(f"chaos failover: lost "
                                 f"{ev.tokens_lost} tokens")
            print(f"chaos failover: stage {ev.failed_stage} died at "
                  f"token {ev.token_index}; ring {ev.n_stages_before}->"
                  f"{ev.n_stages_after} stages, replayed "
                  f"{ev.replayed_tokens} tokens, recovered in "
                  f"{ev.recovery_s:.2f}s (detect {ev.detect_s * 1e3:.1f}"
                  f" ms, re-solve {ev.resolve_s * 1e3:.1f} ms, rebuild "
                  f"{ev.rebuild_s:.2f}s, replay {ev.replay_s:.2f}s), "
                  f"0 tokens lost")
    finally:
        shutil.rmtree(sdir, ignore_errors=True)


def _paged_smoke(cfg, params, args, *, tracer=None, metrics=None) -> None:
    """Paged-KV parity smoke: dense vs paged continuous batching."""
    import jax.numpy as jnp

    from ..models import init_cache
    from ..runtime.engine import make_dense_engine
    from ..runtime.kvcache import make_paged_engine

    B, ctx = args.batch, args.ctx
    gen = RequestGenerator(cfg.vocab, seed=7,
                           prompt_len=(args.prompt_len,
                                       args.prompt_len + 8),
                           max_new=args.new_tokens)
    reqs = gen.generate(2 * B)

    eng_d = make_dense_engine(params, cfg, B, ctx)
    t0 = clock()
    fin_d, _ = eng_d.run(init_cache(cfg, B, ctx, dtype=jnp.float32), reqs)
    t_dense = clock() - t0

    page_tokens = 8
    n_pages = 2 + B * (-(-ctx // page_tokens))
    eng_p, kv = make_paged_engine(params, cfg, B, ctx, n_pages=n_pages,
                                  page_tokens=page_tokens, tracer=tracer,
                                  metrics=metrics,
                                  prefill_chunk=args.prefill_chunk or None)
    t0 = clock()
    fin_p, _ = eng_p.run(kv.init_cache(), reqs)
    t_paged = clock() - t0
    st = kv.stats()
    kv.close()

    dense = {f.uid: f.tokens for f in fin_d}
    paged = {f.uid: f.tokens for f in fin_p}
    if dense != paged:
        bad = [u for u in dense if dense[u] != paged.get(u)]
        raise SystemExit(f"paged-kv parity FAILED for uids {bad}")
    mode = []
    if args.prefill_chunk:
        mode.append(f"chunked prefill ({args.prefill_chunk} tokens)")
    if cfg.kv_dtype == "int8":
        mode.append("int8 KV pages")
    if mode:
        print(f"paged-kv mode: {', '.join(mode)}")
    print(f"paged decode ({len(reqs)} reqs through {B} slots, "
          f"{page_tokens}-token pages): tokens byte-identical to dense; "
          f"{t_paged:.2f}s vs dense {t_dense:.2f}s; KV high-water "
          f"{st.highwater_bytes / 1e6:.2f} MB vs dense envelope "
          f"{st.dense_bytes(B, ctx) / 1e6:.2f} MB "
          f"({st.highwater_bytes / st.dense_bytes(B, ctx):.2f}x); "
          f"prefix hits {st.prefix_hits}, CoW {st.cow_copies}, "
          f"evictions {st.evictions}")

    if args.device_budget > 0 or args.host_budget > 0 \
            or args.park_idle_s is not None:
        _tiered_smoke(cfg, params, args, dense)


def _tiered_smoke(cfg, params, args, dense) -> None:
    """Budgeted/parked paged decode: same tokens, bounded residency."""
    import shutil
    import tempfile

    import jax.numpy as jnp

    from ..runtime.kvcache import make_paged_engine
    from ..runtime.memory import MemoryBudget, TierManager

    B, ctx = args.batch, args.ctx
    budget = MemoryBudget.from_mb(
        device=args.device_budget if args.device_budget > 0 else None,
        host=args.host_budget if args.host_budget > 0 else None)
    memory = TierManager(budget)
    gen = RequestGenerator(cfg.vocab, seed=7,
                           prompt_len=(args.prompt_len,
                                       args.prompt_len + 8),
                           max_new=args.new_tokens)
    reqs = gen.generate(2 * B)
    page_tokens = 8
    n_pages = None if budget.device is not None \
        else 2 + B * (-(-ctx // page_tokens))
    ddir = tempfile.mkdtemp(prefix="kvdisk_")
    try:
        eng, kv = make_paged_engine(
            params, cfg, B, ctx, n_pages=n_pages,
            page_tokens=page_tokens, memory=memory, evict_policy="cost",
            disk_dir=ddir, park_idle_s=args.park_idle_s)
        fin, _ = eng.run(kv.init_cache(), reqs)
        tiered = {f.uid: f.tokens for f in fin}
        shed = {r.uid for r in eng.rejected}
        bad = [u for u in tiered if dense.get(u) != tiered[u]]
        if bad:
            raise SystemExit(f"tiered paged-kv parity FAILED for {bad}")
        stats = memory.stats()
        memory.audit()
        for tier in ("device", "host"):
            s = stats[tier]
            if s.capacity is not None and s.peak > s.capacity:
                raise SystemExit(f"tiered: {tier} high-water "
                                 f"{s.peak} > budget {s.capacity}")
        print(f"tiered paged decode: {len(tiered)} reqs byte-identical "
              f"({len(shed)} shed by budget); device peak "
              f"{stats['device'].peak / 1e6:.2f} MB / "
              f"{'∞' if budget.device is None else f'{budget.device / 1e6:.0f} MB'}, "
              f"host peak {stats['host'].peak / 1e6:.2f} MB, disk peak "
              f"{stats['disk'].peak / 1e6:.2f} MB; refusals "
              f"{stats['host'].refusals}")

        kv.close()

        if args.park_idle_s is not None:
            sid, half = "smoke-session", args.new_tokens
            prompt = reqs[0].prompt
            eng_f, kv_f = make_paged_engine(
                params, cfg, B, ctx,
                n_pages=2 + B * (-(-ctx // page_tokens)),
                page_tokens=page_tokens)
            full, _ = eng_f.run(kv_f.init_cache(),
                                [_SessReq(900, prompt, 2 * half)])
            kv_f.close()
            eng_s, kv_s = make_paged_engine(
                params, cfg, B, ctx,
                n_pages=2 + B * (-(-ctx // page_tokens)),
                page_tokens=page_tokens, disk_dir=ddir,
                park_idle_s=args.park_idle_s)
            cache = kv_s.init_cache()
            f1, _ = eng_s.run(cache, [_SessReq(901, prompt, half, sid)])
            if not kv_s.is_parked(sid):
                raise SystemExit("session never parked at finish")
            f2, _ = eng_s.run(cache, [_SessReq(902, prompt, half, sid)])
            got = f1[0].tokens + \
                [f for f in f2 if f.uid == 902][0].tokens
            ref = full[0].tokens
            if got != ref:
                raise SystemExit("park/restore parity FAILED: "
                                 f"{got} != {ref}")
            st = kv_s.stats()
            kv_s.close()
            print(f"session parking: split run byte-identical to one "
                  f"uninterrupted run ({len(ref)} tokens); parked "
                  f"{st.parked_sessions}, restored "
                  f"{st.restored_sessions}, disk written "
                  f"{st.disk_bytes_written / 1e6:.2f} MB")
    finally:
        shutil.rmtree(ddir, ignore_errors=True)


class _SessReq:
    def __init__(self, uid, prompt, max_new, session=None):
        self.uid = uid
        self.prompt = prompt
        self.max_new_tokens = max_new
        self.session = session


def _stream_smoke(cfg, params, prompts, args, *, ring_ctx=None,
                  tracer=None) -> None:
    """Weight-streaming decode: layer store + prefetcher (+ streamed ring)."""
    import shutil
    import tempfile

    import jax as _jax

    from ..models import decode_step_layerwise
    from ..runtime.paramstore import ParamStore, save_param_store
    from ..runtime.streaming import (StreamingParamSource,
                                     StreamingRingDriver)

    B, W = prompts.shape[0], args.stream_window
    tp = ring_ctx[2] if ring_ctx is not None else args.tp
    store_params = params
    if args.store_quant == "q4":
        # TP-aware group picking so ring window banks shard cleanly; the
        # layer-wise path dequantizes at use either way
        store_params, skipped = RS.quantize_ring_params(
            dict(params), cfg, tp=tp)
        if skipped:
            print(f"store-quant q4: {len(skipped)} leaves left bf16: "
                  f"{', '.join(skipped)}")
    sdir = tempfile.mkdtemp(prefix="paramstore_")
    try:
        save_param_store(store_params, cfg, sdir)
        probe = ParamStore(sdir)
        total = probe.layer_nbytes * cfg.n_layers
        if args.store_quant != "none":
            raw = sum(a.nbytes for a in
                      _jax.tree.leaves(params["blocks"])) // cfg.n_layers
            print(f"store: {probe.quant_format} manifest v{probe.version}, "
                  f"{probe.layer_nbytes / 1e6:.2f} MB/layer packed vs "
                  f"{raw / 1e6:.2f} MB/layer unquantized "
                  f"({probe.layer_nbytes / raw:.2f}x)")
        probe.close()

        from ..runtime.telemetry import NULL_TRACER
        tracer = tracer or NULL_TRACER
        with StreamingParamSource(ParamStore(sdir), window=W,
                                  policy=_io_policy(args),
                                  tracer=tracer) as src:
            c_s = init_cache(cfg, B, args.ctx, dtype=jnp.float32)
            lg, c_s = prefill(params, cfg, prompts, c_s)
            tok = jnp.argmax(lg[:, -1], -1)[:, None]
            t0 = clock()
            for t in range(args.new_tokens):
                with tracer.token_step(t, track="decode",
                                       name="stream_token"):
                    with tracer.phase("compute"):
                        lg, c_s = decode_step_layerwise(src, cfg, c_s,
                                                        tok)
                        tok = jnp.argmax(lg[:, 0], -1)[:, None]
                        tok = _jax.block_until_ready(tok)
                _metrics_tick(tracer, args, t)
            dt = clock() - t0
            st = src.stats()
        label = "" if args.store_quant == "none" \
            else f", store={args.store_quant}"
        print(f"streamed decode (window={W}/{cfg.n_layers} layers{label}): "
              f"{args.new_tokens} tokens × {B} seqs in {dt:.2f}s -> "
              f"{dt / args.new_tokens * 1e3:.1f} ms/token/batch; "
              f"peak resident {st.peak_resident_bytes / 1e6:.1f} MB of "
              f"{total / 1e6:.1f} MB weights; prefetch stall "
              f"{st.stall_s * 1e3:.0f} ms")

        if ring_ctx is not None and "pod" not in ring_ctx[0].axis_names:
            mesh, stages, tp = ring_ctx
            plan = RS.RingPlan.make(cfg, stages, k=args.ring_k)
            pr = RS.pad_vocab(dict(params), cfg, tp)
            head = {k: v for k, v in pr.items() if k != "blocks"}
            c_r = init_cache(cfg, B, args.ctx, dtype=jnp.float32)
            c_r["layers"] = RS.pad_and_permute(c_r["layers"], cfg, stages,
                                               plan.k)
            drv = StreamingRingDriver(
                cfg, mesh, plan, ParamStore(sdir), head_params=head,
                cache_like=c_r,
                prefetch_depth=max(1, W // max(plan.w, 1)),
                policy=_io_policy(args), tracer=tracer)
            ln = c_r["len"]
            tok = jnp.zeros((B, 1), jnp.int32)
            t0 = clock()
            for _ in range(args.new_tokens):
                logits, c_r = drv.step(tok, ln, c_r)
                ln = ln + 1
                tok = jnp.argmax(logits[:, 0, :cfg.vocab], -1)[:, None]
            dt = clock() - t0
            rst = drv.stats()
            drv.close()
            print(f"streamed ring decode (k={plan.k}, w={plan.w}, "
                  f"M={stages}): {args.new_tokens} tokens in {dt:.2f}s -> "
                  f"{dt / args.new_tokens * 1e3:.1f} ms/token/batch; "
                  f"peak staged {rst.peak_resident_bytes / 1e6:.1f} MB")
    finally:
        shutil.rmtree(sdir, ignore_errors=True)


if __name__ == "__main__":
    raise SystemExit(main())
