"""Drive one open-loop cell through the paged serving engine.

Set-up makes the weights from the seed, warms every program the cell's
traffic will run on an engine of its own (then frees it), and builds the
engine the window uses. The window replays the cell's requests against
the wall clock through ``ContinuousBatcher.run(respect_arrivals=True)``
as built by ``kvcache.make_paged_engine``. The harness stamps each token
as the host receives it, by wrapping the engine's ``step``, ``admit``
and ``chunk_step`` on the instance. Once the window has closed and every
request due in it has its first token, the run stops; the program's
state is freed and the reference recomputes a seeded sample of the
requests that finished.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import glob
import shutil
import tempfile
import time
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import accounting as acc
from . import reference, spec, traffic, work
from .trace_reduce import breakdown, load as load_trace, total
from .weights import make_weights

clock = time.perf_counter            # the serving program's clock too
#: host spans the traced run writes, which name the idle gaps
SPANS = ("engine.step", "engine.admit", "engine.chunk_step")

class _Compiles:
    """Counts of the process's compiles (and persistent-cache hits), with
    the time of each; listening starts with the first ``prepare``."""

    def __init__(self):
        self.n, self.hits, self.at = 0, 0, []
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.at.append(clock())

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


_compiles: Optional[_Compiles] = None


def compiles() -> _Compiles:
    global _compiles
    if _compiles is None:
        _compiles = _Compiles()
    return _compiles


@dataclasses.dataclass
class StepRec:
    lengths: Tuple[int, ...]         # positions each active slot attends
    traced: bool


@dataclasses.dataclass
class ChunkRec:
    prefix: int                      # positions cached before the chunk
    size: int
    traced: bool


@dataclasses.dataclass
class Observation:
    """What a run saw; the per-layer readers take their numbers from it."""

    dm: object                       # the family's sizes
    peak: Optional[work.Peak]
    slots: int
    prefill_chunk: int
    t0: float                        # window start (the engine's own)
    t1: float
    due: Dict[int, float]            # uid -> due instant
    admit_start: Dict[int, float]    # uid -> its admit began
    failed: List[int]
    run_end: float
    steps: List[StepRec]
    chunks: List[ChunkRec]
    work_intervals: List[Tuple[float, float]]
    stamps: Dict[int, List[float]]   # uid -> times its tokens reached host
    prefix_hit_tokens: int
    prompt_tokens: int
    trace: object = None             # trace_reduce.Trace of the window
    trace_lo: float = 0.0
    trace_hi: float = 0.0

    def traced_steps(self) -> List[StepRec]:
        return [s for s in self.steps if s.traced]

    def traced_chunks(self) -> List[ChunkRec]:
        return [c for c in self.chunks if c.traced]

    @property
    def traced_work_s(self) -> float:
        return acc.busy_time(self.work_intervals, self.trace_lo,
                             self.trace_hi)

    def waits(self, since: Dict[int, float]) -> List[float]:
        """Per due request, ``since[uid]`` minus its due instant; failed
        requests rank above all (``accounting.ttft_values``)."""
        ok = {u: since[u] - self.due[u] for u in self.due
              if u in since and u not in self.failed}
        return acc.ttft_values(ok, [self.due[u] for u in self.failed],
                               self.run_end)


@dataclasses.dataclass
class Prepared:
    dm: object                       # the family's sizes
    cfg: object                      # the program's ModelConfig
    serving: dict
    ctx: int
    params: dict
    peak: Optional[work.Peak]
    log: dict


def _engine(prep: Prepared, metrics=None):
    from repro.runtime import kvcache

    s = prep.serving
    return kvcache.make_paged_engine(
        prep.params, prep.cfg, s["slots"], prep.ctx, n_pages=s["n_pages"],
        page_tokens=s["page_tokens"], cache_dtype=jnp.bfloat16,
        prefill_chunk=s["prefill_chunk"], offload=False, metrics=metrics)


def warm_requests(mix: dict, vocab: int, seed: int, chunk: int):
    """Requests whose admits and decode steps run every program shape the
    cell's traffic can: each chunk length, cold and prefix-shared."""
    r = traffic.rng(seed, 99)
    system = r.integers(3, vocab, mix["system_prompts"]["tokens"]
                        ).astype(np.int32)
    reqs = []
    for c in traffic.chunk_remainders(mix, chunk):
        for _ in range(2):               # cold, then a prefix hit
            body = r.integers(3, vocab, c).astype(np.int32)
            reqs.append(traffic.Request(
                uid=len(reqs), prompt=np.concatenate([system, body]),
                max_new_tokens=3, arrival_s=0.0, system=-1))
    return reqs


def prepare(config: dict, mix: dict, seed: int,
            device_kind: Optional[str], warm: bool = True) -> Prepared:
    """Weights from the seed, and (``warm``) every program compiled or
    loaded from the cache by an engine that is then freed. The sizes and
    the program's config come from the configuration's model family."""
    fam = spec.family(config["model_type"])
    dm = fam.dims(config)
    t_a = clock()
    prep = Prepared(dm=dm, cfg=fam.program_config(dm),
                    serving=config["serving"],
                    ctx=traffic.max_context(mix),
                    params=make_weights(dm, seed),
                    peak=work.peak_for(device_kind) if device_kind else None,
                    log={})
    prep.log["weights_s"] = clock() - t_a
    if warm:
        c = compiles()
        n0, hits0 = c.n, c.hits
        t_a = clock()
        eng, kv = _engine(prep)
        eng.run(kv.init_cache(), warm_requests(
            mix, dm.vocab, seed, prep.serving["prefill_chunk"]))
        kv.close()
        del eng, kv
        gc.collect()
        prep.log.update(warm_s=clock() - t_a, warm_compiles=c.n - n0,
                        warm_cache_hits=c.hits - hits0)
    return prep


class _WindowClosed(Exception):
    """Raised from a wrapper to end the run early (see ``serve``)."""


def serve(prep: Prepared, reqs, *, seconds: float, trace: bool,
          t_process: Optional[float] = None,
          fault: Optional[Callable] = None, drain: bool = False,
          give_up_s: float = float("inf")):
    """Replay ``reqs`` against the wall clock. Returns ``(obs, done,
    device, log)``: ``done`` maps each finished uid to its tokens.
    ``drain`` runs until every request has finished; ``give_up_s`` ends
    the run that long after the window even with requests unadmitted
    (they count as failed)."""
    from repro.runtime.metrics import MetricsRegistry

    registry = MetricsRegistry(request_log_size=len(reqs) + 16)
    eng, kv = _engine(prep, metrics=registry)
    # the run gets the only reference to the initial pool, so that it is
    # freed once the first step has replaced it
    pool = [jax.block_until_ready(kv.init_cache())]
    if fault is not None:
        fault(eng)

    stamps: Dict[int, List[float]] = {r.uid: [] for r in reqs}
    admit_start: Dict[int, float] = {}
    steps: List[StepRec] = []
    chunks: List[ChunkRec] = []
    work_iv: List[Tuple[float, float]] = []
    state = {"tracing": False, "t1": float("inf")}
    tmp = tempfile.mkdtemp(prefix="bench-trace-") if trace else None

    def annotate(name):
        return jax.profiler.TraceAnnotation(name) if state["tracing"] \
            else contextlib.nullcontext()

    def after(now: float) -> None:
        """Past the window, once every due request has its first token,
        end the run (requests still decoding are answered, and their
        later tokens fall outside the window)."""
        if now < state["t1"] or drain:
            return
        if len(admit_done) + len(eng.rejected) >= len(reqs) \
                or now >= state["t1"] + give_up_s:
            raise _WindowClosed

    admit_done: set = set()
    inner_step, inner_admit, inner_chunk = eng.step, eng.admit, \
        eng.chunk_step

    def step(cache, tokens):
        act = eng.active()
        before = {eng.slots[i].uid: len(eng.slots[i].generated) for i in act}
        lengths = tuple(kv.length(i) + 1 for i in act)
        n_fin = len(eng.finished)
        traced = state["tracing"]
        t_a = clock()
        with annotate("engine.step"):
            out = inner_step(cache, tokens)
        t_b = clock()
        steps.append(StepRec(lengths, traced))
        work_iv.append((t_a, t_b))
        for s in eng.slots:
            if s.uid in before:
                stamps[s.uid].extend([t_b] * (len(s.generated)
                                              - before.pop(s.uid)))
        for f in eng.finished[n_fin:]:
            stamps[f.uid].extend([t_b] * (len(f.tokens) - before.pop(f.uid)))
        after(t_b)
        return out

    def admit(cache, tokens, uid, prompt, max_new, session=None):
        t_a = clock()
        admit_start[uid] = t_a
        with annotate("engine.admit"):
            out = inner_admit(cache, tokens, uid, prompt, max_new,
                              session=session)
        t_b = clock()
        work_iv.append((t_a, t_b))
        stamps[uid].append(t_b)             # the prefill's first token
        admit_done.add(uid)
        after(t_b)
        return out

    def chunk_step(view, tokens, write=True):
        traced = state["tracing"]
        prefix = int(np.asarray(view["len"])[0]) if traced else -1
        with annotate("engine.chunk_step"):
            out = inner_chunk(view, tokens, write)
        chunks.append(ChunkRec(prefix, tokens.shape[1], traced))
        return out

    eng.step, eng.admit, eng.chunk_step = step, admit, chunk_step

    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(tmp, profiler_options=opts)
        state["tracing"] = True
    setup_s = None if t_process is None else clock() - t_process
    n_comp = compiles().n
    t_run = clock()
    state["t1"] = t_run + seconds
    try:
        eng.run(pool.pop(), reqs, respect_arrivals=True, max_steps=1 << 40)
    except _WindowClosed:
        pass
    run_end = clock()
    if state["tracing"]:
        # the trace covers the run: the window and the admits that
        # finish its due requests (writing it takes tens of seconds)
        jax.profiler.stop_trace()
        state["tracing"] = False

    # the engine's own window start: a request is submitted at it plus
    # its arrival offset (finished requests are logged, the rest live)
    live = getattr(getattr(eng, "_tracker", None), "_live", {})
    logged = [t.submit_t - reqs[t.uid].arrival_s
              for t in list(registry.request_log) + list(live.values())]
    t0 = min(logged, default=t_run)
    dev = jax.devices()[0]
    mem = dev.memory_stats() or {}
    done = {f.uid: f.tokens for f in eng.finished}
    failed = sorted({r.uid for r in eng.rejected}
                    | {u for u, ts in stamps.items() if not ts})
    obs = Observation(
        dm=prep.dm, peak=prep.peak, slots=prep.serving["slots"],
        prefill_chunk=prep.serving["prefill_chunk"], t0=t0,
        t1=t0 + seconds, due={r.uid: t0 + r.arrival_s for r in reqs},
        admit_start=admit_start, failed=failed, run_end=run_end,
        steps=steps, chunks=chunks, work_intervals=work_iv, stamps=stamps,
        prefix_hit_tokens=kv.prefix_hits * kv.page_tokens,
        prompt_tokens=sum(len(r.prompt) for r in reqs
                          if r.uid in admit_done))
    kv.close()
    del eng, kv
    gc.collect()

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": int(mem.get("peak_bytes_in_use", 0))}
    if trace:
        path = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)
        obs.trace = load_trace(path[0]) if path else None
        obs.trace_lo, obs.trace_hi = t_run, run_end
        shutil.rmtree(tmp, ignore_errors=True)
        if obs.trace is not None and obs.trace.devices:
            device["busy_s"] = total(obs.trace.devices[0].busy())
            device["window_s"] = obs.trace_hi - obs.trace_lo
    log = {"setup_s": setup_s, "requests": len(reqs), "steps": len(steps),
           "chunks": len(chunks), "finished": len(done),
           "compiles_in_window": sum(1 for t in compiles().at[n_comp:]
                                     if t <= obs.t1),
           "stop_after_window_s": run_end - obs.t1}
    return obs, done, device, log


def sample(done: Dict[int, list], n: int, seed: int) -> List[int]:
    """The finished request with the most served tokens, and n - 1 others
    drawn from the seed."""
    by_len = sorted(done, key=lambda u: (-len(done[u]), u))
    if not by_len:
        return []
    n = min(n, len(by_len))
    rest = traffic.rng(seed, 7).choice(by_len[1:], size=n - 1,
                                       replace=False)
    return by_len[:1] + [int(u) for u in rest]


def check(prep: Prepared, reqs, done: Dict[int, list], seed: int,
          n_sample: int, control: bool = False):
    """The reference over a seeded sample of finished requests: the gap
    of every served token, and with ``control`` the float8 control's
    (``reference.served_gaps``)."""
    chosen = sample(done, n_sample, seed)
    if not chosen:
        return np.zeros(0), (np.zeros(0) if control else None)
    samples = [(reqs[u].prompt, done[u]) for u in chosen]
    return reference.served_gaps(prep.params, prep.dm, samples, prep.ctx,
                                 control=control)


def run_cell(config: dict, mix: dict, params_cell: dict, *, seed: int,
             seconds: float, trace: bool, t_process: float,
             device_kind: Optional[str], fault: Optional[Callable] = None
             ) -> dict:
    """One run: the result's fields (``metrics`` holds the end-to-end
    ones), ``check`` (the numbers compared, with their limits), ``obs``
    for the per-layer readers and ``log`` for standard error."""
    prep = prepare(config, mix, seed, device_kind)
    reqs = traffic.GENERATORS[mix["kind"]](
        mix, rate=params_cell["rate_per_s"], seconds=seconds,
        vocab=prep.dm.vocab, seed=seed)
    obs, done, device, log = serve(prep, reqs, seconds=seconds, trace=trace,
                                   t_process=t_process, fault=fault)
    first = {u: ts[0] for u, ts in obs.stamps.items() if ts}
    log["ttft_s"] = [round(v, 4) for v in sorted(obs.waits(first))]
    t_ref = clock()
    gaps, _ = check(prep, reqs, done, seed, params_cell["sample"])
    log.update(prep.log, reference_s=clock() - t_ref,
               served_tokens_compared=len(gaps),
               gap_quantiles=gap_summary(gaps))
    stat = params_cell["gap_statistic"]
    gap = reference.statistic(gaps, stat)
    limit = params_cell["limits"]["served_logit_gap"]
    res = {
        "correct": bool(gap <= limit and len(gaps) and not obs.failed),
        "attempted": len(reqs), "failed": len(obs.failed),
        "metrics": end_to_end(obs, log["setup_s"]), "device": device,
        "check": {f"served_logit_gap_{stat}": {"value": gap,
                                               "limit": limit},
                  "requests_failed": {"value": len(obs.failed),
                                      "limit": 0}},
        "obs": obs, "log": log}
    if trace and obs.trace is not None and obs.trace.devices:
        ops = obs.trace.devices[0].ops
        lo, hi = (ops[0].start, ops[-1].end) if ops else (0.0, 0.0)
        res["breakdown"] = breakdown(obs.trace, lo, hi, SPANS)
    return res


def gap_summary(gaps) -> Dict[str, float]:
    return {s: reference.statistic(gaps, s)
            for s in ("p50", "p90", "p95", "p99", "max")}


def end_to_end(obs: Observation, setup_s: float) -> Dict[str, dict]:
    first = {u: ts[0] for u, ts in obs.stamps.items() if ts}
    itl = acc.inter_token_gaps(obs.stamps, obs.t0, obs.t1)
    n_tok = acc.tokens_in_window(obs.stamps, obs.t0, obs.t1)
    m = {"setup_s": (setup_s, "s"),
         "ttft_p90_s": (acc.quantile(obs.waits(first), 0.90), "s"),
         "itl_p50_ms": (_ms(acc.quantile(itl, 0.50)), "ms"),
         "itl_p95_ms": (_ms(acc.quantile(itl, 0.95)), "ms"),
         "output_tok_s": (n_tok / (obs.t1 - obs.t0), "tokens/s")}
    return {k: {"value": v, "unit": u} for k, (v, u) in m.items()
            if v is not None}


def _ms(x):
    return None if x is None else x * 1e3
