"""Continuous-batching serving engine.

The paper serves a single stream; a production framework multiplexes many
requests into the fixed-width decode batch the ring step compiles for.
This engine implements slot-based continuous batching over any
``(prefill_fn, decode_fn)`` pair:

  * fixed B decode slots (the compiled ring batch width);
  * arriving requests are prefilled (padded batch of 1..B) and their KV
    written into free slots; finished sequences free their slot
    immediately — no head-of-line blocking on long generations;
  * per-slot position counters feed the ring's ``ln`` vector; inactive
    slots are masked out of sampling.

The engine is deliberately runtime-agnostic: tests drive it with the
pure single-device model functions; ``launch/serve.py`` can drive it
with the jitted ring step.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .telemetry import NULL_TRACER, clock, compile_log


@dataclasses.dataclass
class SlotState:
    uid: Optional[int] = None        # request id (None = free)
    remaining: int = 0               # tokens still to generate
    generated: Optional[List[int]] = None
    proposed: int = 0                # draft tokens proposed (speculative)
    accepted: int = 0                # draft tokens accepted (speculative)
    session: Optional[str] = None    # park the slot's KV under this key


@dataclasses.dataclass
class FinishedRequest:
    uid: int
    tokens: List[int]
    proposed: int = 0                # speculative bookkeeping (0 = vanilla)
    accepted: int = 0

    @property
    def acceptance_rate(self) -> float:
        return self.accepted / max(self.proposed, 1)


@dataclasses.dataclass
class RejectedRequest:
    """A request the engine shed instead of admitting (graceful
    degradation): the pool can never hold it, or its admit starved past
    the deferral TTL. ``reason`` is the operator-facing explanation;
    ``code`` is the machine-facing classification (``shed_capacity`` —
    even an empty pool could never hold it; ``deferred_ttl_expired`` —
    admission starved past the deferral TTL) so load benchmarks can gate
    "zero OOM" without conflating admission control with failures."""

    uid: int
    reason: str
    code: str = "shed_capacity"


class ContinuousBatcher:
    """Slot-multiplexed decode over a fixed-width batch.

    prefill_one(prompt (1,S) int32) -> (first_token int, slot_cache)
        runs the prompt and returns per-layer KV for ONE sequence.
    write_slot(cache, slot_cache, slot_idx, length) -> cache
        installs a prefilled sequence into batch slot ``slot_idx``.
    decode(cache, tokens (B,1)) -> (logits (B,1,V), cache)

    ``spec``: optional ``runtime.speculative.SpeculativeDecoder``. When
    set, each step runs one draft/verify cycle instead of one decode —
    every occupied slot advances by 1..gamma+1 tokens per step while the
    emitted streams stay byte-identical to vanilla greedy decode. The
    decoder owns the draft-side cache; per-slot acceptance counters land
    on ``SlotState``/``FinishedRequest``.

    ``source``: optional ``runtime.paramstore.ParamSource`` the decode
    callables pull weights from (``streaming.make_streaming_engine``
    wires this). The engine itself stays weight-agnostic; holding the
    source lets callers reach prefetch statistics
    (``engine.streaming_stats()``) and guarantees its lifetime spans the
    serving loop.

    ``ctx``: the dense cache's ``max_len``. When set, ``admit`` rejects a
    request whose ``len(prompt) + max_new`` cannot fit — the dense cache
    would otherwise silently clip into its clamped last slot. Leave it
    None only for rolling-SWA caches, whose capacity is a window, not a
    limit.

    ``kv``: optional ``runtime.kvcache.PagedKVCache``. When set, the
    threaded cache is the paged pytree and ``decode`` must be the paged
    step: ``admit`` reserves pages (prefix-sharing identical prompt
    prefixes) before the prefill and scatters the result in; every step
    grows/copy-on-writes the write range first; ``_finish`` returns the
    slot's pages to the pool (hashed prompt pages fall into the prefix
    cache). Admission is alloc-on-demand — the only rejections are a
    request larger than the slot's block table and pool exhaustion.
    """

    def __init__(self, batch: int, prefill_one: Callable,
                 write_slot: Callable, decode: Callable,
                 *, eos_id: Optional[int] = None, spec=None, source=None,
                 ctx: Optional[int] = None, kv=None, tracer=None,
                 metrics=None, prefill_chunk: Optional[int] = None,
                 chunk_step: Optional[Callable] = None):
        self.B = batch
        self.prefill_one = prefill_one
        self.write_slot = write_slot
        self.decode = decode
        self.eos_id = eos_id
        self.spec = spec
        self.source = source
        self.ctx = ctx
        self.kv = kv
        #: chunked admission (paged only): process prompts in chunks of
        #: this many tokens via ``chunk_step(view, tokens, write)`` —
        #: KV written straight into the slot's pages, one decode step
        #: for the active slots interleaved between chunks
        self.prefill_chunk = prefill_chunk
        self.chunk_step = chunk_step
        if prefill_chunk is not None and (kv is None
                                          or chunk_step is None):
            raise ValueError(
                "prefill_chunk requires a paged cache (kv) and a "
                "chunk_step callable")
        self.tracer = tracer or NULL_TRACER
        compile_log()                    # set-up is logged from here on
        self.metrics = metrics
        self._tracker = None
        if metrics is not None:
            from .metrics import RequestTracker
            self._tracker = RequestTracker(metrics)
            metrics.add_source("engine", self.sample_gauges)
        self.slots = [SlotState() for _ in range(batch)]
        self.finished: List[FinishedRequest] = []
        self.rejected: List[RejectedRequest] = []
        self._step_idx = 0
        self._queued_n = 0               # pending requests (gauge)
        self._deferred_n = 0             # admits deferred on pool pressure
        self._spec_proposed = 0
        self._spec_accepted = 0

    def streaming_stats(self):
        """Prefetch statistics of the attached streaming source (or None)."""
        if self.source is not None and hasattr(self.source, "stats"):
            return self.source.stats()
        return None

    def sample_gauges(self) -> Dict[str, float]:
        """Gauge sample for ``MetricsRegistry.add_source``: batcher slot
        occupancy, BlockPool pages + prefix-hit rate, TierManager
        used/peak bytes, speculative acceptance, and I/O retry counts —
        cheap field reads only (no stats() object construction)."""
        g: Dict[str, float] = {
            "slots/active": float(len(self.active())),
            "slots/free": float(len(self.free_slots())),
            "queue/pending": float(self._queued_n),
            "queue/deferred": float(self._deferred_n),
        }
        if self.spec is not None:
            g["spec/acceptance_rate"] = (
                self._spec_accepted / max(self._spec_proposed, 1))
        kv = self.kv
        if kv is not None:
            pool = kv.pool
            g["kv/pages_active"] = float(pool.n_active)
            g["kv/pages_free"] = float(pool.n_free)
            g["kv/pages_cached"] = float(pool.n_cached)
            looks = kv.prefix_hits + pool.alloc_count
            g["kv/prefix_hit_rate"] = kv.prefix_hits / max(looks, 1)
            offl = getattr(kv, "offloader", None)
            if offl is not None and hasattr(offl, "health"):
                g["io/kv_retries"] = float(offl.health.retries)
            mem = getattr(kv, "memory", None)
            if mem is not None:
                for tier, st in mem.stats().items():
                    g[f"mem/{tier}/used_bytes"] = float(st.used)
                    g[f"mem/{tier}/peak_bytes"] = float(st.peak)
        src = self.source
        if src is not None and hasattr(src, "health"):
            g["io/stream_retries"] = float(src.health.retries)
        return g

    # ------------------------------------------------------------------ #

    def free_slots(self) -> List[int]:
        return [i for i, s in enumerate(self.slots) if s.uid is None]

    def active(self) -> List[int]:
        return [i for i, s in enumerate(self.slots) if s.uid is not None]

    def admit(self, cache, tokens: jnp.ndarray, uid: int,
              prompt: np.ndarray, max_new: int,
              session: Optional[str] = None):
        """Prefill ``prompt`` and place it in a free slot.

        Dense caches validate ``len(prompt) + max_new`` against ``ctx``
        up front (a clear error instead of a silent clip); the paged path
        allocates on demand and rejects only a request that exceeds the
        slot's block table or exhausts the pool. Speculative engines add
        ``gamma`` headroom on the paged path — a verify pass transiently
        writes up to gamma positions past the budget before rollback.

        ``session`` names a multi-turn conversation on a parking-enabled
        paged cache: at finish the slot's KV parks to host/disk under
        this key instead of being discarded, and a later admit with the
        same key restores it byte-identically and continues decoding —
        the prompt is ignored on restore (the parked state already
        contains it) and the first decode step resumes from the parked
        resume token, so the concatenated token stream is exactly what
        one uninterrupted request would have produced.

        Traced as span ``batcher.admit`` (args ``uid``,
        ``prompt_tokens``, ``prefix_hit_tokens``).
        """
        with self.tracer.span("batcher.admit", cat="sched", track="decode",
                              uid=uid, prompt_tokens=len(prompt)) as sp:
            if session is not None and self.spec is not None:
                raise ValueError(
                    "session parking and speculative decoding cannot be "
                    "combined: the draft cache is not parked, so a restored "
                    "slot would verify against a cold draft")
            free = self.free_slots()
            if not free:
                raise RuntimeError("no free slots")
            slot = free[0]
            tr = self._tracker
            if tr is not None:
                tr.submit(uid, prompt_len=len(prompt))  # no-op if seen
            t_admit = clock() if tr is not None else 0.0
            if self.kv is not None and session is not None \
                    and self.kv.is_parked(session):
                cache, meta, length = self.kv.restore_session(
                    cache, slot, session, max_new=max_new)
                resume = int(meta["resume_token"])
                tokens = tokens.at[slot, 0].set(resume)
                # the resume token's KV is written by the first decode step,
                # exactly as the last generated token's would have been — so
                # remaining counts the full max_new and generated starts
                # empty (the token was already emitted last turn).
                self.slots[slot] = SlotState(uid=uid, remaining=max_new,
                                             generated=[], session=session)
                if tr is not None:
                    tr.admitted(uid, t=t_admit, restored=True)
                    tr.prefill_done(uid, clock() - t_admit)
                return cache, tokens
            if self.kv is not None and self.prefill_chunk is not None:
                plan = self.kv.plan_admit(
                    cache, slot, [int(t) for t in np.asarray(prompt)],
                    max_new + (self.spec.gamma if self.spec else 0),
                    register=False)
                self._note_prefix_hits(sp, plan)
                try:
                    cache, tokens, first_tok = self._chunked_prefill(
                        cache, tokens, slot, np.asarray(prompt), uid)
                except BaseException:
                    # a failed chunk must not leak the planned pages
                    self.kv.abort_admit(slot)
                    raise
            elif self.kv is not None:
                margin = self.spec.gamma if self.spec is not None else 0
                plan = self.kv.plan_admit(cache, slot,
                                          [int(t) for t in np.asarray(prompt)],
                                          max_new + margin)
                self._note_prefix_hits(sp, plan)
                try:
                    first_tok, slot_cache = self.prefill_one(
                        jnp.asarray(prompt)[None, :])
                    cache = self.kv.install(cache, slot, slot_cache["layers"],
                                            len(prompt))
                except BaseException:
                    # a failed prefill must not leak the planned pages
                    self.kv.abort_admit(slot)
                    raise
            else:
                if self.ctx is not None and len(prompt) + max_new > self.ctx:
                    raise ValueError(
                        f"request {uid}: prompt ({len(prompt)}) + max_new "
                        f"({max_new}) exceeds the cache context "
                        f"({self.ctx}); the preallocated cache would "
                        f"silently clip — raise ctx or trim the request")
                first_tok, slot_cache = self.prefill_one(
                    jnp.asarray(prompt)[None, :])
                cache = self.write_slot(cache, slot_cache, slot,
                                        len(prompt))
            if self.spec is not None:
                self.spec.admit(jnp.asarray(prompt)[None, :], slot,
                                len(prompt))
            tokens = tokens.at[slot, 0].set(first_tok)
            self.slots[slot] = SlotState(uid=uid, remaining=max_new - 1,
                                         generated=[int(first_tok)],
                                         session=session)
            if tr is not None:
                tr.admitted(uid, t=t_admit)
                tr.prefill_done(uid, clock() - t_admit)
                tr.token(uid)                # prefill emits the first token
            return cache, tokens

    def _note_prefix_hits(self, sp, plan) -> None:
        if sp is not None:
            sp["prefix_hit_tokens"] = (plan["shared"] + plan["fetched"]) \
                * self.kv.page_tokens

    def _chunked_prefill(self, cache, tokens, slot: int,
                         prompt: np.ndarray, uid: int):
        """Admit one prompt in page-sized chunks computed straight into
        the slot's planned pages, interleaving one decode step for the
        active slots between chunks — the long-admit TPOT spike becomes
        a bounded per-chunk stall. The leading prefix-shared pages are
        skipped entirely (their KV is already resident); a fully shared
        prompt re-derives its last-position logits read-only. Returns
        ``(cache, tokens, first_token)``.
        """
        kv = self.kv
        S = len(prompt)
        cache, skip = kv.begin_chunked_admit(cache, slot, S)
        table1 = jnp.asarray(kv.chunk_table(slot))
        o, write = skip, True
        if skip >= S:
            # whole prompt prefix-shared: nothing to write, but the
            # first token still needs the final position's logits
            o, write = S - 1, False
        logits = None
        n_chunks = 0
        while o < S:
            c = min(self.prefill_chunk, S - o)
            view = {"pages": cache["pages"], "block_table": table1,
                    "len": jnp.full((1,), o, jnp.int32)}
            t0 = clock()
            with self.tracer.span("batcher.chunk", cat="compute",
                                  track="decode", uid=uid, chunk=n_chunks,
                                  tokens=c):
                logits, view = self.chunk_step(
                    view, jnp.asarray(prompt[o:o + c])[None, :], write)
                logits.block_until_ready()
            cache = {**cache, "pages": view["pages"]}
            n_chunks += 1
            o += c
            if o < S and self.active():
                # active decode slots stalled for exactly one chunk;
                # give them a step before the next one
                if self._tracker is not None:
                    self._tracker.interleave_stall(clock() - t0)
                cache, tokens = self.step(cache, tokens)
        first_tok = int(jnp.argmax(logits[0, -1]))
        cache = kv.finish_chunked_admit(cache, slot, S)
        if self._tracker is not None:
            self._tracker.prefill_chunks(uid, n_chunks)
        return cache, tokens, first_tok

    def _finish(self, i: int, cache):
        st = self.slots[i]
        self.finished.append(
            FinishedRequest(uid=st.uid, tokens=st.generated,
                            proposed=st.proposed, accepted=st.accepted))
        if self._tracker is not None:
            self._tracker.finished(st.uid)
        self.slots[i] = SlotState()                      # free immediately
        if self.kv is not None:
            if st.session is not None and self.kv.parking and st.generated:
                from .iopolicy import BudgetExceeded
                try:
                    self.kv.park_session(
                        cache, i, st.session,
                        meta={"resume_token": int(st.generated[-1])})
                    return cache
                except BudgetExceeded:
                    # no tier can hold the parked bytes — degrade to a
                    # normal finish; the next turn re-prefills from
                    # scratch instead of failing the current one.
                    self.tracer.instant(f"park-refused[{st.session}]",
                                        cat="sched", track="decode")
            self.kv.release_slot(i)
        return cache

    def step(self, cache, tokens: jnp.ndarray):
        """One decode step for every occupied slot.

        Each step is one token-step scope on the tracer: the decode +
        argmax (host-synced) charge to ``compute``, stalls inside the
        decode callable (prefetcher waits, KV fetches) attribute to
        their own components, and the remainder books as scheduler
        idle. Token-step records partition measured TPOT. The step is
        span ``batcher.step`` (arg ``index``); inside it
        ``kv.begin_step`` and ``batcher.readback``, the host waiting on
        the device and copying the tokens back.
        """
        t0 = clock() if self._tracker is not None else 0.0
        with self.tracer.token_step(self._step_idx, track="decode",
                                    name="batcher.step"):
            self._step_idx += 1
            if self.spec is not None:
                out = self._spec_step(cache, tokens)
            else:
                out = self._vanilla_step(cache, tokens)
        if self._tracker is not None:
            self._tracker.step_done(clock() - t0)
        return out

    def _vanilla_step(self, cache, tokens: jnp.ndarray):
        if self.kv is not None:
            cache = self.kv.begin_step(cache, self.active(), 1)
        with self.tracer.phase("compute", track="decode"):
            logits, cache = self.decode(cache, tokens)
            nxt = jnp.argmax(logits[:, 0], axis=-1)      # greedy
            with self.tracer.span("batcher.readback", cat="sync",
                                  track="decode"):
                nxt_host = np.asarray(nxt)               # force the sync
        tokens = nxt[:, None].astype(tokens.dtype)
        for i in self.active():
            st = self.slots[i]
            tok = int(nxt_host[i])
            if self.kv is not None:
                self.kv.advance(i)
            st.generated.append(tok)
            if self._tracker is not None:
                self._tracker.token(st.uid)
            st.remaining -= 1
            if st.remaining <= 0 or (self.eos_id is not None
                                     and tok == self.eos_id):
                cache = self._finish(i, cache)
        return cache, tokens

    def _spec_step(self, cache, tokens: jnp.ndarray):
        """One draft/verify cycle: every occupied slot advances by up to
        gamma+1 tokens. Tokens emitted past a slot's budget (or past EOS)
        are dropped — the slot frees immediately, exactly like vanilla."""
        len0 = {}
        if self.kv is not None:
            # the verify pass writes gamma+1 positions before rollback
            cache = self.kv.begin_step(cache, self.active(),
                                       self.spec.gamma + 1)
            len0 = {i: self.kv.length(i) for i in self.active()}
        with self.tracer.phase("compute", track="decode"):
            cache, res = self.spec.cycle(cache, tokens,
                                         active=self.active())
            with self.tracer.span("batcher.readback", cat="sync",
                                  track="decode"):
                n_emit_host = np.asarray(res.n_emit)     # force the sync
        tokens = res.next_tokens.astype(tokens.dtype)
        accepted = proposed = 0
        for i in self.active():
            st = self.slots[i]
            n = int(n_emit_host[i])
            if self.kv is not None:
                # pages past the accepted length return to the pool — the
                # allocator half of the rollback (len was already reset)
                self.kv.trim_to(i, len0[i] + n)
            # counters estimate draft/target *agreement* (the acceptance
            # probability behind E[tokens/cycle]), so verified-but-
            # truncated drafts still count — truncation doesn't bias the
            # agreement sample.
            st.proposed += self.spec.gamma
            st.accepted += n - 1
            proposed += self.spec.gamma
            accepted += n - 1
            for tok in res.emitted[i, :n]:
                tok = int(tok)
                st.generated.append(tok)
                if self._tracker is not None:
                    self._tracker.token(st.uid)
                st.remaining -= 1
                if st.remaining <= 0 or (self.eos_id is not None
                                         and tok == self.eos_id):
                    cache = self._finish(i, cache)
                    break
        if proposed:
            self._spec_proposed += proposed
            self._spec_accepted += accepted
            self.tracer.counter("spec/proposed", proposed, track="decode")
            self.tracer.counter("spec/accepted", accepted, track="decode")
        return cache, tokens

    def run(self, cache, requests, *, max_steps: int = 10_000,
            admit_patience: int = 256, respect_arrivals: bool = False):
        """Drive a request list (sorted by arrival) to completion.

        On the paged path a transiently exhausted pool (pages held by
        slots still decoding) defers the admit until finishes free pages;
        it only propagates when no active slot could ever free any.
        Deferral is bounded: a request that cannot fit an *empty* pool
        (``kv.can_ever_admit``) or whose admit has been refused for
        ``admit_patience`` consecutive steps is shed onto
        ``self.rejected`` with a clear "pool too small for request"
        error instead of starving the run.

        ``respect_arrivals=True`` replays each request's ``arrival_s``
        offset against the wall clock (load benchmarks): a request is
        invisible to admission until its arrival passes, its metrics
        ``submit`` timestamp is its arrival instant (so TTFT includes
        real queue wait), and an idle engine sleeps until the next
        arrival instead of burning decode steps.
        """
        import time as _time

        from .kvcache import PoolExhausted

        tokens = jnp.zeros((self.B, 1), jnp.int32)
        pending = list(requests)
        if respect_arrivals:
            pending.sort(key=lambda r: getattr(r, "arrival_s", 0.0))
        deferrals: Dict[int, int] = {}
        steps = 0
        t_start = clock()

        def arrived(req):
            return (not respect_arrivals
                    or getattr(req, "arrival_s", 0.0)
                    <= clock() - t_start)

        while (pending or self.active()) and steps < max_steps:
            if self._tracker is not None:
                for req in pending:
                    if not arrived(req):
                        break
                    self._tracker.submit(
                        req.uid,
                        t=t_start + getattr(req, "arrival_s", 0.0),
                        prompt_len=len(req.prompt))
            while pending and self.free_slots() and arrived(pending[0]):
                req = pending.pop(0)
                try:
                    cache, tokens = self.admit(
                        cache, tokens, req.uid, req.prompt,
                        req.max_new_tokens,
                        session=getattr(req, "session", None))
                    deferrals.pop(req.uid, None)
                except PoolExhausted as e:
                    if not self.active():
                        raise              # nothing will ever free pages
                    margin = self.spec.gamma if self.spec is not None \
                        else 0
                    if self.kv is not None and not self.kv.can_ever_admit(
                            len(req.prompt),
                            req.max_new_tokens + margin):
                        # deferring cannot help: even an empty pool is
                        # too small — shed now with the classified reason
                        self._shed(req.uid, "shed_capacity",
                                   f"pool too small for request "
                                   f"{req.uid}: {e}")
                        self.tracer.instant(f"reject[{req.uid}]",
                                            cat="sched", track="decode",
                                            uid=req.uid,
                                            reason="pool too small")
                        continue
                    n = deferrals.get(req.uid, 0) + 1
                    if n > admit_patience:
                        deferrals.pop(req.uid, None)
                        self._shed(req.uid, "deferred_ttl_expired",
                                   f"pool too small for request "
                                   f"{req.uid}: admission deferred "
                                   f"{n - 1} consecutive steps without "
                                   f"a slot freeing enough pages ({e})")
                        self.tracer.instant(f"reject[{req.uid}]",
                                            cat="sched", track="decode",
                                            uid=req.uid,
                                            reason="admit starved")
                        continue
                    deferrals[req.uid] = n
                    pending.insert(0, req)
                    break
            self._queued_n = len(pending)
            self._deferred_n = len(deferrals)
            if self.active():
                cache, tokens = self.step(cache, tokens)
            elif respect_arrivals and pending:
                # idle until the next arrival — a waiting engine burns
                # neither decode steps nor the step budget
                next_t = t_start + getattr(pending[0], "arrival_s", 0.0)
                with self.tracer.span("batcher.wait_arrival", cat="sched",
                                      track="decode"):
                    _time.sleep(min(max(next_t - clock(), 0.0), 0.005))
                if self.kv is not None and self.kv.parking:
                    self.kv.sweep_parked()
                continue
            if self.kv is not None and self.kv.parking:
                self.kv.sweep_parked()
            steps += 1
        self._queued_n = 0
        self._deferred_n = 0
        return self.finished, steps

    def _shed(self, uid: int, code: str, reason: str) -> None:
        self.rejected.append(
            RejectedRequest(uid=uid, reason=reason, code=code))
        if self._tracker is not None:
            self._tracker.rejected(uid, code, reason)


def make_dense_engine(params, cfg, batch: int, ctx: int, *,
                      eos_id: Optional[int] = None, spec=None,
                      cache_dtype=jnp.float32,
                      tracer=None, metrics=None) -> ContinuousBatcher:
    """Reference dense-cache engine wiring (prefill-one / slot-write /
    decode over ``models.decode_step``) — the single source of the
    slot-write convention, shared by the serving driver, benchmarks and
    tests. Drive it with ``eng.run(init_cache(cfg, batch, ctx), reqs)``.
    """
    from ..models import model as M

    def prefill_one(prompt):
        c1 = M.init_cache(cfg, 1, ctx, dtype=cache_dtype)
        logits, c1 = M.prefill(params, cfg, prompt, c1)
        return int(jnp.argmax(logits[0, -1])), c1

    def write_slot(cache, slot_cache, slot, length):
        def wr(dst, src):
            if dst.ndim >= 2 and dst.shape[1] == batch \
                    and src.shape[1] == 1:
                return dst.at[:, slot].set(src[:, 0])
            return dst
        new = jax.tree.map(wr, cache, slot_cache)
        new["len"] = cache["len"].at[slot].set(slot_cache["len"][0])
        return new

    def decode(cache, tokens):
        return M.decode_step(params, cfg, cache, tokens)

    return ContinuousBatcher(batch, prefill_one, write_slot, decode,
                             eos_id=eos_id, spec=spec, ctx=ctx,
                             tracer=tracer, metrics=metrics)
