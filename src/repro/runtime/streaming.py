"""Async layer prefetcher + streamed decode drivers (paper §3.1).

The paper's pipelined-ring insight is that disk I/O for the *next* layer
window can hide behind compute for the current one — but only if
prefetch and release are disentangled: naive ``mmap`` offloading lets the
OS reclaim the pages being prefetched to satisfy the prefetch itself
("prefetch-release conflict"). This module implements the fix
explicitly:

  * a background thread reads layer ``k + w`` from the layer-sharded
    store (``runtime.paramstore``) into private host staging buffers
    while layer ``k`` computes — staging copies cannot be reclaimed by
    the kernel, so prefetch never self-evicts;
  * staged buffers are (optionally) ``jax.device_put`` ahead of use, so
    the host→device copy of window ``w+1`` overlaps compute on window
    ``w`` (double buffering);
  * release is explicit and strictly *behind* the compute front: once
    the front passes layer ``k``, its staging buffer is freed and the
    store drops the mmap pages (``MADV_DONTNEED``) — the resident set is
    bounded by the window size, never the model size.

Three consumers:

  * ``StreamingParamSource`` — plugs into the layer-wise model forward
    (``models.model.decode_step_layerwise`` etc.) and the
    ``ContinuousBatcher`` via ``make_streaming_engine``;
  * ``RingBankPrefetcher`` / ``StreamingRingDriver`` — drive the SPMD
    piped ring (``runtime.serve.build_ring_stream_step``) with per-step
    window banks, the multi-device version of the same pipeline;
  * the prefetch timeline (``PrefetchEvent``) feeds
    ``core.latency.streaming_crosscheck`` so the analytic disk terms are
    validated against measured reads.

Quantized (v2) stores flow through unchanged: ``store.layer(i)`` hands
back ``QuantizedTensor`` leaves whose packed/scale children are what the
staging copies, byte accounting and ``device_put`` traverse — so the
prefetch window, the resident-bytes bound and ``PrefetchStats`` all see
the ~4x-smaller packed footprint, and dequantization happens at use
(layer-wise model paths / ``serve.run_ring_window``), never in staging.
"""
from __future__ import annotations

import dataclasses
import logging
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .iopolicy import IOPolicy, StallTimeout, WorkerHealth
from .memory import TierManager
from .paramstore import ParamSource, ParamStore
from .telemetry import NULL_TRACER, clock

Params = Dict[str, Any]

log = logging.getLogger(__name__)


@dataclasses.dataclass(frozen=True)
class PrefetchEvent:
    """One background layer read (staging copy from the mmap store)."""

    layer: int
    t_start: float
    t_end: float
    nbytes: int

    @property
    def duration(self) -> float:
        return self.t_end - self.t_start

    @property
    def bps(self) -> float:
        return self.nbytes / max(self.duration, 1e-12)


@dataclasses.dataclass
class PrefetchStats:
    """Aggregate view of a prefetcher run (benchmarks + cross-checks)."""

    events: List[PrefetchEvent]
    peak_resident_bytes: int          # max staged parameter bytes
    total_bytes_read: int
    stall_s: float                    # compute blocked waiting on a layer
    layers_served: int
    releases: int
    retries: int = 0                  # transient I/O retries (IOPolicy)
    released_bytes: int = 0           # bytes the store returned to the OS
    budget_refusals: int = 0          # staging leases the budget refused

    @property
    def bytes_per_layer(self) -> float:
        """Measured streamed bytes per staged layer. For a quantized (v2)
        store this is the *packed* footprint — staging copies exactly the
        packed int4/int2 + scale sub-leaves, so it lands ~4x under the
        bf16 store's ``layer_nbytes`` (the benchmark's acceptance gate
        reads this, not manifest math)."""
        reads = [e for e in self.events if e.nbytes > 0]
        return (sum(e.nbytes for e in reads) / len(reads)) if reads else 0.0

    @property
    def median_layer_read_s(self) -> float:
        from ..core.latency import median_event_duration

        return median_event_duration(self.events)

    @property
    def measured_disk_bps(self) -> float:
        from ..core.latency import aggregate_bps

        return aggregate_bps(self.events)


class LayerPrefetcher:
    """Keep a cyclic window of ``window`` layers staged ahead of the front.

    ``get(i)`` blocks until layer ``i`` is staged, schedules reads through
    ``i + window - 1`` (mod L), and releases every staged layer behind the
    front (cyclic distance >= window). Access is expected to be the decode
    pattern — layers 0..L-1 in order, repeated per token — but any order
    is correct (out-of-window requests are staged on demand).

    ``window`` is a *scheduling lookahead*, not a capacity cap: every
    staged byte is leased from ``memory`` (a shared
    :class:`~runtime.memory.TierManager`, or a private unbounded one when
    omitted) — host bytes while staging, moved to the device tier after
    ``device_put`` — so one ``MemoryBudget`` bounds weights and KV
    together and a full tier throttles the worker (it blocks for a
    release) instead of overshooting.
    """

    def __init__(self, store: ParamStore, *, window: int = 4,
                 device_put: bool = True,
                 policy: Optional[IOPolicy] = None, tracer=None,
                 memory: Optional[TierManager] = None,
                 owner: str = "weights"):
        if window < 1:
            raise ValueError("window must be >= 1")
        self.store = store
        self.window = min(window, store.n_layers)
        self.device_put = device_put
        self.policy = policy or IOPolicy()
        self.tracer = tracer or NULL_TRACER
        self.memory = memory if memory is not None \
            else TierManager(tracer=tracer, name="prefetch-memory")
        self.owner = owner
        self.health = WorkerHealth(name="LayerPrefetcher")
        # layer -> (tree, nbytes, tier at rest)
        self._buf: Dict[int, Tuple[Params, int, str]] = {}
        self._queue: deque = deque()
        self._inflight: set = set()
        self._cv = threading.Condition()
        self._stop = False
        self._closed = False
        self._interrupted = False
        self._error: Optional[BaseException] = None
        self._events: List[PrefetchEvent] = []
        self._resident = 0
        self._peak = 0
        self._read = 0
        self._stall = 0.0
        self._served = 0
        self._thread = threading.Thread(target=self._worker, daemon=True)
        self._thread.start()

    # -- worker ------------------------------------------------------------ #

    def _reopen(self, i: int) -> None:
        reopen = getattr(self.store, "reopen", None)
        if reopen is not None:
            reopen(i)

    def _stage(self, i: int) -> Tuple[Params, int, float, float]:
        """Copy layer i out of the mmap into private host buffers."""
        self.store.willneed(i)
        t0 = clock()
        views = self.store.layer(i)
        # a real copy, not ascontiguousarray (which aliases contiguous mmap
        # views): staging must be private so the kernel reclaiming mmap
        # pages can never touch data the compute front is about to use
        staged = jax.tree.map(lambda a: np.array(a, copy=True), views)
        t1 = clock()                 # event = disk->staging only (the term
        nbytes = sum(a.nbytes for a in jax.tree.leaves(staged))
        return staged, nbytes, t0, t1     # latency model prices b/s_disk

    def _fail(self, i: int, e: BaseException) -> None:
        with self._cv:
            self._error = e
            self._inflight.discard(i)
            self._cv.notify_all()

    def _worker(self) -> None:
        est = self.store.layer_nbytes     # upper bound on a staged layer
        while True:
            with self._cv:
                while not self._queue and not self._stop:
                    self._cv.wait()
                if self._stop:
                    return
                i = self._queue.popleft()
                self._inflight.add(i)
            # lease *before* materializing: the sum of live leases is an
            # upper bound on true residency, so the budget's high-water
            # holds by construction. A full tier blocks here (throttling
            # prefetch) until the front releases a layer behind it.
            try:
                self.memory.lease("host", est, self.owner, wait=True,
                                  timeout=self.policy.op_deadline_s,
                                  cancelled=lambda: self._stop)
            except BaseException as e:
                self._fail(i, e)
                return
            try:
                staged, nbytes, t0, t1 = self.policy.run(
                    f"layer_read[{i}]", lambda: self._stage(i),
                    reopen=lambda: self._reopen(i), health=self.health)
            except (KeyboardInterrupt, SystemExit):
                # control flow, never a latched I/O error: unblock any
                # waiting get() (it raises "prefetcher stopped") and let
                # the exception terminate the worker thread
                self.memory.release("host", est, self.owner)
                with self._cv:
                    self._stop = True
                    self._interrupted = True
                    self._inflight.discard(i)
                    self._cv.notify_all()
                raise
            except BaseException as e:   # surface in get(), don't deadlock
                self.memory.release("host", est, self.owner)
                self._fail(i, e)
                return
            # shrink the upper-bound lease to the bytes actually staged
            # (a v2 store reads the ~4x-smaller packed footprint)
            self.memory.resize("host", self.owner, est, nbytes)
            tier = "host"
            if self.device_put:
                # async H2D: the transfer of layer k+w overlaps compute on
                # k. Lease device bytes first, copy, then drop the host
                # staging lease (the np buffers die with the rebind).
                try:
                    self.memory.lease("device", nbytes, self.owner,
                                      wait=True,
                                      timeout=self.policy.op_deadline_s,
                                      cancelled=lambda: self._stop)
                except BaseException as e:
                    self.memory.release("host", nbytes, self.owner)
                    self._fail(i, e)
                    return
                with self.tracer.span("h2d", cat="prefetch",
                                      track="prefetcher", layer=i):
                    staged = jax.tree.map(jnp.asarray, staged)
                self.memory.release("host", nbytes, self.owner)
                tier = "device"
            self.tracer.span_event(f"layer_read[{i}]", t0, t1,
                                   cat="prefetch", track="prefetcher",
                                   nbytes=nbytes)
            with self._cv:
                self._inflight.discard(i)
                if i not in self._buf and not self._stop:
                    self._buf[i] = (staged, nbytes, tier)
                    self._resident += nbytes
                    self._peak = max(self._peak, self._resident)
                else:   # duplicate stage / raced close: hand bytes back
                    self.memory.release(tier, nbytes, self.owner)
                self._read += nbytes
                self._events.append(PrefetchEvent(i, t0, t1, nbytes))
                self._cv.notify_all()

    # -- front side -------------------------------------------------------- #

    def _schedule_locked(self, i: int) -> None:
        L = self.store.n_layers
        for d in range(self.window):
            j = (i + d) % L
            if j not in self._buf and j not in self._inflight \
                    and j not in self._queue:
                self._queue.append(j)
        self._cv.notify_all()

    def _release_locked(self, front: int) -> None:
        L = self.store.n_layers
        dropped = False
        for j in list(self._buf):
            if (j - front) % L >= self.window:
                _, nbytes, tier = self._buf.pop(j)
                self._resident -= nbytes
                self.memory.release(tier, nbytes, self.owner)
                self.store.release(j)
                dropped = True
        if dropped:
            self.tracer.counter(
                "store/released_bytes",
                getattr(self.store, "released_bytes", 0),
                track="prefetcher")

    def get(self, i: int, *, timeout: Optional[float] = None) -> Params:
        """Block until layer ``i`` is staged, at most ``timeout`` seconds
        (default: the policy's ``get_timeout_s``) — a wedged worker
        becomes a :class:`StallTimeout` with a health report, never an
        unbounded block."""
        if timeout is None:
            timeout = self.policy.get_timeout_s
        deadline = clock() + timeout
        with self._cv:
            self._schedule_locked(i)
            self._release_locked(i)
            t0 = clock()
            # blocked time here is the un-hidden disk term — attribute
            # it to the caller's open token step as ``disk_wait`` (the
            # span itself only traces when the wait actually stalled)
            with self.tracer.phase("disk_wait", cat="prefetch",
                                   track="decode", min_dur=2e-4,
                                   label="disk_wait", layer=i):
                while i not in self._buf:
                    if self._error is not None:
                        raise RuntimeError(
                            f"prefetch of layer {i} failed "
                            f"({self.health.report()})") from self._error
                    if self._stop:
                        raise RuntimeError(
                            "prefetcher stopped" + (
                                " (worker interrupted)"
                                if self._interrupted else ""))
                    remaining = deadline - clock()
                    if remaining <= 0:
                        self.health.stalled = True
                        raise StallTimeout(
                            f"layer {i} not staged within {timeout:.1f}s "
                            f"({self.health.report()})",
                            op=f"layer_read[{i}]")
                    self._cv.wait(min(remaining, 0.25))
            self._stall += clock() - t0
            self._served += 1
            return self._buf[i][0]

    def stats(self) -> PrefetchStats:
        with self._cv:
            refusals = sum(s.refusals
                           for s in self.memory.stats().values())
            return PrefetchStats(
                events=list(self._events), peak_resident_bytes=self._peak,
                total_bytes_read=self._read, stall_s=self._stall,
                layers_served=self._served, releases=self.store.released,
                retries=self.health.retries,
                released_bytes=getattr(self.store, "released_bytes", 0),
                budget_refusals=refusals)

    def close(self, timeout: float = 5.0) -> bool:
        """Stop the worker; returns True once it has actually joined.

        Idempotent: a second call re-checks the join without re-stopping.
        A thread that fails to join within ``timeout`` is reported as a
        stall (logged with the health record) and left daemonized; the
        object is unusable either way. Staged buffers hand their leases
        back so a shared budget balances after shutdown.
        """
        with self._cv:
            self._closed = True
            self._stop = True
            for j in list(self._buf):
                _, nbytes, tier = self._buf.pop(j)
                self._resident -= nbytes
                self.memory.release(tier, nbytes, self.owner)
            self._cv.notify_all()
        self._thread.join(timeout=timeout)
        if self._thread.is_alive():
            self.health.stalled = True
            log.error("LayerPrefetcher.close: worker failed to join "
                      "within %.1fs — %s", timeout, self.health.report())
            return False
        self.health.closed = True
        return True


class StreamingParamSource(ParamSource):
    """ParamSource over a store + async prefetcher (the streamed path).

    The head (embedding / final norm / lm head) is loaded once and stays
    resident, exactly as the paper pins the head on device 1; block layers
    stream through the ``window``-sized prefetch buffer.
    """

    def __init__(self, store: ParamStore, *, window: int = 4,
                 device_put: bool = True,
                 policy: Optional[IOPolicy] = None, tracer=None,
                 memory: Optional[TierManager] = None):
        self.store = store
        self.n_layers = store.n_layers
        self.prefetcher = LayerPrefetcher(store, window=window,
                                          device_put=device_put,
                                          policy=policy, tracer=tracer,
                                          memory=memory)
        head = store.head()
        if device_put:
            head = jax.tree.map(jnp.asarray, head)
        self._head = head

    def layer(self, i: int) -> Params:
        return self.prefetcher.get(i)

    def head(self) -> Params:
        return self._head

    def stats(self) -> PrefetchStats:
        return self.prefetcher.stats()

    def health(self) -> WorkerHealth:
        return self.prefetcher.health

    def close(self) -> None:
        self.prefetcher.close()
        self.store.close()

    def __enter__(self) -> "StreamingParamSource":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


# --------------------------------------------------------------------------- #
#  continuous-batching integration
# --------------------------------------------------------------------------- #

def make_streaming_engine(source: ParamSource, cfg, batch: int, ctx: int,
                          *, eos_id: Optional[int] = None, spec=None,
                          cache_dtype=jnp.float32, tracer=None,
                          metrics=None):
    """Build a ``ContinuousBatcher`` whose prefill/decode pull weights from
    ``source`` layer by layer (resident or streamed — same engine).
    """
    from ..models import model as M
    from .engine import ContinuousBatcher

    def prefill_one(prompt):
        c1 = M.init_cache(cfg, 1, ctx, dtype=cache_dtype)
        logits, c1 = M.prefill_layerwise(source, cfg, prompt, c1)
        return int(jnp.argmax(logits[0, -1])), c1

    def write_slot(cache, slot_cache, slot, length):
        def wr(dst, src):
            if dst.ndim >= 2 and dst.shape[1] == batch and src.shape[1] == 1:
                return dst.at[:, slot].set(src[:, 0])
            return dst

        new = jax.tree.map(wr, cache, slot_cache)
        new["len"] = cache["len"].at[slot].set(slot_cache["len"][0])
        return new

    def decode(cache, tokens):
        return M.decode_step_layerwise(source, cfg, cache, tokens)

    return ContinuousBatcher(batch, prefill_one, write_slot, decode,
                             eos_id=eos_id, spec=spec, source=source,
                             ctx=ctx, tracer=tracer, metrics=metrics)


# --------------------------------------------------------------------------- #
#  piped-ring streaming (multi-device)
# --------------------------------------------------------------------------- #

class RingBankPrefetcher:
    """Stage per-microstep window banks for the streamed SPMD ring.

    The ring schedule needs, at microstep ``t``, a bank whose stage-``m``
    rows hold that stage's round-``r_m(t)`` window
    (``serve.ring_bank_layers``). A background thread assembles each
    step's bank from the layer store (staging copies + sharded
    ``device_put``) one step ahead of the compute front; per-layer staging
    buffers are reused across the steps that need them and dropped after
    their last use in the pass — release strictly behind the front.
    """

    def __init__(self, store: ParamStore, cfg, mesh, plan, *,
                 bank_specs, depth: int = 2,
                 policy: Optional[IOPolicy] = None, tracer=None,
                 memory: Optional[TierManager] = None,
                 owner: str = "weights"):
        from . import serve as RS

        self.store = store
        self.plan = plan
        self.depth = max(depth, 1)
        self.policy = policy or IOPolicy()
        self.tracer = tracer or NULL_TRACER
        self.memory = memory if memory is not None \
            else TierManager(tracer=tracer, name="ring-prefetch-memory")
        self.owner = owner
        self.health = WorkerHealth(name="RingBankPrefetcher")
        self._sharding = jax.tree.map(
            lambda s: jax.sharding.NamedSharding(mesh, s), bank_specs)
        n_steps = plan.k * plan.n_stages + plan.n_stages - 1
        self._rows = [RS.ring_bank_layers(plan, t) for t in range(n_steps)]
        self.n_steps = n_steps
        L = cfg.n_layers
        last: Dict[int, int] = {}
        for t, rows in enumerate(self._rows):
            for layer in rows:
                if 0 <= layer < L:
                    last[int(layer)] = t
        self._last_use = last
        self.n_layers = L
        self._zero = None                 # cached zero layer (padding rows)
        self._staged: Dict[int, Params] = {}
        self._banks: Dict[int, Any] = {}
        self._bank_nbytes: Dict[int, int] = {}
        self._cv = threading.Condition()
        self._stop = False
        self._closed = False
        self._interrupted = False
        self._error: Optional[BaseException] = None
        self._want: deque = deque()
        self._front = -1                  # last consumed step
        self._resident = 0
        self._peak = 0
        self._read = 0
        self._stall = 0.0                 # compute front blocked in get()
        self._served = 0
        self._events: List[PrefetchEvent] = []
        self._thread = threading.Thread(target=self._worker, daemon=True)
        self._thread.start()

    # -- staging ----------------------------------------------------------- #

    def _reopen(self, layer: int) -> None:
        reopen = getattr(self.store, "reopen", None)
        if reopen is not None:
            reopen(layer)

    def _read_np(self, layer: int) -> Params:
        views = self.store.layer(layer)
        return jax.tree.map(lambda a: np.array(a, copy=True), views)

    def _layer_np(self, layer: int) -> Params:
        if layer >= self.n_layers:              # ring padding rows
            if self._zero is None:
                proto = self.policy.run(
                    "layer_read[0]", lambda: self._read_np(0),
                    reopen=lambda: self._reopen(0), health=self.health)
                self._zero = jax.tree.map(
                    lambda a: np.zeros(a.shape, a.dtype), proto)
            return self._zero
        staged = self._staged.get(layer)
        if staged is None:
            # lease the manifest upper bound before reading, shrink to
            # the packed bytes actually staged (v2 stores)
            est = self.store.layer_nbytes
            self.memory.lease("host", est, self.owner, wait=True,
                              timeout=self.policy.op_deadline_s,
                              cancelled=lambda: self._stop)
            t0 = clock()
            try:
                staged = self.policy.run(
                    f"layer_read[{layer}]", lambda: self._read_np(layer),
                    reopen=lambda: self._reopen(layer), health=self.health)
            except BaseException:
                self.memory.release("host", est, self.owner)
                raise
            t1 = clock()
            nbytes = sum(a.nbytes for a in jax.tree.leaves(staged))
            self.memory.resize("host", self.owner, est, nbytes)
            self.tracer.span_event(f"layer_read[{layer}]", t0, t1,
                                   cat="prefetch",
                                   track="ring-prefetcher",
                                   nbytes=nbytes)
            with self._cv:    # bookkeeping races with done()'s releases
                self._staged[layer] = staged
                self._resident += nbytes
                self._peak = max(self._peak, self._resident)
                self._read += nbytes
                self._events.append(PrefetchEvent(layer, t0, t1, nbytes))
        return staged

    def _build_bank(self, t: int):
        rows = self._rows[t]
        layers = [self._layer_np(int(i)) for i in rows]
        with self.tracer.span("bank_h2d", cat="prefetch",
                              track="ring-prefetcher", bank=t):
            bank_np = jax.tree.map(lambda *xs: np.stack(xs, 0), *layers)
            nbytes = sum(a.nbytes for a in jax.tree.leaves(bank_np))
            # device bytes for the stacked bank: leased before the put,
            # released when done(t) drops the bank behind the front
            self.memory.lease("device", nbytes, self.owner, wait=True,
                              timeout=self.policy.op_deadline_s,
                              cancelled=lambda: self._stop)
            try:
                bank = jax.device_put(bank_np, self._sharding)
            except BaseException:
                self.memory.release("device", nbytes, self.owner)
                raise
            with self._cv:
                self._bank_nbytes[t] = nbytes
            return bank

    def _worker(self) -> None:
        while True:
            with self._cv:
                # throttle: never build more than ``depth`` banks past the
                # front — this is what bounds staged bytes by the window,
                # not the model (prefetch cannot run away from release)
                while not self._stop and (
                        not self._want
                        or self._want[0] > self._front + self.depth):
                    self._cv.wait()
                if self._stop:
                    return
                t = self._want.popleft()
            try:
                bank = self._build_bank(t)
            except (KeyboardInterrupt, SystemExit):
                # control flow: unblock waiters, then die loudly
                with self._cv:
                    self._stop = True
                    self._interrupted = True
                    self._cv.notify_all()
                raise
            except BaseException as e:   # surface in get(), don't deadlock
                with self._cv:
                    self._error = e
                    self._cv.notify_all()
                return
            with self._cv:
                self._banks[t] = bank
                self._cv.notify_all()

    # -- front side -------------------------------------------------------- #

    def begin_pass(self) -> None:
        """Enqueue the whole step schedule (banks build ``depth`` ahead)."""
        with self._cv:
            self._drain_locked(banks_only=True)
            self._front = -1
            self._want.extend(range(self.n_steps))
            self._cv.notify_all()

    def _drain_locked(self, *, banks_only: bool = False) -> None:
        """Hand every live lease back (abandoned pass / shutdown)."""
        for t in list(self._banks):
            self._banks.pop(t)
            self.memory.release("device", self._bank_nbytes.pop(t, 0),
                                self.owner)
        if banks_only:
            return
        for layer in list(self._staged):
            staged = self._staged.pop(layer)
            nbytes = sum(a.nbytes for a in jax.tree.leaves(staged))
            self._resident -= nbytes
            self.memory.release("host", nbytes, self.owner)
            self.store.release(layer)

    def get(self, t: int, *, timeout: Optional[float] = None):
        if timeout is None:
            timeout = self.policy.get_timeout_s
        deadline = clock() + timeout
        with self._cv:
            t0 = clock()
            with self.tracer.phase("disk_wait", cat="prefetch",
                                   track="decode", min_dur=2e-4,
                                   label="bank_wait", bank=t):
                while t not in self._banks:
                    if self._error is not None:
                        raise RuntimeError(
                            f"bank staging for step {t} failed "
                            f"({self.health.report()})") from self._error
                    if self._stop:
                        raise RuntimeError(
                            "bank prefetcher stopped" + (
                                " (worker interrupted)"
                                if self._interrupted else ""))
                    remaining = deadline - clock()
                    if remaining <= 0:
                        self.health.stalled = True
                        raise StallTimeout(
                            f"bank for step {t} not staged within "
                            f"{timeout:.1f}s ({self.health.report()})",
                            op=f"bank_build[{t}]")
                    self._cv.wait(min(remaining, 0.25))
            self._stall += clock() - t0
            self._served += 1
            return self._banks[t]

    def done(self, t: int) -> None:
        """Step ``t`` consumed: drop its bank and release layers whose last
        use in this pass was step ``t`` (behind the compute front)."""
        with self._cv:
            if self._banks.pop(t, None) is not None:
                self.memory.release("device",
                                    self._bank_nbytes.pop(t, 0),
                                    self.owner)
            self._front = max(self._front, t)
            for layer, last in self._last_use.items():
                if last == t and layer in self._staged:
                    staged = self._staged.pop(layer)
                    nbytes = sum(
                        a.nbytes for a in jax.tree.leaves(staged))
                    self._resident -= nbytes
                    self.memory.release("host", nbytes, self.owner)
                    self.store.release(layer)
            self._cv.notify_all()

    def stats(self) -> PrefetchStats:
        with self._cv:
            return PrefetchStats(
                events=list(self._events), peak_resident_bytes=self._peak,
                total_bytes_read=self._read, stall_s=self._stall,
                layers_served=len(self._events),
                releases=self.store.released,
                retries=self.health.retries,
                released_bytes=getattr(self.store, "released_bytes", 0),
                budget_refusals=sum(
                    s.refusals for s in self.memory.stats().values()))

    def close(self, timeout: float = 5.0) -> bool:
        """Stop the worker (idempotent); True once it has joined, False
        with a logged stall report if it is stuck."""
        with self._cv:
            self._closed = True
            self._stop = True
            self._cv.notify_all()
        self._thread.join(timeout=timeout)
        if not self._thread.is_alive():
            with self._cv:
                self._drain_locked()
        if self._thread.is_alive():
            self.health.stalled = True
            log.error("RingBankPrefetcher.close: worker failed to join "
                      "within %.1fs — %s", timeout, self.health.report())
            return False
        self.health.closed = True
        return True


class StreamingRingDriver:
    """Host-driven piped-ring decode whose window banks stream from disk.

    Where ``build_ring_serve_step`` closes over the full ring-ordered
    layer bank ((k*w, ...) per stage, all resident), this driver holds
    only each microstep's (w, ...) window on device: the host loop runs
    the ``k*M + M - 1`` ring microsteps itself, feeding banks staged by
    ``RingBankPrefetcher`` — disk reads and H2D copies for step ``t+1``
    overlap the device compute of step ``t``, and layers behind the
    front are released. The KV cache stays device-resident (it is state,
    not streamable weights).
    """

    def __init__(self, cfg, mesh, plan, store: ParamStore, *,
                 head_params: Params, cache_like, n_tokens: int = 1,
                 prefetch_depth: int = 2,
                 policy: Optional[IOPolicy] = None, tracer=None,
                 memory: Optional[TierManager] = None):
        from . import serve as RS

        self.cfg = cfg
        self.plan = plan
        policy = policy or IOPolicy()
        self.tracer = tracer or NULL_TRACER
        layer_like = policy.run("layer_read[0]", lambda: store.layer(0))
        fns, bank_specs = RS.build_ring_stream_step(
            cfg, mesh, plan, head_params, cache_like, layer_like,
            n_tokens=n_tokens)
        self._embed, self._micro, self._final = fns
        self.head_params = head_params
        self.n_tokens = n_tokens
        self.prefetch = RingBankPrefetcher(store, cfg, mesh, plan,
                                           bank_specs=bank_specs,
                                           depth=prefetch_depth,
                                           policy=policy, tracer=tracer,
                                           memory=memory)
        self.n_steps = self.prefetch.n_steps
        self._token_idx = 0

    def step(self, tokens, ln, cache):
        """One decode pass (all L layers streamed once): (logits, cache).

        With a tracer attached each pass is one token-step scope: bank
        waits attribute to ``disk_wait`` (inside the prefetcher's
        ``get``), the ring microsteps to ``compute``, and the microstep
        spans land on the ``ring`` track of the exported trace.
        """
        with self.tracer.token_step(self._token_idx, track="decode",
                                    name="ring_token"):
            self._token_idx += 1
            return self._step_inner(tokens, ln, cache)

    def _step_inner(self, tokens, ln, cache):
        cfg, plan = self.cfg, self.plan
        B = tokens.shape[0]
        mb = B // plan.n_stages
        d = self.head_params["embed"].shape[1]
        self.prefetch.begin_pass()
        with self.tracer.phase("compute", cat="ring", track="ring",
                               label="embed"):
            emb_all = self._embed(tokens, self.head_params)
        dtype = emb_all.dtype
        x = jnp.zeros((plan.n_stages * mb, self.n_tokens, d), dtype)
        out_buf = jnp.zeros((plan.n_stages * B, self.n_tokens, d), dtype)
        layers_c = cache["layers"]
        for t in range(self.n_steps):
            bank = self.prefetch.get(t)
            with self.tracer.phase("compute", cat="ring", track="ring",
                                   label="microstep", microstep=t):
                x, layers_c, out_buf = self._micro(
                    jnp.int32(t), x, emb_all, ln, layers_c, out_buf,
                    bank, self.head_params["final_norm"])
            self.prefetch.done(t)
        with self.tracer.phase("compute", cat="ring", track="ring",
                               label="head"):
            logits = self._final(out_buf, self.head_params)
            logits = jax.block_until_ready(logits)
        new_cache = dict(cache)
        new_cache["layers"] = layers_c
        new_cache["len"] = ln + self.n_tokens
        return logits, new_cache

    def stats(self) -> PrefetchStats:
        return self.prefetch.stats()

    def health(self) -> WorkerHealth:
        return self.prefetch.health

    def close(self, timeout: float = 5.0) -> bool:
        return self.prefetch.close(timeout=timeout)
