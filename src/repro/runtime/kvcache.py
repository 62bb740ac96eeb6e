"""Paged KV cache: block-pool allocator, prefix reuse, host offload.

The dense cache (``models.model.init_cache``) preallocates
``(L, B, max_len, ...)`` — memory scales with ``batch * max_len`` no
matter how many tokens are actually live, which is what OOMs first on
low-RAM devices and caps ``ContinuousBatcher`` concurrency. This module
applies the paper's working-window recipe to KV state the way PR 2/3
applied it to weights:

  * **BlockPool** — fixed-size token pages with refcounts. Sequences own
    pages only for tokens they actually hold; HBM high-water tracks
    *active* tokens, not the batch envelope.
  * **Prefix reuse** — every full prompt page (and the final partial
    page) is content-addressed by its exact chained token key (compared
    by value — a collision can never silently share the wrong bytes);
    identical prompt prefixes retain the same refcounted pages instead
    of recomputing and re-storing them. Writes into a shared page copy-on-write at the
    divergence page; writes into a privately-held but still-addressable
    page unregister its hash first, so the content a hash names is
    immutable by construction.
  * **Host offload** — pages whose refcount drops to zero stay resident
    as a prefix cache; when the pool needs room they are evicted to
    pinned host copies instead of being discarded. A prefix hit on an
    offloaded page allocates a fresh device page and fetches the bytes
    back on a background staging thread (the double-buffer pattern of
    ``runtime.streaming``), so the H2D copy overlaps the admit's prefill
    compute exactly like layer prefetch overlaps decode. The fetch
    timeline reuses ``PrefetchEvent`` so ``core.latency`` can cross-check
    the offload-traffic term against measurement.
  * **Tiered budget** — every resident byte (the device pool, host
    copies, disk page files) leases from one shared
    ``runtime.memory.TierManager``; a full host tier spills the
    offloader's coldest pages to a ``PageFileStore`` disk tier
    (``kv_d2disk``/``kv_disk2h`` under the same retry policy and fault
    injector as every other I/O path) and eviction can be cost-model
    driven (``evict_policy="cost"``): the victim minimizes expected
    recall seconds priced by ``core.latency.kv_recall_costs``, not
    recency. ``quantize_page`` int8-compresses offloaded bytes
    (``offload_quant=True``), and idle **sessions park** to per-session
    disk files and restore byte-identically (``park_session`` /
    ``restore_session`` / ``sweep_parked``).

Device state lives in the engine-threaded cache pytree
(``{"pages", "block_table", "len"}``); this module's classes hold only
host bookkeeping plus the staging thread, and every device mutation
takes and returns the cache functionally.
"""
from __future__ import annotations

import dataclasses
import logging
import mmap
import os
import threading
import functools
import time
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .iopolicy import BudgetExceeded, IOPolicy, StallTimeout, WorkerHealth
from .memory import TierManager
from .paramstore import _np_dtype
from .streaming import PrefetchEvent, PrefetchStats
from .telemetry import NULL_TRACER, clock

log = logging.getLogger(__name__)

Params = Dict[str, Any]

#: page id 0 is a write sink: freed slots keep decoding junk into it (the
#: batch is fixed-width, inactive rows still run), so it is never handed
#: out by the allocator and its content is never read unmasked.
SINK_PAGE = 0


class PoolExhausted(RuntimeError):
    """The block pool cannot satisfy an allocation (clear admit error)."""


def chain_key(prev: tuple, tokens: Sequence[int], count: int) -> tuple:
    """Content key of a prompt page given its predecessor's key.

    The key IS the (nested) token chain, not a digest — lookups compare
    the actual tokens, so a collision can never silently share another
    prompt's KV pages. ``count`` participates so a partial page
    (count < page_tokens) only matches a page with the identical token
    count — partial pages are shared only between byte-identical
    prompts. Start the chain with ``()``.
    """
    return (prev, count, tuple(int(t) for t in tokens))


# --------------------------------------------------------------------------- #
#  block pool (host-side allocator)
# --------------------------------------------------------------------------- #

class BlockPool:
    """Refcounted fixed-size page allocator with an LRU prefix cache.

    Page states:
      free     — on the free list, content meaningless;
      active   — refcount >= 1 (held by one or more slots);
      cached   — refcount 0 but still hash-addressable (prefix cache),
                 evicted LRU-first when the free list runs dry.

    ``release`` on a page that is not active raises — the double-free is
    a bug in the caller, not a condition to paper over.

    Eviction of cached (refcount-0) pages is pluggable:
    ``evict_policy="lru"`` keeps the original least-recently-used order;
    ``"cost"`` picks the victim minimizing *expected recall loss* —
    ``(1 + hit count) * recall_cost_fn(key)``, where ``recall_cost_fn``
    prices bringing the page back from wherever eviction would land it
    (``core.latency.kv_recall_costs`` terms) — so a hot page whose
    recall would come from disk outlives a cold page recallable from
    host, which plain LRU cannot express.

    Capacity stops being the pool's concern beyond its fixed page
    count: ``PagedKVCache`` leases the whole pool allocation from the
    shared :class:`~runtime.memory.TierManager` and derives ``n_pages``
    from the device budget, so this class never carries a standalone
    byte cap.
    """

    def __init__(self, n_pages: int, page_tokens: int, *,
                 evict_policy: str = "lru",
                 recall_cost_fn: Optional[Callable[[Any], float]] = None):
        if n_pages < 2:
            raise ValueError("need >= 2 pages (page 0 is the write sink)")
        if page_tokens < 1:
            raise ValueError("page_tokens must be >= 1")
        if evict_policy not in ("lru", "cost"):
            raise ValueError(f"unknown evict_policy {evict_policy!r} "
                             f"(expected 'lru' or 'cost')")
        self.n_pages = n_pages
        self.page_tokens = page_tokens
        self.evict_policy = evict_policy
        self.recall_cost_fn = recall_cost_fn
        self._free: List[int] = list(range(n_pages - 1, SINK_PAGE, -1))
        self._ref: Dict[int, int] = {}
        self._hash_of: Dict[int, Any] = {}       # pid -> registered key
        self._pid_of: Dict[Any, int] = {}        # content key -> pid
        self._cached: "OrderedDict[int, None]" = OrderedDict()  # LRU, ref 0
        self._freq: Dict[Any, int] = {}          # content key -> reuse hits
        self.alloc_count = 0
        self.evictions = 0

    # -- queries ----------------------------------------------------------- #

    def refcount(self, pid: int) -> int:
        return self._ref.get(pid, 0)

    def lookup(self, h) -> Optional[int]:
        """Device-resident page registered under content key ``h`` (or
        None). Keys are compared by value (the exact token chain), so a
        hit is always the right bytes. Hits feed the per-key reuse
        frequency the cost-model eviction weighs."""
        pid = self._pid_of.get(h)
        if pid is not None:
            self._freq[h] = self._freq.get(h, 0) + 1
        return pid

    def note_hit(self, h) -> None:
        """Record a reuse of key ``h`` served off-device (an offloaded
        copy) — same frequency signal as a resident ``lookup`` hit."""
        self._freq[h] = self._freq.get(h, 0) + 1

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_active(self) -> int:
        return len(self._ref)

    @property
    def n_cached(self) -> int:
        return len(self._cached)

    def available(self) -> int:
        """Pages an alloc burst could obtain (free + evictable cached)."""
        return len(self._free) + len(self._cached)

    # -- lifecycle --------------------------------------------------------- #

    def alloc(self, *, evict_cb=None) -> int:
        """Take a page (refcount 1). Falls back to evicting the LRU cached
        page; ``evict_cb(pid, h)`` runs first so the owner can offload the
        content. Raises ``PoolExhausted`` when neither source has a page.
        """
        if self._free:
            pid = self._free.pop()
        elif self._cached:
            pid = self._pick_victim()
            del self._cached[pid]
            h = self._hash_of.pop(pid)
            del self._pid_of[h]
            self.evictions += 1
            if evict_cb is not None:
                evict_cb(pid, h)
        else:
            raise PoolExhausted(
                f"KV block pool exhausted: {self.n_pages - 1} pages, "
                f"{self.n_active} active, none cached/free")
        self._ref[pid] = 1
        self.alloc_count += 1
        return pid

    def _pick_victim(self) -> int:
        """Choose which cached page eviction reclaims.

        LRU: oldest entry. Cost: minimize expected recall loss,
        ``(1 + reuse hits) * modeled recall seconds`` — evicting the
        page we are least likely to miss, and cheapest to recall when
        we do. Falls back to LRU without a pricing function.
        """
        if self.evict_policy == "cost" and self.recall_cost_fn is not None:
            return min(
                self._cached,
                key=lambda p: (1 + self._freq.get(self._hash_of[p], 0))
                * self.recall_cost_fn(self._hash_of[p]))
        return next(iter(self._cached))                    # LRU

    def retain(self, pid: int) -> None:
        """Add a reference (prefix share / cached-page revival)."""
        if pid == SINK_PAGE:
            raise ValueError("cannot retain the sink page")
        if pid in self._cached:
            del self._cached[pid]
            self._ref[pid] = 1
        else:
            if pid not in self._ref:
                raise ValueError(f"retain of non-active page {pid}")
            self._ref[pid] += 1

    def release(self, pid: int) -> None:
        """Drop a reference; at zero the page goes to the prefix cache if
        hash-addressable, otherwise back to the free list."""
        n = self._ref.get(pid)
        if n is None:
            raise ValueError(f"double free of page {pid}")
        if n > 1:
            self._ref[pid] = n - 1
            return
        del self._ref[pid]
        if pid in self._hash_of:
            self._cached[pid] = None                       # MRU end
            self._cached.move_to_end(pid)
        else:
            self._free.append(pid)

    # -- hash addressing --------------------------------------------------- #

    def register(self, h, pid: int) -> None:
        """Make an active page addressable by content key ``h``."""
        if pid not in self._ref:
            raise ValueError(f"register of non-active page {pid}")
        old = self._pid_of.get(h)
        if old is not None and old != pid:
            # identical content already registered; keep the older page
            return
        self._pid_of[h] = pid
        self._hash_of[pid] = h

    def unregister(self, pid: int) -> None:
        """Forget a page's hash (it is about to be written in place)."""
        h = self._hash_of.pop(pid, None)
        if h is not None:
            self._pid_of.pop(h, None)

    # -- invariants (tests) ------------------------------------------------ #

    def check(self) -> None:
        free, active, cached = set(self._free), set(self._ref), \
            set(self._cached)
        assert SINK_PAGE not in free | active | cached
        assert not free & active and not free & cached \
            and not active & cached
        assert len(free) + len(active) + len(cached) == self.n_pages - 1
        assert all(n >= 1 for n in self._ref.values())
        assert cached <= set(self._hash_of)
        for h, pid in self._pid_of.items():
            assert self._hash_of.get(pid) == h


# --------------------------------------------------------------------------- #
#  int8 page quantization (quantize-on-write during offload)
# --------------------------------------------------------------------------- #

_SCALE_SUFFIX = "::scale"


def quantize_page(tree: Params) -> Params:
    """Symmetric per-vector int8 quantization of a page tree.

    Same scheme as the dense int8-KV path (``models.layers.quantize_kv``):
    each last-axis vector (one head's K or V for one token) gets an
    ``amax/127`` float32 scale stored under ``<leaf>::scale``. Halves the
    float32 page footprint (4B -> 1B + 4B/head_dim) on the host and disk
    tiers; lossy, so it is applied only to evicted prefix-cache pages —
    never to parked sessions, whose restore must be byte-identical.
    """
    out: Params = {}
    for name, a in tree.items():
        a = np.asarray(a)
        f = a.astype(np.float32)
        scale = np.max(np.abs(f), axis=-1, keepdims=True) / 127.0
        scale = np.where(scale > 0, scale, 1.0).astype(np.float32)
        out[name] = np.clip(np.rint(f / scale), -127, 127).astype(np.int8)
        out[name + _SCALE_SUFFIX] = scale
    return out


def dequantize_page(tree: Params, dtype) -> Params:
    """Inverse of :func:`quantize_page` (cast back to the pool dtype)."""
    out: Params = {}
    for name, a in tree.items():
        if name.endswith(_SCALE_SUFFIX):
            continue
        scale = tree.get(name + _SCALE_SUFFIX)
        if scale is None:
            out[name] = np.asarray(a)
        else:
            out[name] = (np.asarray(a).astype(np.float32)
                         * scale).astype(dtype)
    return out


def is_quantized_page(tree: Params) -> bool:
    return any(k.endswith(_SCALE_SUFFIX) for k in tree)


# --------------------------------------------------------------------------- #
#  disk tier (per-session / per-page mmap page files)
# --------------------------------------------------------------------------- #

class PageFileStore:
    """Disk tier for KV pages: one flat binary file per key, read back
    through mmap views — ``ParamStore``'s layout at page granularity.

    Keys are arbitrary hashables (content chain keys for spilled
    prefix-cache pages, ``("sess", id, j)`` for a parked session's page
    files); the spec index lives in memory, so the store is scoped to
    one serving process like the pool it backs. Writes run under the
    shared :class:`IOPolicy` as op ``kv_d2disk`` and reads as
    ``kv_disk2h`` — both injectable by ``faults.FaultInjector`` and
    retried/deadlined exactly like layer reads. ``get`` copies out of
    the mapping, so restored bytes are private and byte-identical.
    """

    def __init__(self, directory: str, *,
                 policy: Optional[IOPolicy] = None, injector=None,
                 tracer=None):
        os.makedirs(directory, exist_ok=True)
        self.directory = directory
        self.policy = policy or IOPolicy()
        self.injector = injector
        self.tracer = tracer or NULL_TRACER
        self.health = WorkerHealth(name="PageFileStore")
        # key -> (path, [(leaf name, shape, dtype name, offset, nbytes)])
        self._index: Dict[Any, Tuple[str, List[Tuple]]] = {}
        self._seq = 0
        self._lock = threading.Lock()
        self.written_bytes = 0
        self.read_bytes = 0
        self.events: List[PrefetchEvent] = []     # read (recall) timeline

    def holds(self, key) -> bool:
        with self._lock:
            return key in self._index

    def nbytes(self, key) -> int:
        with self._lock:
            ent = self._index.get(key)
            return sum(s[4] for s in ent[1]) if ent else 0

    def __len__(self) -> int:
        with self._lock:
            return len(self._index)

    def put(self, key, tree: Params) -> int:
        """Persist a flat page tree under ``key``; returns bytes written.
        Atomic per key: the index only records a fully-written file, and
        a retried write starts the file over."""
        with self._lock:
            path = os.path.join(self.directory,
                                f"page_{self._seq:06d}.bin")
            self._seq += 1
        leaves = [(name, np.ascontiguousarray(tree[name]))
                  for name in sorted(tree)]
        specs: List[Tuple] = []
        offset = 0
        for name, arr in leaves:
            specs.append((name, arr.shape, arr.dtype.name
                          if arr.dtype.name != "void" else str(arr.dtype),
                          offset, arr.nbytes))
            offset += arr.nbytes

        def write() -> int:
            if self.injector is not None:
                self.injector.check("kv_d2disk", key=key)
            with open(path, "wb") as f:
                for _, arr in leaves:
                    f.write(arr.tobytes())
            return offset

        t0 = clock()
        total = self.policy.run("kv_d2disk", write, health=self.health)
        self.tracer.span_event(f"kv_d2disk[{key}]", t0, clock(), cat="kv",
                               track="kv-offloader", nbytes=total)
        with self._lock:
            self._index[key] = (path, specs)
            self.written_bytes += total
        return total

    def get(self, key) -> Params:
        """Read a page tree back (private copies, byte-identical)."""
        with self._lock:
            path, specs = self._index[key]

        def read() -> Params:
            if self.injector is not None:
                self.injector.check("kv_disk2h", key=key)
            with open(path, "rb") as f:
                mm = mmap.mmap(f.fileno(), 0, access=mmap.ACCESS_READ)
                try:
                    buf = np.frombuffer(mm, dtype=np.uint8)
                    out: Params = {}
                    for name, shape, dt, off, nb in specs:
                        out[name] = buf[off:off + nb] \
                            .view(_np_dtype(dt)).reshape(shape).copy()
                    return out
                finally:
                    del buf
                    mm.close()

        t0 = clock()
        out = self.policy.run("kv_disk2h", read, health=self.health)
        t1 = clock()
        total = sum(s[4] for s in specs)
        self.tracer.span_event(f"kv_disk2h[{key}]", t0, t1, cat="kv",
                               track="kv-offloader", nbytes=total)
        with self._lock:
            self.read_bytes += total
            self.events.append(PrefetchEvent(0, t0, t1, total))
        return out

    def drop(self, key) -> int:
        """Forget ``key`` and delete its file; returns bytes freed."""
        with self._lock:
            ent = self._index.pop(key, None)
        if ent is None:
            return 0
        path, specs = ent
        try:
            os.unlink(path)
        except OSError:       # pragma: no cover - already gone
            pass
        return sum(s[4] for s in specs)

    def close(self) -> None:
        with self._lock:
            entries = list(self._index.values())
            self._index.clear()
        for path, _ in entries:
            try:
                os.unlink(path)
            except OSError:   # pragma: no cover - already gone
                pass


# --------------------------------------------------------------------------- #
#  host offload (staged fetch, streaming.py's double-buffer pattern)
# --------------------------------------------------------------------------- #

class BlockOffloader:
    """Host-side store of evicted pages + async device staging.

    ``offload`` (eviction path) copies a page's per-layer bytes to host
    synchronously — it runs inside an allocation that needs the device
    page now. ``schedule`` queues the reverse H2D transfer on a worker
    thread; ``get`` blocks until the staged device tree is ready. Fetches
    are scheduled at admit time and collected after the admit's prefill
    compute, so the copy overlaps compute exactly like the layer
    prefetcher's window reads.

    Host copies lease from the shared memory budget's ``host`` tier
    (private/unbounded when no manager is passed — the seed behavior).
    A refused lease no longer grows past the budget: the offloader
    first **spills** its oldest host pages to the ``disk`` tier (a
    :class:`PageFileStore`, op ``kv_d2disk``) to make room, and only
    when there is no disk store — or it is full too — surfaces
    :class:`BudgetExceeded`, which the shared policy classifies
    transient (a finishing slot is usually about to release pages).
    ``quant=True`` int8-quantizes pages on write (``quantize_page``),
    halving host/disk bytes at the price of bounded dequantization
    drift on refetch.
    """

    def __init__(self, *, policy: Optional[IOPolicy] = None,
                 injector=None, tracer=None,
                 memory: Optional[TierManager] = None,
                 owner: str = "kv",
                 disk: Optional[PageFileStore] = None,
                 quant: bool = False, page_dtype=np.float32) -> None:
        self.policy = policy or IOPolicy()
        self.injector = injector          # faults.FaultInjector or None
        self.tracer = tracer or NULL_TRACER
        self.memory = memory if memory is not None \
            else TierManager(tracer=tracer, name="kv-offload-memory")
        self.owner = owner
        self.disk = disk
        self.quant = quant
        self.page_dtype = page_dtype
        self.health = WorkerHealth(name="BlockOffloader")
        self.stall_s = 0.0                # get() blocked on a staging fetch
        self._host: Dict[Any, Tuple[Params, int]] = {}  # key -> (tree, nb)
        self._disk_keys: Dict[Any, int] = {}            # spilled key -> nb
        self._staged: Dict[Any, Params] = {}            # key -> jnp tree
        self._queue: List[Any] = []
        self._cv = threading.Condition()
        self._stop = False
        self._closed = False
        self._interrupted = False
        self._error: Optional[BaseException] = None
        self.events: List[PrefetchEvent] = []
        self.offloaded_bytes = 0
        self.fetched_bytes = 0
        self.spilled_pages = 0            # host pages demoted to disk
        self.fetched_disk_pages = 0       # recalls served from disk
        self._thread = threading.Thread(target=self._worker, daemon=True)
        self._thread.start()

    def _h2d(self, tree: Params) -> Params:
        if self.injector is not None:
            self.injector.check("kv_h2d")
        if is_quantized_page(tree):       # dequantize-on-read (lossy tier)
            tree = dequantize_page(tree, self.page_dtype)
        return jax.tree.map(jnp.asarray, tree)            # H2D staging

    def _fetch_tree(self, h) -> Tuple[Optional[Params], str, int]:
        """Locate a page's bytes: host hit, or disk recall (kv_disk2h)
        staged through a transient host lease."""
        with self._cv:
            ent = self._host.get(h)
            if ent is not None:
                return ent[0], "host", ent[1]
            on_disk = h in self._disk_keys
        if on_disk:
            nbytes = self.disk.nbytes(h)
            # the staging lease must not deadlock against our own host
            # copies: when the host tier is full of offloaded pages,
            # spill the coldest to disk to make room; only wait on the
            # budget once there is nothing left of ours to demote
            acquired = False
            with self._cv:
                while not acquired:
                    acquired = self.memory.try_lease("host", nbytes,
                                                     self.owner)
                    if not acquired and not self._spill_one_locked():
                        break
            if not acquired:
                self.memory.lease("host", nbytes, self.owner, wait=True,
                                  timeout=self.policy.op_deadline_s,
                                  cancelled=lambda: self._stop)
            try:
                tree = self.disk.get(h)   # policy + injector inside
            except BaseException:
                self.memory.release("host", nbytes, self.owner)
                raise
            return tree, "disk", nbytes
        return None, "none", 0

    def _worker(self) -> None:
        while True:
            with self._cv:
                while not self._queue and not self._stop:
                    self._cv.wait()
                if self._stop:
                    return
                h = self._queue.pop(0)
            try:
                tree, src, nbytes = self._fetch_tree(h)
                if tree is None:
                    continue
                t0 = clock()
                staged = self.policy.run("kv_h2d",
                                         lambda: self._h2d(tree),
                                         health=self.health)
                t1 = clock()
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
            if src == "disk":
                # staged on device now: drop the transient host lease,
                # the disk copy and its disk-tier lease
                self.memory.release("host", nbytes, self.owner)
                with self._cv:
                    disk_nb = self._disk_keys.pop(h, 0)
                self.disk.drop(h)
                self.memory.release("disk", disk_nb, self.owner)
                self.fetched_disk_pages += 1
            self.tracer.span_event(f"kv_h2d[{h}]", t0, t1, cat="kv",
                                   track="kv-offloader", nbytes=nbytes)
            with self._cv:
                self._staged[h] = staged
                self.events.append(PrefetchEvent(0, t0, t1, nbytes))
                self.fetched_bytes += nbytes
                self._cv.notify_all()

    # -- eviction side ----------------------------------------------------- #

    def _spill_one_locked(self) -> bool:
        """Demote the oldest host page to the disk tier to make room.
        Returns False when there is nothing to spill or no disk store."""
        if self.disk is None or not self._host:
            return False
        key = next(iter(self._host))
        tree, nbytes = self._host[key]
        # claim disk capacity first (refusal -> BudgetExceeded before any
        # bytes move), then write; roll the move back if the write fails
        self.memory.move("host", "disk", nbytes, self.owner)
        try:
            self.disk.put(key, tree)      # op kv_d2disk under the policy
        except BaseException:
            self.memory.move("disk", "host", nbytes, self.owner)
            raise
        del self._host[key]
        self._disk_keys[key] = nbytes
        self.spilled_pages += 1
        return True

    def offload(self, h, tree: Params) -> None:
        if self.quant:                    # quantize-on-write: host/disk
            tree = quantize_page(tree)    # hold the int8 + scale bytes
        nbytes = sum(np.asarray(a).nbytes
                     for a in jax.tree.leaves(tree))

        def put():
            if self.injector is not None:
                self.injector.check("kv_d2h")
            # enforce the host budget: spill cold pages to disk until the
            # lease fits; a refusal with no disk room left surfaces as
            # BudgetExceeded (transient under the policy — a finishing
            # slot may free host bytes before the retries exhaust)
            with self._cv:
                while not self.memory.try_lease("host", nbytes,
                                                self.owner):
                    if not self._spill_one_locked():
                        st = self.memory.stats()["host"]
                        raise BudgetExceeded(
                            f"KV offload of {nbytes} B refused: host "
                            f"tier {st.used}/{st.capacity} B used and "
                            f"no disk tier to spill to",
                            tier="host", requested=nbytes, used=st.used,
                            capacity=st.capacity or 0)
            return nbytes

        # the D2H copy happened in the eviction callback; this commits the
        # host store (and is where an injected kv_d2h fault surfaces) —
        # transient faults retry under the shared policy
        t0 = clock()
        self.policy.run("kv_d2h", put, health=self.health)
        self.tracer.span_event(f"kv_d2h[{h}]", t0, clock(), cat="kv",
                               track="kv-offloader", nbytes=nbytes)
        with self._cv:
            self._host[h] = (tree, nbytes)
            self.offloaded_bytes += nbytes

    def holds(self, h) -> bool:
        with self._cv:
            return h in self._host or h in self._disk_keys

    # -- fetch side -------------------------------------------------------- #

    def schedule(self, h: int) -> None:
        with self._cv:
            if h in self._staged or h in self._queue:
                return
            self._queue.append(h)
            self._cv.notify_all()

    def get(self, h: int, *, timeout: Optional[float] = None) -> Params:
        if timeout is None:
            timeout = self.policy.get_timeout_s
        t_enter = clock()
        deadline = t_enter + timeout
        with self.tracer.phase("h2d", cat="kv", track="decode",
                               min_dur=2e-4, label="kv_wait"):
            with self._cv:
                while h not in self._staged:
                    if self._error is not None:
                        raise RuntimeError(
                            f"offload fetch of page hash {h} failed "
                            f"({self.health.report()})") from self._error
                    if self._stop:
                        raise RuntimeError(
                            "offloader stopped" + (
                                " (worker interrupted)"
                                if self._interrupted else ""))
                    remaining = deadline - clock()
                    if remaining <= 0:
                        self.health.stalled = True
                        raise StallTimeout(
                            f"offloaded page not staged within "
                            f"{timeout:.1f}s "
                            f"({self.health.report()})", op="kv_h2d")
                    self._cv.wait(min(remaining, 0.25))
                staged = self._staged.pop(h)
                ent = self._host.pop(h, None)   # back on device
                if ent is not None:             # host copy done: unlease
                    self.memory.release("host", ent[1], self.owner)
                self.stall_s += clock() - t_enter
                return staged

    def stats(self) -> PrefetchStats:
        """Uniform ``PrefetchStats`` view — the same surface the layer
        and ring-bank prefetchers expose, so stall/retry counters from
        all three staging paths read identically in reports."""
        with self._cv:
            events = list(self.events)
            fetched = self.fetched_bytes
        return PrefetchStats(
            events=events, peak_resident_bytes=0,
            total_bytes_read=fetched, stall_s=self.stall_s,
            layers_served=len(events), releases=0,
            retries=self.health.retries,
            budget_refusals=sum(s.refusals
                                for s in self.memory.stats().values()))

    def close(self, timeout: float = 5.0) -> bool:
        """Stop the worker (idempotent); True once it has joined, False
        with a logged stall report if it is stuck. Host copies hand
        their leases back so a shared budget balances after shutdown."""
        with self._cv:
            self._closed = True
            self._stop = True
            self._cv.notify_all()
        self._thread.join(timeout=timeout)
        if self._thread.is_alive():
            self.health.stalled = True
            log.error("BlockOffloader.close: worker failed to join "
                      "within %.1fs — %s", timeout, self.health.report())
            return False
        with self._cv:
            for h in list(self._host):
                _, nbytes = self._host.pop(h)
                self.memory.release("host", nbytes, self.owner)
            for h in list(self._disk_keys):
                self.memory.release("disk", self._disk_keys.pop(h),
                                    self.owner)
        self.health.closed = True
        return True


# --------------------------------------------------------------------------- #
#  paged cache manager
# --------------------------------------------------------------------------- #

@dataclasses.dataclass
class KVStats:
    """Allocator + traffic view of a paged-cache run (benchmarks/gates)."""

    n_pages: int
    page_tokens: int
    page_bytes: int                   # one page across all layers/leaves
    active_pages_highwater: int       # max simultaneously-referenced pages
    active_tokens_highwater: int      # max live tokens across slots
    prefix_hits: int                  # pages obtained by hash match
    cow_copies: int
    evictions: int
    offloaded_bytes: int
    fetched_bytes: int
    fetch_events: List[PrefetchEvent]
    fetch_stall_s: float = 0.0        # admits blocked on a staging fetch
    fetch_retries: int = 0            # transient I/O retries (IOPolicy)
    disk_bytes_written: int = 0       # kv_d2disk traffic (spills + parks)
    disk_bytes_read: int = 0          # kv_disk2h traffic (recalls)
    spilled_pages: int = 0            # host pages demoted to disk
    fetched_disk_pages: int = 0       # prefix recalls served from disk
    parked_sessions: int = 0          # lifetime park count
    restored_sessions: int = 0        # lifetime restore count
    budget_refusals: int = 0          # tier leases the budget refused
    table_syncs: int = 0              # block_table/len round trips

    @property
    def highwater_bytes(self) -> int:
        return self.active_pages_highwater * self.page_bytes

    def dense_bytes(self, batch: int, max_len: int) -> int:
        """What the dense (L, B, max_len, ...) preallocation would hold."""
        per_tok = self.page_bytes / max(self.page_tokens, 1)
        return int(batch * max_len * per_tok)


def paged_cache_spec(cfg) -> Dict[str, Tuple[int, ...]]:
    """Per-leaf trailing shapes of one cache line (one token, one layer)."""
    if cfg.family not in ("dense", "moe", "vlm"):
        raise ValueError(
            f"paged KV cache unsupported for family {cfg.family} "
            "(recurrent state has no per-token pages)")
    if cfg.mla:
        if cfg.kv_dtype == "int8":
            raise NotImplementedError(
                "paged MLA latent storage does not support int8 "
                "quantization (the latent is already compressed)")
        return {"latent": (cfg.kv_lora_rank + cfg.qk_rope_dim,)}
    hk, hd = max(cfg.kv_heads, 1), cfg.head_dim
    if cfg.kv_dtype == "int8":
        # int8 K/V plus per-(position, kv-head) scales
        # (``layers.quantize_kv`` convention) — the paged flash kernels
        # read the quantized leaves directly, dequant fused.
        return {"k": (hk, hd), "v": (hk, hd),
                "k_scale": (hk,), "v_scale": (hk,)}
    return {"k": (hk, hd), "v": (hk, hd)}


def paged_leaf_dtype(name: str, cfg, pool_dtype):
    """Storage dtype of a paged-cache leaf: int8 for quantized K/V,
    the pool dtype for everything else (scales included)."""
    if cfg.kv_dtype == "int8" and name in ("k", "v"):
        return jnp.int8
    return pool_dtype


@dataclasses.dataclass
class ParkedSession:
    """A session's KV lifted off the device tier between requests.

    ``tier == "host"``: ``pages`` holds the np page trees. ``tier ==
    "disk"``: pages live in per-session :class:`PageFileStore` files
    (keys ``("sess", session, j)``) and ``pages`` is None. ``meta`` is
    an opaque engine blob (resume token) returned verbatim on restore —
    the cache parks bytes, not scheduling state.
    """

    session: str
    length: int
    n_pages: int
    nbytes: int
    tier: str
    pages: Optional[List[Params]]
    meta: dict
    parked_t: float


class PagedKVCache:
    """Owner of the block pool + per-slot page lists for a serving batch.

    The device arrays live in the cache pytree this class *builds* but
    does not hold: every mutating method threads the cache through
    functionally, so the engine's usual ``cache = f(cache, ...)`` flow is
    preserved and jit boundaries see plain arrays.

    cache = {
      "pages":       {leaf: (L, P, page_tokens, ...)},
      "block_table": (B, max_pages_per_slot) int32,
      "len":         (B,) int32,
    }

    Tiered-memory integration (``memory``): the whole pool allocation
    leases from the shared ``device`` tier at construction (``n_pages``
    may be omitted and is then derived from the device budget), the
    offloader's host copies lease from ``host``, and the disk tier
    (``disk_dir``) holds spilled prefix pages plus **parked sessions**:
    ``park_session`` lifts an idle slot's pages off the device
    (host first, demoted to per-session page files by ``sweep_parked``
    after ``park_idle_s`` seconds), and ``restore_session`` brings them
    back byte-identically on the session's next request.
    """

    def __init__(self, cfg, *, batch: int, ctx: int,
                 n_pages: Optional[int] = None,
                 page_tokens: int = 16, dtype=jnp.float32,
                 offload: bool = True,
                 io_policy: Optional[IOPolicy] = None, injector=None,
                 tracer=None, memory: Optional[TierManager] = None,
                 evict_policy: str = "lru", offload_quant: bool = False,
                 disk_dir: Optional[str] = None,
                 park_idle_s: Optional[float] = None,
                 recall_costs=None):
        self.cfg = cfg
        self.B = batch
        self.page_tokens = page_tokens
        self.max_pages = -(-ctx // page_tokens)
        self.ctx = self.max_pages * page_tokens
        self._spec = paged_cache_spec(cfg)
        self.dtype = dtype
        self.tracer = tracer or NULL_TRACER
        self.memory = memory if memory is not None \
            else TierManager(tracer=tracer, name="kv-memory")
        if n_pages is None:
            avail = self.memory.available("device")
            if avail is None:
                raise ValueError(
                    "n_pages omitted: pass a memory manager with a "
                    "device budget to derive the pool size from it")
            n_pages = max(int(avail // max(self.page_bytes, 1)), 2)
        if recall_costs is None:
            from ..core.latency import kv_recall_costs
            recall_costs = kv_recall_costs(self.page_bytes)
        self.recall_costs = recall_costs
        self.pool = BlockPool(
            n_pages, page_tokens, evict_policy=evict_policy,
            recall_cost_fn=self._recall_cost
            if evict_policy == "cost" else None)
        # the pool array is one fixed device allocation — lease it whole
        # (construction fails loudly if the budget cannot hold it)
        self._pool_lease = n_pages * self.page_bytes
        self.memory.lease("device", self._pool_lease, "kv")
        self.disk = PageFileStore(disk_dir, policy=io_policy,
                                  injector=injector, tracer=tracer) \
            if disk_dir else None
        np_dtype = np.dtype(jnp.zeros((), dtype).dtype)
        self.offloader = BlockOffloader(policy=io_policy,
                                        injector=injector,
                                        tracer=tracer,
                                        memory=self.memory,
                                        disk=self.disk,
                                        quant=offload_quant,
                                        page_dtype=np_dtype) \
            if offload else None
        self.park_idle_s = park_idle_s
        self._parked: Dict[str, ParkedSession] = {}
        self.parked_count = 0
        self.restored_count = 0
        # host mirrors
        self._slot_pages: List[List[int]] = [[] for _ in range(batch)]
        self._len = [0] * batch
        #: worst-case page budget reserved per live slot (admission
        #: control): with sum(reserved) <= usable pages, per-step growth
        #: and CoW can always be satisfied from free + evictable pages,
        #: so decode never dies mid-step — exhaustion is an admit-time
        #: signal the engine can defer on.
        self._reserved = [0] * batch
        self._usable = n_pages - 1
        self._dirty = set(range(batch))          # table rows to (re)write
        #: slot -> [(page kind, content key)] for the admit in flight
        #: between plan_admit and install ("shared"|"fetched"|"fresh")
        self._admit_meta: Dict[int, List[Tuple[str, Any]]] = {}
        #: slots mid chunked admission: their device table row stays all
        #: sink (decode steps interleaved between chunks must not write
        #: into the half-filled real pages); chunk steps address the
        #: pages through a private ``chunk_table`` row instead
        self._chunking: set = set()
        # stats
        self._active_pages_hw = 0
        self._active_tokens_hw = 0
        self.prefix_hits = 0
        self.cow_copies = 0
        #: device round trips of the block table and lengths
        #: (``_sync_tables`` with a dirty slot)
        self.table_syncs = 0

    # -- construction ------------------------------------------------------ #

    def init_cache(self) -> Dict[str, Any]:
        L = self.cfg.n_layers
        P, bs = self.pool.n_pages, self.page_tokens
        pages = {name: jnp.zeros((L, P, bs) + trail,
                                 paged_leaf_dtype(name, self.cfg,
                                                  self.dtype))
                 for name, trail in self._spec.items()}
        return {"pages": pages,
                "block_table": jnp.zeros((self.B, self.max_pages),
                                         jnp.int32),
                "len": jnp.zeros((self.B,), jnp.int32)}

    @property
    def page_bytes(self) -> int:
        L, bs = self.cfg.n_layers, self.page_tokens
        return sum(
            L * bs * int(np.prod(trail, dtype=np.int64))
            * jnp.zeros((), paged_leaf_dtype(name, self.cfg, self.dtype)
                        ).dtype.itemsize
            for name, trail in self._spec.items())

    # -- stats ------------------------------------------------------------- #

    def _note_highwater(self) -> None:
        self._active_pages_hw = max(self._active_pages_hw,
                                    self.pool.n_active)
        self._active_tokens_hw = max(self._active_tokens_hw,
                                     sum(self._len))

    def stats(self) -> KVStats:
        off = self.offloader
        return KVStats(
            n_pages=self.pool.n_pages, page_tokens=self.page_tokens,
            page_bytes=self.page_bytes,
            active_pages_highwater=self._active_pages_hw,
            active_tokens_highwater=self._active_tokens_hw,
            prefix_hits=self.prefix_hits, cow_copies=self.cow_copies,
            evictions=self.pool.evictions,
            offloaded_bytes=off.offloaded_bytes if off else 0,
            fetched_bytes=off.fetched_bytes if off else 0,
            fetch_events=list(off.events) if off else [],
            fetch_stall_s=off.stall_s if off else 0.0,
            fetch_retries=off.health.retries if off else 0,
            disk_bytes_written=self.disk.written_bytes if self.disk else 0,
            disk_bytes_read=self.disk.read_bytes if self.disk else 0,
            spilled_pages=off.spilled_pages if off else 0,
            fetched_disk_pages=off.fetched_disk_pages if off else 0,
            parked_sessions=self.parked_count,
            restored_sessions=self.restored_count,
            budget_refusals=sum(
                s.refusals for s in self.memory.stats().values()),
            table_syncs=self.table_syncs)

    # -- cost-model eviction pricing --------------------------------------- #

    def _recall_cost(self, h) -> float:
        """Modeled seconds to recall page ``h`` if evicted now — the
        ``core.latency.kv_recall_costs`` term for the tier eviction
        would land it in (host normally; disk when it already lives
        there or the host tier has no room left)."""
        if self.offloader is None:
            return self.recall_costs.disk_s      # content would be lost
        if self.disk is not None:
            if self.disk.holds(h):
                return self.recall_costs.disk_s
            avail = self.memory.available("host")
            if avail is not None and avail < self.page_bytes:
                return self.recall_costs.disk_s  # eviction would spill
        return self.recall_costs.host_s

    # -- page content ops (functional on the cache) ------------------------ #

    def _evict_cb(self, cache):
        """Eviction hook: offload the page's bytes to host before reuse."""
        if self.offloader is None:
            return None

        def cb(pid, h):
            tree = {name: np.asarray(arr[:, pid])
                    for name, arr in cache["pages"].items()}
            self.offloader.offload(h, tree)
        return cb

    def _copy_page(self, cache, src: int, dst: int):
        pages = {name: arr.at[:, dst].set(arr[:, src])
                 for name, arr in cache["pages"].items()}
        return {**cache, "pages": pages}

    def _scatter_pages(self, cache, pids: List[int],
                       trees: List[Params]):
        """Write page contents (``trees[i]``: {leaf: (L, bs, ...)}) into
        pool positions ``pids`` — ONE batched update per leaf, so an
        n-page admit costs one pool-array copy instead of n."""
        if not pids:
            return cache
        idx = jnp.asarray(pids, jnp.int32)
        pages = dict(cache["pages"])
        for name in pages:
            stacked = jnp.stack([jnp.asarray(t[name]) for t in trees],
                                axis=1)
            pages[name] = pages[name].at[:, idx].set(
                stacked.astype(pages[name].dtype))
        return {**cache, "pages": pages}

    def _sync_tables(self, cache):
        """Write dirty slots' page lists (and lengths) into the device
        cache. Runs before the decode writes of a step, when the host
        mirror and the device counter agree for every live slot."""
        if not self._dirty:
            return cache
        self.table_syncs += 1
        with self.tracer.span("kv.sync_tables", cat="kv", track="decode",
                              slots=len(self._dirty)):
            table = np.asarray(cache["block_table"]).copy()
            lens = np.asarray(cache["len"]).copy()
            for slot in self._dirty:
                row = np.full((self.max_pages,), SINK_PAGE, np.int32)
                if slot not in self._chunking:   # mid-chunk: stay masked
                    pids = self._slot_pages[slot][:self.max_pages]
                    row[:len(pids)] = pids
                table[slot] = row
                lens[slot] = 0 if slot in self._chunking \
                    else self._len[slot]
            self._dirty.clear()
            return {**cache, "block_table": jnp.asarray(table),
                    "len": jnp.asarray(lens)}

    # -- admit ------------------------------------------------------------- #

    def can_ever_admit(self, prompt_len: int, max_new: int) -> bool:
        """Could this request be admitted into an *empty* pool?

        False means deferral is pointless — no amount of completed slots
        frees enough pages — so the engine sheds the request immediately
        with a clear "pool too small" error instead of starving it.
        """
        total = prompt_len + max_new
        if total > self.ctx:
            return False
        worst = -(-total // self.page_tokens) + 1
        return worst <= self._usable

    def plan_admit(self, cache, slot: int, prompt: Sequence[int],
                   max_new: int, *, register: bool = True
                   ) -> Dict[str, int]:
        """Reserve pages for a prompt: prefix-share where hashes match,
        schedule background fetches for offloaded matches, allocate the
        rest (the alloc-on-demand half of the admit contract — the only
        rejections are a request too long for the slot table and pool
        exhaustion, both with clear errors).

        Runs *before* the prefill compute so offload fetches overlap it;
        ``install`` collects them afterwards. ``cache`` is read-only here
        (eviction offload copies page bytes device->host).

        ``register=False`` defers hash registration of fresh pages to
        ``finish_chunked_admit``: chunked admission fills them over many
        interleaved steps, and a concurrent admit must not prefix-share
        a page whose bytes are not all there yet. (The dense path fills
        pages in one ``install`` with no interleaving, so it registers
        eagerly and keeps the plan-to-install overlap.)
        """
        with self.tracer.span("kv.plan_admit", cat="kv", track="decode",
                              slot=slot):
            bs = self.page_tokens
            S, total = len(prompt), len(prompt) + max_new
            if total > self.ctx:
                raise ValueError(
                    f"request needs {total} positions (prompt {S} + max_new "
                    f"{max_new}) but the paged slot addresses only "
                    f"{self.ctx} ({self.max_pages} pages x {bs} tokens)")
            if self._slot_pages[slot]:
                raise RuntimeError(f"slot {slot} already holds pages")
            # worst-case lifetime pages: every position paged, +1 for the
            # copy-on-write clone of a shared divergence page
            worst = -(-total // bs) + 1
            committed = sum(self._reserved) + worst
            if committed > self._usable:
                raise PoolExhausted(
                    f"KV block pool exhausted: admitting would oversubscribe "
                    f"{committed}/{self._usable} pages "
                    f"({sum(1 for r in self._reserved if r)} slots live)")
            n_blocks = -(-S // bs)
            pids: List[int] = []
            meta: List[Tuple[str, Any]] = []
            h: tuple = ()
            try:
                for j in range(n_blocks):
                    toks = prompt[j * bs:(j + 1) * bs]
                    h = chain_key(h, toks, len(toks))
                    pid = self.pool.lookup(h)
                    if pid is not None:                      # resident hit
                        self.pool.retain(pid)
                        kind = "shared"
                    elif self.offloader is not None \
                            and self.offloader.holds(h):
                        pid = self.pool.alloc(evict_cb=self._evict_cb(cache))
                        self.offloader.schedule(h)
                        self.pool.register(h, pid)
                        self.pool.note_hit(h)    # off-device reuse: same
                        kind = "fetched"         # frequency signal as lookup
                    else:
                        pid = self.pool.alloc(evict_cb=self._evict_cb(cache))
                        if register:
                            self.pool.register(h, pid)
                        kind = "fresh"
                    pids.append(pid)
                    meta.append((kind, h))
            except PoolExhausted:
                # roll the reservation back whole: pages registered for this
                # admit were never filled, so they must not survive into the
                # prefix cache
                for pid, (kind, _) in zip(pids, meta):
                    if kind != "shared":
                        self.pool.unregister(pid)
                    self.pool.release(pid)
                raise
            self.prefix_hits += sum(1 for k, _ in meta if k != "fresh")
            self._slot_pages[slot] = pids
            self._admit_meta[slot] = meta
            self._reserved[slot] = worst
            self._dirty.add(slot)
            return {k: sum(1 for kk, _ in meta if kk == k)
                    for k in ("shared", "fetched", "fresh")}

    def abort_admit(self, slot: int) -> None:
        """Undo a ``plan_admit`` whose prefill failed: return the slot's
        pages (un-registering never-filled ones so they cannot enter the
        prefix cache) and drop its reservation. The engine calls this on
        any error between plan and install."""
        meta = self._admit_meta.pop(slot, None)
        if meta is None:
            return
        for pid, (kind, _) in zip(self._slot_pages[slot], meta):
            if kind != "shared":
                self.pool.unregister(pid)
            self.pool.release(pid)
        self._slot_pages[slot] = []
        self._reserved[slot] = 0
        self._len[slot] = 0
        self._chunking.discard(slot)
        self._dirty.add(slot)

    def install(self, cache, slot: int, slot_layers: Params,
                length: int) -> Dict[str, Any]:
        """Scatter a freshly-prefilled sequence's KV into its pages.

        ``slot_layers``: the per-layer cache of a single-sequence prefill
        (leaves ``(L, 1, S_cap, ...)``). Pages obtained by prefix share
        are skipped — their bytes are already correct and rewriting them
        would defeat the point; offloaded matches are collected from the
        staging thread here, after the prefill compute they overlapped.
        """
        bs = self.page_tokens
        meta = self._admit_meta.pop(slot)
        pids_w: List[int] = []
        trees: List[Params] = []
        for j, (pid, (kind, h)) in enumerate(
                zip(self._slot_pages[slot], meta)):
            if kind == "shared":
                continue
            if kind == "fetched":
                pids_w.append(pid)
                trees.append(self.offloader.get(h))
                continue
            lo = j * bs
            blk = {}
            for name, arr in slot_layers.items():
                # slice/pad on device: no host round-trip of prompt KV
                piece = jnp.asarray(arr)[:, 0, lo:lo + bs]
                if piece.shape[1] < bs:                   # partial page
                    pad = [(0, 0)] * piece.ndim
                    pad[1] = (0, bs - piece.shape[1])
                    piece = jnp.pad(piece, pad)
                blk[name] = piece
            pids_w.append(pid)
            trees.append(blk)
        cache = self._scatter_pages(cache, pids_w, trees)
        self._len[slot] = length
        self._dirty.add(slot)
        cache = self._sync_tables(cache)
        self._note_highwater()
        return cache

    # -- chunked admission (prompt KV computed straight into pages) --------- #

    def begin_chunked_admit(self, cache, slot: int, prompt_len: int
                            ) -> Tuple[Dict[str, Any], int]:
        """Prepare a planned admit (``plan_admit(register=False)``) for
        chunk-direct writes: collect offloaded prefix matches into their
        device pages now (chunk attention reads them, so the fetch can
        no longer overlap the whole prefill), compute how many leading
        prompt tokens are already materialized (shared + fetched prefix
        — chunk compute starts after them), and mask the slot's device
        table row (all sink, len 0) so decode steps interleaved between
        chunks cannot write into the half-filled pages. Chunk steps
        address the pages through ``chunk_table`` instead.

        Returns ``(cache, skip_tokens)``.
        """
        meta = self._admit_meta[slot]
        pids = self._slot_pages[slot]
        pids_w: List[int] = []
        trees: List[Params] = []
        for pid, (kind, h) in zip(pids, meta):
            if kind == "fetched":
                pids_w.append(pid)
                trees.append(self.offloader.get(h))
        cache = self._scatter_pages(cache, pids_w, trees)
        skip = 0
        for kind, _ in meta:
            if kind == "fresh":
                break
            skip += 1
        skip_tokens = prompt_len if skip >= len(meta) \
            else skip * self.page_tokens
        self._chunking.add(slot)
        self._dirty.add(slot)
        cache = self._sync_tables(cache)
        return cache, skip_tokens

    def chunk_table(self, slot: int) -> np.ndarray:
        """(1, max_pages) int32 block-table row for chunk steps of a
        mid-admission slot (its row in the shared device table is masked
        until ``finish_chunked_admit``)."""
        row = np.full((1, self.max_pages), SINK_PAGE, np.int32)
        pids = self._slot_pages[slot][:self.max_pages]
        row[0, :len(pids)] = pids
        return row

    def finish_chunked_admit(self, cache, slot: int, length: int
                             ) -> Dict[str, Any]:
        """Complete a chunked admit: the prompt's KV is fully in pages,
        so register the fresh pages' content keys (future admits may now
        prefix-share them) and unmask the slot's table row."""
        meta = self._admit_meta.pop(slot)
        for pid, (kind, h) in zip(self._slot_pages[slot], meta):
            if kind == "fresh":
                self.pool.register(h, pid)
        self._chunking.discard(slot)
        self._len[slot] = length
        self._dirty.add(slot)
        cache = self._sync_tables(cache)
        self._note_highwater()
        return cache

    # -- per-step maintenance ---------------------------------------------- #

    def begin_step(self, cache, active: Sequence[int], n_tokens: int
                   ) -> Dict[str, Any]:
        """Make the next ``n_tokens`` positions of every active slot
        writable: grow page lists across boundaries, copy-on-write shared
        pages in the write range, unregister hashes of private pages
        about to be written, and flush table/len cleanup of freed slots.
        """
        with self.tracer.span("kv.begin_step", cat="kv", track="decode"):
            bs = self.page_tokens
            for slot in active:
                ln = self._len[slot]
                need = -(-(ln + n_tokens) // bs)
                if need > self.max_pages:
                    raise PoolExhausted(
                        f"slot {slot} needs {need} pages "
                        f"(len {ln} + {n_tokens}) > table width "
                        f"{self.max_pages}")
                pids = self._slot_pages[slot]
                while len(pids) < need:
                    pids.append(self.pool.alloc(
                        evict_cb=self._evict_cb(cache)))
                    self._dirty.add(slot)
                first_blk = ln // bs
                last_blk = (ln + n_tokens - 1) // bs
                for j in range(first_blk, last_blk + 1):
                    pid = pids[j]
                    if self.pool.refcount(pid) > 1:           # divergence: CoW
                        new = self.pool.alloc(evict_cb=self._evict_cb(cache))
                        cache = self._copy_page(cache, pid, new)
                        self.pool.release(pid)
                        pids[j] = new
                        self.cow_copies += 1
                        self._dirty.add(slot)
                    else:
                        self.pool.unregister(pid)     # content will change
            cache = self._sync_tables(cache)
            self._note_highwater()
            return cache

    def advance(self, slot: int, n: int = 1) -> None:
        """Commit ``n`` generated tokens (vanilla decode bookkeeping)."""
        self._len[slot] += n

    def length(self, slot: int) -> int:
        """Host-mirrored valid length of ``slot`` (== device ``len`` at
        step boundaries)."""
        return self._len[slot]

    def trim_to(self, slot: int, new_len: int) -> None:
        """Speculative rollback: keep pages covering ``new_len`` tokens,
        free the rest (rejected drafts past the accepted length)."""
        bs = self.page_tokens
        keep = -(-new_len // bs) if new_len > 0 else 0
        pids = self._slot_pages[slot]
        for pid in pids[keep:]:
            self.pool.release(pid)
        if len(pids) > keep:
            del pids[keep:]
            self._dirty.add(slot)
        self._len[slot] = new_len

    def release_slot(self, slot: int) -> None:
        """Finished sequence: drop its references. Hashed prompt pages
        fall into the LRU prefix cache for future admits; private pages
        return to the free list. Table/len cleanup is applied lazily at
        the next ``begin_step`` (stale rows only ever feed the masked
        region until then)."""
        for pid in self._slot_pages[slot]:
            self.pool.release(pid)
        self._slot_pages[slot] = []
        self._len[slot] = 0
        self._reserved[slot] = 0
        self._dirty.add(slot)

    # -- session parking (disk-tier resumable sessions) --------------------- #

    @property
    def parking(self) -> bool:
        """Whether session parking is configured (``park_idle_s``)."""
        return self.park_idle_s is not None

    def is_parked(self, session: str) -> bool:
        return session in self._parked

    def _session_key(self, session: str, j: int) -> tuple:
        return ("sess", session, j)

    def _write_session_files(self, session: str,
                             trees: List[Params]) -> None:
        for j, tree in enumerate(trees):
            self.disk.put(self._session_key(session, j), tree)

    def _drop_session_files(self, session: str, n: int) -> None:
        for j in range(n):
            self.disk.drop(self._session_key(session, j))

    def park_session(self, cache, slot: int, session: str,
                     meta: dict) -> None:
        """Lift ``slot``'s pages off the device tier under ``session``.

        Copies every page's bytes to leased host buffers (or straight
        to per-session disk files when the host tier refuses) and frees
        the device pages — the slot is immediately reusable. ``meta``
        (the engine's resume token) rides along and comes back verbatim
        from :meth:`restore_session`. Parking is always lossless:
        quantize-on-write applies only to the offloader's prefix tier,
        never here, so the restored token stream is byte-identical.
        Raises :class:`BudgetExceeded` when neither host nor disk can
        hold the session (the caller drops it instead of overshooting).
        """
        if session in self._parked:      # stale park: a newer request
            self._drop_parked(session)   # supersedes the old KV
        pids = self._slot_pages[slot]
        trees = [{name: np.asarray(arr[:, pid])
                  for name, arr in cache["pages"].items()}
                 for pid in pids]
        nbytes = sum(a.nbytes for t in trees for a in t.values())
        if self.memory.try_lease("host", nbytes, "kv"):
            tier = "host"
        else:
            if self.disk is None:
                st = self.memory.stats()["host"]
                raise BudgetExceeded(
                    f"cannot park session {session!r}: host tier "
                    f"{st.used}/{st.capacity} B used and no disk tier",
                    tier="host", requested=nbytes, used=st.used,
                    capacity=st.capacity or 0)
            self.memory.lease("disk", nbytes, "kv")   # BudgetExceeded ok
            try:
                self._write_session_files(session, trees)
            except BaseException:
                self.memory.release("disk", nbytes, "kv")
                raise
            tier = "disk"
        self._parked[session] = ParkedSession(
            session=session, length=self._len[slot], n_pages=len(pids),
            nbytes=nbytes, tier=tier,
            pages=trees if tier == "host" else None, meta=dict(meta),
            parked_t=clock())
        self.parked_count += 1
        self.release_slot(slot)    # device pages free; prompt pages may
        self._note_highwater()     # still serve the prefix cache

    def sweep_parked(self) -> int:
        """Demote host-parked sessions idle for ``park_idle_s`` seconds
        to per-session disk page files; returns sessions demoted. A full
        disk tier leaves a session on host (retried next sweep)."""
        if not self.parking or self.disk is None:
            return 0
        now = clock()
        n = 0
        for ps in self._parked.values():
            if ps.tier != "host" or now - ps.parked_t < self.park_idle_s:
                continue
            try:
                self.memory.move("host", "disk", ps.nbytes, "kv")
            except BudgetExceeded:
                continue                 # disk full: stay on host
            try:
                self._write_session_files(ps.session, ps.pages)
            except BaseException:
                self.memory.move("disk", "host", ps.nbytes, "kv")
                raise
            ps.tier = "disk"
            ps.pages = None
            n += 1
        return n

    def restore_session(self, cache, slot: int, session: str, *,
                        max_new: int):
        """Bring a parked session's pages back onto the device into
        ``slot``; returns ``(cache, meta, length)`` with ``meta`` the
        blob ``park_session`` recorded. Restored bytes are identical to
        the parked bytes (host copies or disk page files — both
        lossless), so decode continues exactly where it left off.
        Raises ``PoolExhausted`` (the session stays parked) when the
        pool cannot hold it right now — the engine's deferral path.
        """
        ps = self._parked[session]
        bs = self.page_tokens
        total = ps.length + max_new
        if total > self.ctx:
            raise ValueError(
                f"session {session!r} needs {total} positions "
                f"(parked len {ps.length} + max_new {max_new}) but the "
                f"paged slot addresses only {self.ctx}")
        if self._slot_pages[slot]:
            raise RuntimeError(f"slot {slot} already holds pages")
        worst = -(-total // bs) + 1
        committed = sum(self._reserved) + worst
        if committed > self._usable:
            raise PoolExhausted(
                f"KV block pool exhausted: restoring session "
                f"{session!r} would oversubscribe "
                f"{committed}/{self._usable} pages")
        pids: List[int] = []
        try:
            for _ in range(ps.n_pages):
                pids.append(self.pool.alloc(
                    evict_cb=self._evict_cb(cache)))
        except PoolExhausted:
            for pid in pids:
                self.pool.release(pid)
            raise                        # still parked; admit defers
        if ps.tier == "host":
            trees = ps.pages
        else:
            trees = [self.disk.get(self._session_key(session, j))
                     for j in range(ps.n_pages)]   # op kv_disk2h
        cache = self._scatter_pages(cache, pids, trees)
        self._slot_pages[slot] = pids
        self._len[slot] = ps.length
        self._reserved[slot] = worst
        self._dirty.add(slot)
        cache = self._sync_tables(cache)
        del self._parked[session]
        self.memory.release(ps.tier, ps.nbytes, "kv")
        if ps.tier == "disk":
            self._drop_session_files(session, ps.n_pages)
        self.restored_count += 1
        self._note_highwater()
        return cache, ps.meta, ps.length

    def _drop_parked(self, session: str) -> None:
        ps = self._parked.pop(session, None)
        if ps is None:
            return
        self.memory.release(ps.tier, ps.nbytes, "kv")
        if ps.tier == "disk":
            self._drop_session_files(session, ps.n_pages)

    def close(self) -> None:
        for session in list(self._parked):
            self._drop_parked(session)
        if self.offloader is not None:
            self.offloader.close()
        if self.disk is not None:
            self.disk.close()
        if self._pool_lease:           # idempotent: lease returns once
            self.memory.release("device", self._pool_lease, "kv")
            self._pool_lease = 0


# --------------------------------------------------------------------------- #
#  continuous-batching integration
# --------------------------------------------------------------------------- #

# module-level jits so the compile cache is shared across engine builds
# (benchmarks tear engines down between scenarios; warmup must survive).
# ``cfg`` is a frozen dataclass -> hashable static; ``write`` selects the
# prefix-hit replay variant that must not touch pages.
@functools.partial(jax.jit, static_argnames=("cfg",))
def _decode_paged_jit(params, cfg, cache, tokens):
    from ..models import model as M

    return M.decode_step_paged(params, cfg, cache, tokens)


@functools.partial(jax.jit, static_argnames=("cfg", "write"))
def _prefill_chunk_jit(params, cfg, view, tokens, write):
    from ..models import model as M

    return M.prefill_chunk_paged(params, cfg, view, tokens, write=write)


def make_paged_engine(params, cfg, batch: int, ctx: int, *,
                      n_pages: Optional[int] = None,
                      page_tokens: int = 16, eos_id: Optional[int] = None,
                      spec=None, offload: bool = True,
                      cache_dtype=jnp.float32,
                      io_policy: Optional[IOPolicy] = None,
                      injector=None, tracer=None,
                      memory: Optional[TierManager] = None,
                      evict_policy: str = "lru",
                      offload_quant: bool = False,
                      disk_dir: Optional[str] = None,
                      park_idle_s: Optional[float] = None,
                      prefill_chunk: Optional[int] = None,
                      metrics=None):
    """Build a ``ContinuousBatcher`` over a paged KV cache.

    Returns ``(engine, kv)``; drive it with ``engine.run(kv.init_cache(),
    requests)``. The decode step is ``models.decode_step_paged`` — greedy
    output is byte-identical to the dense engine's, only where KV lives
    changes.

    ``prefill_chunk``: admit prompts in chunks of this many tokens
    (rounded to a page multiple), computed straight into the slot's
    pages (``models.prefill_chunk_paged``) and interleaved with decode
    steps for the already-active slots — a long admit no longer stalls
    every decoding stream for its whole prefill. Token streams stay
    byte-identical to the one-shot dense prefill. None = classic
    dense-scratch prefill + scatter install.
    """
    from ..models import model as M
    from .engine import ContinuousBatcher

    kv = PagedKVCache(cfg, batch=batch, ctx=ctx, n_pages=n_pages,
                      page_tokens=page_tokens, dtype=cache_dtype,
                      offload=offload, io_policy=io_policy,
                      injector=injector, tracer=tracer, memory=memory,
                      evict_policy=evict_policy,
                      offload_quant=offload_quant, disk_dir=disk_dir,
                      park_idle_s=park_idle_s)

    def prefill_one(prompt):
        c1 = M.init_cache(cfg, 1, ctx, dtype=cache_dtype)
        logits, c1 = M.prefill(params, cfg, prompt, c1)
        return int(jnp.argmax(logits[0, -1])), c1

    def decode(cache, tokens):
        # jitted steady-state step: the paged hot path runs compiled,
        # not op-by-op (the one-shot dense-scratch prefill stays eager —
        # chunked admission is the fast path that replaces it)
        return _decode_paged_jit(params, cfg, cache, tokens)

    def chunk_step(view, tokens, write=True):
        return _prefill_chunk_jit(params, cfg, view, tokens, write)

    def write_slot(cache, slot_cache, slot, length):   # paged: kv.install
        raise RuntimeError("paged engine installs via kv, not write_slot")

    if prefill_chunk is not None:
        # page-sized chunks: chunk boundaries must align with page
        # boundaries so fresh pages are filled whole before a future
        # admit may share them
        prefill_chunk = max(prefill_chunk // page_tokens, 1) * page_tokens
    eng = ContinuousBatcher(batch, prefill_one, write_slot, decode,
                            eos_id=eos_id, spec=spec, kv=kv,
                            tracer=tracer, metrics=metrics,
                            prefill_chunk=prefill_chunk,
                            chunk_step=chunk_step)
    return eng, kv
