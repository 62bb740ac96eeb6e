"""Unified runtime telemetry: spans, counters, per-token stall
attribution, and Chrome-trace (Perfetto) export.

The paper's claims are *latency* claims — 674 ms/token with disk I/O,
compute and Wi-Fi-class comms overlapped — yet until this module every
subsystem kept private ad-hoc stats on inconsistent clocks
(``faults.FiredFault`` on ``perf_counter``, ``iopolicy.WorkerHealth`` on
``monotonic``, prefetch timelines on ``perf_counter``), so there was no
way to lay a token's milliseconds on one timeline. This module is the
shared measurement substrate:

  * **one clock** — :func:`clock` (``time.perf_counter``). Every
    timestamp in the runtime (prefetch events, fault audit trails,
    worker-health progress, failover splits) takes it, so records from
    different subsystems merge into one ordered timeline.
  * **a tracer** — :class:`Tracer`: a thread-safe *bounded ring buffer*
    of typed events (:class:`SpanEvent` / :class:`CounterEvent` /
    :class:`InstantEvent`). Near-zero overhead when disabled (a flag
    and a profiler-session check per call site, no lock); optional
    deterministic 1-in-N sampling when enabled. The buffer never grows
    past ``capacity`` — a week-long serve cannot OOM on its own
    telemetry; ``evicted`` counts what wrapped away.
  * **per-token stall attribution** — :meth:`Tracer.token_step` opens a
    step scope on the calling thread; :meth:`Tracer.phase` calls inside
    it (from *any* instrumented callee — the prefetcher's blocked
    ``get()``, the engine's jitted decode call) accumulate **exclusive**
    time per component (``disk_wait``, ``staging_copy``, ``h2d``,
    ``compute``, ``comms``) with the remainder booked to ``sched_idle``,
    so the components sum to the measured step wall time *by
    construction*. The resulting :class:`StallRecord` stream is the
    per-token answer to "where did the milliseconds go".
  * **Chrome trace export** — :meth:`Tracer.chrome_trace` /
    :meth:`Tracer.export_chrome_trace` emit Chrome Trace Event Format
    JSON (one track per worker thread / ring stage) that loads directly
    in Perfetto (https://ui.perfetto.dev) or ``chrome://tracing``.
  * **the profiler's clock** — while a ``jax.profiler`` session records,
    every :meth:`Tracer.span`, :meth:`Tracer.token_step` and
    :meth:`Tracer.phase` is also a ``TraceAnnotation`` of the same name
    (its args as the event's stats), on any tracer, :data:`NULL_TRACER`
    included. The spans then lie on the device trace's timeline. With no
    session a span site costs one ``is_enabled()`` check. Span names are
    static; what varies (uid, step index) goes in args.
  * **a compile log** — :func:`compile_log`: each jaxpr trace, MLIR
    lowering and backend compile (or persistent-cache load) of the
    process, stamped on :func:`clock`.

``FailoverEvent`` recovery splits become spans on the shared timeline
through :meth:`Tracer.ingest_failover_event`, while the hot paths emit
live when a tracer is attached. ``core.latency.telemetry_crosscheck``
closes the loop by comparing the measured per-term splits against the
Halda latency model's disk/compute/comms terms (the drift signal
ROADMAP item 4's online re-solve consumes).

Validator CLI (used by CI's trace smoke)::

    python -m repro.runtime.telemetry --validate trace.json \\
        --require prefetcher decode
"""
from __future__ import annotations

import dataclasses
import json
import logging
import threading
import time
from collections import deque
from contextlib import contextmanager, nullcontext
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

from jax import monitoring
from jax.profiler import TraceAnnotation

#: the one runtime clock. Monotonic, high resolution, and — crucially —
#: the SAME base every subsystem stamps against (``PrefetchEvent``
#: already used ``perf_counter``; ``faults``/``iopolicy`` now route
#: through here instead of mixing in ``time.monotonic``).
clock = time.perf_counter

#: canonical stall-attribution components. ``phase()`` names outside
#: this set accumulate into ``other``; the un-phased remainder of a step
#: is ``sched_idle``. Together they partition the step wall time.
COMPONENTS = ("disk_wait", "staging_copy", "h2d", "compute", "comms",
              "sched_idle", "other")


# --------------------------------------------------------------------------- #
#  typed event schema
# --------------------------------------------------------------------------- #

@dataclasses.dataclass(frozen=True)
class SpanEvent:
    """A named interval on one track (Chrome ``ph="X"``)."""

    name: str
    cat: str
    track: str
    t_start: float
    t_end: float
    args: Tuple[Tuple[str, Any], ...] = ()

    @property
    def duration(self) -> float:
        return self.t_end - self.t_start


@dataclasses.dataclass(frozen=True)
class CounterEvent:
    """A sampled scalar (Chrome ``ph="C"`` — a value-over-time graph)."""

    name: str
    track: str
    t: float
    value: float


@dataclasses.dataclass(frozen=True)
class InstantEvent:
    """A point event (Chrome ``ph="i"`` — e.g. a fired fault)."""

    name: str
    cat: str
    track: str
    t: float
    args: Tuple[Tuple[str, Any], ...] = ()


TraceEvent = Union[SpanEvent, CounterEvent, InstantEvent]


@dataclasses.dataclass(frozen=True)
class StallRecord:
    """Per-token (per-step) stall attribution.

    Exclusive seconds per component; ``sched_idle_s`` is the measured
    wall time not inside any phase, so the components always sum to
    ``wall_s`` up to float rounding — the benchmark gate checks the sum
    against independently-measured TPOT.
    """

    index: int                    # token/step index
    t_start: float
    t_end: float
    disk_wait_s: float = 0.0      # front blocked waiting on a layer/bank
    staging_copy_s: float = 0.0   # synchronous host staging copies
    h2d_s: float = 0.0            # synchronous host->device transfers
    compute_s: float = 0.0        # jitted kernel/step calls
    comms_s: float = 0.0          # ring hops measured outside compute
    sched_idle_s: float = 0.0     # engine bookkeeping / python overhead
    other_s: float = 0.0          # non-canonical phase names

    @property
    def wall_s(self) -> float:
        return self.t_end - self.t_start

    @property
    def accounted_s(self) -> float:
        return (self.disk_wait_s + self.staging_copy_s + self.h2d_s
                + self.compute_s + self.comms_s + self.sched_idle_s
                + self.other_s)

    def component(self, name: str) -> float:
        return getattr(self, f"{name}_s")


def stall_summary(records: Sequence[StallRecord]) -> Dict[str, float]:
    """Mean seconds per component over a record stream, plus ``wall``
    (mean TPOT) and ``n`` — the shape ``telemetry_crosscheck`` and the
    ``--metrics-interval`` report consume."""
    out = {c: 0.0 for c in COMPONENTS}
    out["wall"] = 0.0
    out["n"] = float(len(records))
    if not records:
        return out
    for r in records:
        for c in COMPONENTS:
            out[c] += r.component(c)
        out["wall"] += r.wall_s
    for k in (*COMPONENTS, "wall"):
        out[k] /= len(records)
    return out


def format_summary(summary: Dict[str, float]) -> str:
    """One operator-facing line: mean TPOT and its split."""
    wall = summary.get("wall", 0.0)
    parts = ", ".join(
        f"{c} {summary.get(c, 0.0) * 1e3:.2f}" for c in COMPONENTS
        if summary.get(c, 0.0) > 0.0)
    return (f"tpot {wall * 1e3:.2f} ms over {int(summary.get('n', 0))} "
            f"steps [{parts} ms]")


# --------------------------------------------------------------------------- #
#  token-step scope (stall attribution)
# --------------------------------------------------------------------------- #

class TokenStep:
    """Open step scope: exclusive-time phase accounting on one thread.

    Entering a nested phase *pauses* the enclosing one (the prefetcher's
    ``disk_wait`` inside the engine's ``compute`` is charged to
    ``disk_wait``, not double-counted), so the recorded components
    partition the phased time exactly.
    """

    __slots__ = ("index", "track", "t_start", "components", "_stack")

    def __init__(self, index: int, track: str, t_start: float):
        self.index = index
        self.track = track
        self.t_start = t_start
        self.components: Dict[str, float] = {}
        self._stack: List[List[Any]] = []     # [name, t_resumed]

    def enter_phase(self, name: str, t: float) -> None:
        if self._stack:
            top = self._stack[-1]
            self.components[top[0]] = self.components.get(top[0], 0.0) \
                + (t - top[1])
        self._stack.append([name, t])

    def exit_phase(self, t: float) -> None:
        name, t0 = self._stack.pop()
        self.components[name] = self.components.get(name, 0.0) + (t - t0)
        if self._stack:
            self._stack[-1][1] = t

    def finish(self, t_end: float) -> StallRecord:
        while self._stack:                    # abandoned phases (errors)
            self.exit_phase(t_end)
        known = {c: 0.0 for c in COMPONENTS}
        for name, secs in self.components.items():
            known[name if name in known else "other"] += secs
        phased = sum(known.values())
        known["sched_idle"] = max((t_end - self.t_start) - phased, 0.0)
        return StallRecord(
            index=self.index, t_start=self.t_start, t_end=t_end,
            **{f"{c}_s": known[c] for c in COMPONENTS})


# --------------------------------------------------------------------------- #
#  the tracer
# --------------------------------------------------------------------------- #

class Tracer:
    """Thread-safe bounded-ring-buffer span/counter tracer.

    ``enabled=False`` (or :data:`NULL_TRACER`) is the production default:
    every emission path checks the flag (and, for spans, whether a
    profiler session records) first and returns without locking, so
    instrumentation can stay compiled into the hot paths permanently.
    ``sample=1/N`` keeps every N-th event (deterministic — no RNG),
    bounding trace size on long serves while stall attribution (which
    aggregates, not stores-per-event) stays exact.
    """

    def __init__(self, *, enabled: bool = True, capacity: int = 65536,
                 sample: float = 1.0):
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        if not (0.0 < sample <= 1.0):
            raise ValueError("sample must be in (0, 1]")
        self.enabled = enabled
        self.capacity = capacity
        self._keep_every = max(1, int(round(1.0 / sample)))
        self._buf: deque = deque(maxlen=capacity)
        self._stalls: deque = deque(maxlen=capacity)
        self._lock = threading.Lock()
        self._local = threading.local()
        self._seq = 0
        self.evicted = 0              # events that wrapped off the ring
        self.stalls_evicted = 0

    # -- clock ------------------------------------------------------------- #

    @staticmethod
    def now() -> float:
        return clock()

    # -- emission ---------------------------------------------------------- #

    def _append(self, ev: TraceEvent) -> None:
        with self._lock:
            self._seq += 1
            if self._keep_every > 1 and self._seq % self._keep_every:
                return
            if len(self._buf) == self.capacity:
                self.evicted += 1
            self._buf.append(ev)

    def span_event(self, name: str, t_start: float, t_end: float, *,
                   cat: str = "span", track: Optional[str] = None,
                   **args) -> None:
        if not self.enabled:
            return
        self._append(SpanEvent(
            name=name, cat=cat, track=track or _thread_track(),
            t_start=t_start, t_end=t_end,
            args=tuple(sorted(args.items()))))

    def instant(self, name: str, *, cat: str = "instant",
                track: Optional[str] = None, t: Optional[float] = None,
                **args) -> None:
        if not self.enabled:
            return
        self._append(InstantEvent(
            name=name, cat=cat, track=track or _thread_track(),
            t=t if t is not None else clock(),
            args=tuple(sorted(args.items()))))

    def counter(self, name: str, value: float, *,
                track: Optional[str] = None,
                t: Optional[float] = None) -> None:
        if not self.enabled:
            return
        self._append(CounterEvent(
            name=name, track=track or _thread_track(),
            t=t if t is not None else clock(), value=float(value)))

    @contextmanager
    def span(self, name: str, *, cat: str = "span",
             track: Optional[str] = None, **args):
        """Time a block as one span: on the ring buffer when enabled, and
        as a ``TraceAnnotation`` while a profiler session records. Yields
        a dict the block may add args to, or None where neither records
        (then no clock is read)."""
        ann = _annotation(name, args)
        if not self.enabled and ann is _NO_ANNOTATION:
            yield None
            return
        more: Dict[str, Any] = {}
        t0 = clock()
        with ann:
            try:
                yield more
            finally:
                if more and ann is not _NO_ANNOTATION:
                    ann.set_metadata(**more)
                if self.enabled:
                    self.span_event(name, t0, clock(), cat=cat,
                                    track=track, **args, **more)

    # -- stall attribution ------------------------------------------------- #

    @contextmanager
    def token_step(self, index: int, *, track: str = "decode",
                   name: str = "token", **args):
        """Open a per-token step scope on this thread. ``phase()`` calls
        underneath (in this thread) attribute into it; on exit a
        :class:`StallRecord` is appended and the step is emitted as a
        span ``name`` (arg ``index``) on the ``track`` timeline."""
        ann = _annotation(name, args, index=index)
        if not self.enabled:
            with ann:
                yield None
            return
        prev = getattr(self._local, "step", None)
        step = TokenStep(index, track, clock())
        self._local.step = step
        try:
            with ann:
                yield step
        finally:
            t_end = clock()
            self._local.step = prev
            rec = step.finish(t_end)
            with self._lock:
                if len(self._stalls) == self.capacity:
                    self.stalls_evicted += 1
                self._stalls.append(rec)
            self.span_event(name, step.t_start, t_end, cat="decode",
                            track=track, index=index,
                            disk_wait_ms=round(rec.disk_wait_s * 1e3, 3),
                            compute_ms=round(rec.compute_s * 1e3, 3),
                            **args)

    def current_step(self) -> Optional[TokenStep]:
        return getattr(self._local, "step", None)

    @contextmanager
    def phase(self, name: str, *, cat: str = "phase",
              track: Optional[str] = None, min_dur: float = 0.0,
              label: Optional[str] = None, **args):
        """Attribute a block to stall component ``name``.

        Inside an open :meth:`token_step` on this thread the exclusive
        duration lands on that step's record; a span is also emitted
        (named ``label`` if given, suppressed under ``min_dur`` — e.g.
        the prefetcher's usually-instant ``disk_wait`` waits only trace
        when they actually stalled; a profiler annotation is never
        suppressed). Disabled tracer: straight passthrough.
        """
        ann = _annotation(label or name, args)
        if not self.enabled:
            with ann:
                yield
            return
        step = getattr(self._local, "step", None)
        t0 = clock()
        if step is not None:
            step.enter_phase(name, t0)
        try:
            with ann:
                yield
        finally:
            t1 = clock()
            if step is not None:
                step.exit_phase(t1)
            if t1 - t0 >= min_dur:
                self.span_event(label or name, t0, t1, cat=cat,
                                track=track, **args)

    # -- snapshots --------------------------------------------------------- #

    def events(self) -> List[TraceEvent]:
        with self._lock:
            return list(self._buf)

    def stalls(self) -> List[StallRecord]:
        with self._lock:
            return list(self._stalls)

    def summary(self, last_n: Optional[int] = None) -> Dict[str, float]:
        recs = self.stalls()
        if last_n is not None:
            recs = recs[-last_n:]
        return stall_summary(recs)

    def tracks(self) -> List[str]:
        seen: Dict[str, None] = {}
        for ev in self.events():
            seen.setdefault(ev.track)
        return list(seen)

    def clear(self) -> None:
        with self._lock:
            self._buf.clear()
            self._stalls.clear()
            self._seq = 0
            self.evicted = 0
            self.stalls_evicted = 0

    # -- legacy-record ingestion ------------------------------------------- #

    def ingest_failover_event(self, ev, *, t_end: Optional[float] = None,
                              track: str = "failover") -> None:
        """``failover.FailoverEvent`` -> its detect/resolve/rebuild/replay
        split as contiguous spans ending at ``t_end`` (default: now)."""
        t1 = t_end if t_end is not None else clock()
        t0 = t1 - ev.recovery_s
        edges = [t0]
        for d in (ev.detect_s, ev.resolve_s, ev.rebuild_s, ev.replay_s):
            edges.append(edges[-1] + d)
        for name, a, b in zip(("detect", "resolve", "rebuild", "replay"),
                              edges[:-1], edges[1:]):
            self.span_event(f"failover/{name}", a, b, cat="failover",
                            track=track, token_index=ev.token_index,
                            failed_stage=ev.failed_stage,
                            stages_after=ev.n_stages_after)

    # -- Chrome trace (Perfetto) export ------------------------------------ #

    def chrome_trace(self) -> Dict[str, Any]:
        """Chrome Trace Event Format dict (``traceEvents`` +
        ``displayTimeUnit``) — loads in Perfetto / chrome://tracing.
        One pid, one tid per track, tracks named via metadata events."""
        events = self.events()
        t0 = min((ev.t_start if isinstance(ev, SpanEvent) else ev.t
                  for ev in events), default=0.0)
        tids: Dict[str, int] = {}
        out: List[Dict[str, Any]] = [{
            "name": "process_name", "ph": "M", "pid": 1, "tid": 0,
            "args": {"name": "repro-runtime"}}]

        def tid(track: str) -> int:
            if track not in tids:
                tids[track] = len(tids) + 1
                out.append({"name": "thread_name", "ph": "M", "pid": 1,
                            "tid": tids[track], "args": {"name": track}})
            return tids[track]

        for ev in events:
            if isinstance(ev, SpanEvent):
                out.append({
                    "name": ev.name, "cat": ev.cat or "span", "ph": "X",
                    "ts": (ev.t_start - t0) * 1e6,
                    "dur": max(ev.duration, 0.0) * 1e6,
                    "pid": 1, "tid": tid(ev.track),
                    "args": dict(ev.args)})
            elif isinstance(ev, CounterEvent):
                out.append({
                    "name": ev.name, "ph": "C",
                    "ts": (ev.t - t0) * 1e6, "pid": 1,
                    "tid": tid(ev.track),
                    "args": {"value": ev.value}})
            else:
                out.append({
                    "name": ev.name, "cat": ev.cat or "instant",
                    "ph": "i", "s": "t", "ts": (ev.t - t0) * 1e6,
                    "pid": 1, "tid": tid(ev.track),
                    "args": dict(ev.args)})
        return {"traceEvents": out, "displayTimeUnit": "ms",
                "metadata": {"evicted": self.evicted,
                             "stalls_evicted": self.stalls_evicted,
                             "complete": self.evicted == 0}}

    def export_chrome_trace(self, path: str) -> str:
        doc = self.chrome_trace()
        if self.evicted:
            # a truncated trace must never pass for a complete one
            logging.getLogger(__name__).warning(
                "trace %s is truncated: ring evicted %d events "
                "(%d token-step stall records) — raise Tracer(capacity=)",
                path, self.evicted, self.stalls_evicted)
        with open(path, "w") as f:
            json.dump(doc, f)
            f.write("\n")
        return path


def _thread_track() -> str:
    return threading.current_thread().name


_NO_ANNOTATION = nullcontext()


def _annotation(name: str, args: Dict[str, Any], **more):
    """The span's ``TraceAnnotation`` while a profiler session records,
    else a shared no-op context (no annotation made, nothing formatted)."""
    if TraceAnnotation.is_enabled():
        return TraceAnnotation(name, **args, **more)
    return _NO_ANNOTATION


#: the shared disabled tracer: instrumented code defaults to it so the
#: hot paths never branch on ``None``.
NULL_TRACER = Tracer(enabled=False, capacity=1)


# --------------------------------------------------------------------------- #
#  compile log (where set-up goes, which call recompiled)
# --------------------------------------------------------------------------- #

#: ``jax.monitoring`` time-span events of building a program -> kind
COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    # a load from the persistent compilation cache is timed here too
    "/jax/core/compile/backend_compile_duration": "compile",
}


@dataclasses.dataclass(frozen=True)
class CompileSpan:
    kind: str                     # trace | lower | compile
    fun: str                      # the traced function or module
    t_start: float                # on ``clock``
    t_end: float


class CompileLog:
    """The process's program builds on :func:`clock`: a
    :class:`CompileSpan` per jaxpr trace, MLIR lowering and backend
    compile. Bounded like the tracer's ring; ``t_start`` is when it
    began to listen. Get the process's one log from
    :func:`compile_log`."""

    def __init__(self, capacity: int = 65536):
        self.t_start = clock()
        self.spans: deque = deque(maxlen=capacity)

    def _on_span(self, event: str, start: float, end: float,
                 fun_name: str = "", **_) -> None:
        kind = COMPILE_EVENTS.get(event)
        if kind is not None:
            # jax times the span on the wall clock and reports it as it
            # ends: place it on ``clock`` by its end
            t1 = clock()
            self.spans.append(CompileSpan(kind, str(fun_name),
                                          t1 - (end - start), t1))

    def seconds(self, kinds: Sequence[str], t_end: float = float("inf")
                ) -> float:
        """Seconds spent in spans of ``kinds`` that ended by ``t_end``; a
        trace nested in another (a jitted callee traced inside its
        caller) counts once."""
        iv = sorted((s.t_start, s.t_end) for s in list(self.spans)
                    if s.kind in kinds and s.t_end <= t_end)
        total, hi = 0.0, float("-inf")
        for a, b in iv:
            if b > hi:
                total += b - max(a, hi)
                hi = b
        return total


_compile_log: Optional[CompileLog] = None
_compile_log_lock = threading.Lock()


def compile_log() -> CompileLog:
    """The process's compile log, listening from the first call on
    (engine builds make that call)."""
    global _compile_log
    with _compile_log_lock:
        if _compile_log is None:
            log = CompileLog()
            monitoring.register_event_time_span_listener(log._on_span)
            _compile_log = log
        return _compile_log


# --------------------------------------------------------------------------- #
#  trace validation (CI's trace smoke + the observability benchmark)
# --------------------------------------------------------------------------- #

def validate_chrome_trace(path: str,
                          require_tracks: Sequence[str] = ()
                          ) -> Dict[str, Any]:
    """Parse a Chrome-trace JSON and check schema invariants.

    Raises ``ValueError`` on a malformed trace or a missing required
    track (substring match against thread names, so ``prefetcher``
    matches both the layer and ring-bank prefetchers). Returns a summary
    dict (tracks, event/phase counts) for reporting.
    """
    with open(path) as f:
        doc = json.load(f)
    if not isinstance(doc, dict) or "traceEvents" not in doc:
        raise ValueError(f"{path}: not a Chrome trace "
                         "(missing traceEvents)")
    events = doc["traceEvents"]
    if not isinstance(events, list) or not events:
        raise ValueError(f"{path}: empty traceEvents")
    tracks: List[str] = []
    phases: Dict[str, int] = {}
    for i, ev in enumerate(events):
        if not isinstance(ev, dict):
            raise ValueError(f"{path}: event {i} is not an object")
        ph = ev.get("ph")
        if ph not in ("X", "C", "i", "M", "B", "E"):
            raise ValueError(f"{path}: event {i} has unknown ph {ph!r}")
        phases[ph] = phases.get(ph, 0) + 1
        if ph == "M":
            if ev.get("name") == "thread_name":
                tracks.append(str(ev["args"]["name"]))
            continue
        for key in ("name", "ts", "pid", "tid"):
            if key not in ev:
                raise ValueError(f"{path}: event {i} missing {key!r}")
        if not isinstance(ev["ts"], (int, float)) or ev["ts"] < 0:
            raise ValueError(f"{path}: event {i} bad ts {ev['ts']!r}")
        if ph == "X" and (not isinstance(ev.get("dur"), (int, float))
                          or ev["dur"] < 0):
            raise ValueError(f"{path}: event {i} bad dur "
                             f"{ev.get('dur')!r}")
    missing = [want for want in require_tracks
               if not any(want in t for t in tracks)]
    if missing:
        raise ValueError(
            f"{path}: required tracks missing: {missing} "
            f"(present: {tracks})")
    evicted = int(doc.get("metadata", {}).get("evicted", 0))
    return {"tracks": tracks, "n_events": len(events), "phases": phases,
            "evicted": evicted}


def _main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        description="Validate a Chrome-trace JSON exported by "
                    "repro.runtime.telemetry")
    ap.add_argument("--validate", required=True, metavar="TRACE_JSON")
    ap.add_argument("--require", nargs="*", default=(),
                    help="track-name substrings that must be present")
    args = ap.parse_args(argv)
    info = validate_chrome_trace(args.validate, args.require)
    print(f"{args.validate}: valid Chrome trace — "
          f"{info['n_events']} events, tracks {info['tracks']}, "
          f"phases {info['phases']}")
    if info["evicted"]:
        print(f"WARNING: trace is truncated — ring evicted "
              f"{info['evicted']} events")
    return 0


if __name__ == "__main__":
    raise SystemExit(_main())
