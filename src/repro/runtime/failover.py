"""Elastic failover for the streamed SPMD ring (wires ``runtime.elastic``
into the serve path).

The paper's A.5 machinery — drop dead devices, re-run Halda over the
survivors, re-permute the layer stack, continue from the last token —
lived in ``runtime/elastic.py`` but nothing drove it.
:class:`ElasticRingServer` closes the loop for the streamed ring:

  * **detect** — any exception out of a ring pass is walked for a
    :class:`iopolicy.StageFailure` (the classified form of "stage m is
    unreachable", injected by the chaos suite, raised by health
    monitoring in production). Unattributed fatal errors rebuild the
    driver on the same stages (a wedged worker thread, not a dead host).
  * **re-solve** — ``elastic.fail_stages`` drops the dead stage and
    recomputes the ring plan; the survivor set shrinks further until the
    SPMD constraints hold again (``batch % M == 0``, ``M * tp`` devices).
    With device/model profiles attached, ``elastic.resolve_heterogeneous``
    re-runs the full Halda solve over the survivors and its ``k`` is
    adopted when the uniform ring supports it.
  * **resume** — a fresh mesh/driver/cache is built for the new plan and
    the *entire* token history (prompt + every emitted token) is replayed
    through the ring ("re-prefill": decode KV is the only
    non-checkpointed state, so it is rebuilt by re-running the
    conversation). Emitted tokens are never discarded — generation
    resumes exactly at the next token, and because the replay is the
    same deterministic computation a clean run on the survivor mesh
    performs, post-recovery tokens match that reference bit-for-bit.

Every recovery emits a :class:`FailoverEvent` with the detect/re-solve/
replay timing split and tokens-lost accounting that
``benchmarks/fault_recovery.py`` reports.
"""
from __future__ import annotations

import dataclasses
import logging
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import elastic
from . import serve as RS
from .iopolicy import IOPolicy, StageFailure, find_cause
from .streaming import StreamingRingDriver
from .telemetry import NULL_TRACER, clock

Params = Dict[str, Any]

log = logging.getLogger(__name__)


@dataclasses.dataclass(frozen=True)
class FailoverEvent:
    """One recovery: what died, what the new plan is, what it cost."""

    token_index: int              # emitted tokens when the failure struck
    failed_stage: Optional[int]   # original stage id (None = unattributed)
    generation: int               # elastic generation after recovery
    n_stages_before: int
    n_stages_after: int
    plan: Dict[str, int]          # new RingPlan as a dict
    halda: Optional[Dict[str, Any]]   # re-solve summary (profiles given)
    detect_s: float               # failure raised -> cause classified
    resolve_s: float              # elastic/Halda re-plan
    rebuild_s: float              # mesh + driver + jit rebuild
    replay_s: float               # re-prefill of the token history
    tokens_lost: int              # emitted tokens discarded (always 0)
    replayed_tokens: int

    @property
    def recovery_s(self) -> float:
        return self.detect_s + self.resolve_s + self.rebuild_s \
            + self.replay_s


class ElasticRingServer:
    """Streamed-ring generation loop with stage-failure recovery.

    ``store`` is any ``ParamStore``-like source (a ``faults.FaultyStore``
    in the chaos suite); ``params`` the full unpadded parameter dict
    (head leaves are used; blocks stream from the store). The server
    owns mesh/driver/cache construction so it can rebuild them when the
    stage set changes.

    ``device_profiles``/``model_profile`` (``core.profiles``) are
    optional: when both are given, each failover re-runs the Halda
    solver over the surviving stages' profiles and adopts its ``k`` if
    the uniform-window ring supports it.
    """

    def __init__(self, cfg, store, params: Params, *, batch: int,
                 ctx: int, n_stages: int, tp: int, k: int = 1,
                 prefetch_depth: int = 2, max_failovers: int = 2,
                 policy: Optional[IOPolicy] = None,
                 device_profiles: Optional[Sequence] = None,
                 model_profile=None, tracer=None):
        if not RS.ring_supported(cfg, batch, n_stages):
            raise ValueError(
                f"ring unsupported: family {cfg.family}, "
                f"batch {batch} % stages {n_stages} != 0")
        self.cfg = cfg
        self.store = store
        self.batch = batch
        self.ctx = ctx
        self.tp = tp
        self.prefetch_depth = prefetch_depth
        self.max_failovers = max_failovers
        self.policy = policy or IOPolicy()
        self.tracer = tracer or NULL_TRACER
        self.device_profiles = list(device_profiles) \
            if device_profiles is not None else None
        self.model_profile = model_profile
        self.state = elastic.initial_state(cfg, n_stages, k=k)
        # head stays resident and tp never changes, so pad once
        self._head = {key: v for key, v in
                      RS.pad_vocab(dict(params), cfg, tp).items()
                      if key != "blocks"}
        self.events: List[FailoverEvent] = []
        self.driver: Optional[StreamingRingDriver] = None
        self.mesh = None
        self._pending_event: Optional[Dict[str, Any]] = None

    # -- (re)construction -------------------------------------------------- #

    def _feasible(self, state: elastic.ElasticState
                  ) -> elastic.ElasticState:
        """Shrink the survivor set until the SPMD ring constraints hold:
        ``batch % M == 0`` and ``M * tp`` devices exist. Dropping a
        healthy stage is graceful degradation, not data loss — its
        layers re-distribute like a failed stage's."""
        n_dev = len(jax.devices())
        while True:
            M = len(state.stages)
            if M >= 1 and self.batch % M == 0 and M * self.tp <= n_dev:
                return state
            if M <= 1:
                raise RuntimeError(
                    f"no feasible ring: batch {self.batch}, tp {self.tp},"
                    f" {n_dev} devices, {M} surviving stages")
            state = elastic.fail_stages(state, self.cfg,
                                        [state.stages[-1]])

    def _build(self):
        """Mesh + fresh ring-permuted cache + streaming driver for the
        current elastic state."""
        from ..launch.mesh import make_mesh
        from ..models import init_cache

        M = self.state.plan.n_stages
        mesh = make_mesh((M, self.tp))
        cache = init_cache(self.cfg, self.batch, self.ctx,
                           dtype=jnp.float32)
        cache["layers"] = RS.pad_and_permute(cache["layers"], self.cfg,
                                             M, self.state.plan.k)
        driver = StreamingRingDriver(
            self.cfg, mesh, self.state.plan, self.store,
            head_params=self._head, cache_like=cache,
            prefetch_depth=self.prefetch_depth, policy=self.policy,
            tracer=self.tracer)
        self.mesh, self.driver = mesh, driver
        return driver, cache

    # -- recovery ---------------------------------------------------------- #

    def _resolve(self, exc: BaseException, n_emitted: int,
                 t_detect0: float) -> None:
        """Classify ``exc``, update the elastic state, record the event
        timing skeleton (completed by the caller after rebuild+replay)."""
        cause = find_cause(exc, StageFailure)
        detect_s = clock() - t_detect0
        before = len(self.state.stages)
        t0 = clock()
        failed_id: Optional[int] = None
        halda_info: Optional[Dict[str, Any]] = None
        if cause is not None and 0 <= cause.stage < before:
            failed_id = self.state.stages[cause.stage]
            self.state = elastic.fail_stages(self.state, self.cfg,
                                             [failed_id])
            self.state = self._feasible(self.state)
            if self.device_profiles is not None \
                    and self.model_profile is not None:
                profs = [self.device_profiles[s] for s in
                         self.state.stages
                         if s < len(self.device_profiles)]
                try:
                    sol = elastic.resolve_heterogeneous(
                        profs, self.model_profile)
                    halda_info = {"k": int(sol.k),
                                  "w": [int(x) for x in sol.w],
                                  "latency_s": float(sol.latency)}
                    per = self.state.plan.L_pad \
                        // self.state.plan.n_stages
                    if sol.k >= 1 and per % sol.k == 0 \
                            and sol.k != self.state.plan.k:
                        self.state = elastic.fail_stages(
                            self.state, self.cfg, [], k=int(sol.k))
                except Exception as e:      # re-solve is best-effort
                    log.warning("halda re-solve failed: %s", e)
        else:
            # unattributed: rebuild on the same stages (wedged worker,
            # poisoned jit buffer — not a dead host)
            log.warning("unattributed ring failure at token %d: %s",
                        n_emitted, exc)
        resolve_s = clock() - t0
        self._pending_event = dict(
            token_index=n_emitted, failed_stage=failed_id,
            generation=self.state.generation,
            n_stages_before=before,
            n_stages_after=len(self.state.stages),
            plan=dataclasses.asdict(self.state.plan),
            halda=halda_info, detect_s=detect_s, resolve_s=resolve_s)

    def _replay(self, driver, cache, history: List[np.ndarray]):
        """Feed every history column through the ring (re-prefill);
        returns (cache, ln, next_token_column)."""
        ln = cache["len"]
        logits = None
        for col in history:
            tok = jnp.asarray(col, jnp.int32).reshape(self.batch, 1)
            logits, cache = driver.step(tok, ln, cache)
            ln = ln + 1
        nxt = np.asarray(
            jnp.argmax(logits[:, 0, :self.cfg.vocab], -1), np.int32)
        return cache, ln, nxt

    # -- generation -------------------------------------------------------- #

    def generate(self, prompts, max_new: int) -> np.ndarray:
        """Greedy-decode ``max_new`` tokens per sequence; returns
        ``(batch, max_new)`` int32. Failures mid-stream recover per the
        module docstring; ``self.events`` records each one."""
        prompts = np.asarray(prompts, np.int32)
        if prompts.shape[0] != self.batch:
            raise ValueError(f"prompts batch {prompts.shape[0]} != "
                             f"engine batch {self.batch}")
        history: List[np.ndarray] = [prompts[:, t]
                                     for t in range(prompts.shape[1])]
        emitted: List[np.ndarray] = []
        driver = None
        failovers = 0
        while len(emitted) < max_new:
            try:
                if driver is None:
                    t_b0 = clock()
                    driver, cache = self._build()
                    rebuild_s = clock() - t_b0
                    t_r0 = clock()
                    cache, ln, nxt = self._replay(driver, cache, history)
                    replay_s = clock() - t_r0
                    ev = getattr(self, "_pending_event", None)
                    if ev is not None:
                        fe = FailoverEvent(
                            **ev, rebuild_s=rebuild_s, replay_s=replay_s,
                            tokens_lost=0,
                            replayed_tokens=len(history))
                        self.events.append(fe)
                        # recovery splits land on the shared timeline as
                        # back-to-back spans ending now
                        self.tracer.ingest_failover_event(fe,
                                                          t_end=clock())
                        self._pending_event = None
                while len(emitted) < max_new:
                    emitted.append(nxt)
                    history.append(nxt)
                    if len(emitted) >= max_new:
                        break
                    tok = jnp.asarray(nxt, jnp.int32).reshape(
                        self.batch, 1)
                    logits, cache = driver.step(tok, ln, cache)
                    ln = ln + 1
                    nxt = np.asarray(
                        jnp.argmax(logits[:, 0, :self.cfg.vocab], -1),
                        np.int32)
            except (KeyboardInterrupt, SystemExit):
                raise
            except Exception as exc:
                t_caught = clock()
                self.tracer.instant("stage_failure", cat="failover",
                                    track="failover",
                                    token_index=len(emitted),
                                    error=type(exc).__name__)
                failovers += 1
                if failovers > self.max_failovers:
                    raise
                log.warning("ring failure at token %d (failover %d/%d): "
                            "%s", len(emitted), failovers,
                            self.max_failovers, exc)
                if driver is not None:
                    driver.close()
                    driver = None
                self._resolve(exc, len(emitted), t_caught)
        return np.stack(emitted, axis=1) if emitted \
            else np.zeros((self.batch, 0), np.int32)

    def stats(self):
        return self.driver.stats() if self.driver is not None else None

    def close(self) -> None:
        if self.driver is not None:
            self.driver.close()
            self.driver = None
