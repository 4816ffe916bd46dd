"""Continuous micro-batching scheduler for the multi-tenant ACAM service.

Requests from *any* tenant are coalesced into fixed-slot micro-batches and
served by ONE fused classify dispatch per tick:

    tick:  pop <= slots requests (FIFO across tenants)
           -> one `repro.match.MatchEngine.classify_serve` call over the
              registry's super-bank: the per-slot tenant threshold-row
              gather, binarisation, match, per-slot class-window Eq. 12
              decision + margin AND the cascade's ``margin < tau``
              escalation bit — on the kernel backend all of it is ONE
              resident pallas_call (`acam_*_serve`), with no jnp prologue
              or epilogue. Executed under the engine's 2D PartitionPlan
              when a mesh is installed: slots shard over the dp axes, the
              super-bank's class rows over the model axis (the registry
              aligns tenant windows to those shards), and the per-slot
              winner/margin come from the engine's cross-shard
              (max, argmax) reduce (all-gather fold or XOR-butterfly tree,
              `plan.reduce`) — bit-identical to replicated execution,
              still ONE dispatch
           -> per-slot tenant-local predictions + margins + escalate bits

The batch shape is pinned to ``slots`` (ragged tails are padded with empty
class windows, which the kernel resolves to pred 0 / margin 0 and the
scheduler drops), and the super-bank's shapes are bucketed by the registry —
so the jitted tick function compiles once and stays hot across tenant
churn. Batch-fill statistics are recorded per tick so coalescing quality is
observable (`SchedulerStats.occupancy`).

The scheduler's cascade knowledge is one number per slot: the service
installs a ``tau_fn`` (tenant id -> margin threshold, None = no head) and
each `SlotResult` comes back with the in-kernel `escalate` bit; the service
layer (`repro.serve.acam_service`) still owns the routing itself. It does own two resilience duties:
`expire()` pops requests that outlived the cascade's per-request deadline
(the FIFO prefix), and every tick's wall time heartbeats into a
`repro.ft.elastic.StragglerMonitor` — slow-tick strikes are surfaced
through `SchedulerStats.slow_ticks` / `last_verdict` so the control plane
can shed load or shrink the mesh before latency collapses.

With `ObsSpec.profile_annotations` on, every tick is traced in spans of the
flight recorder (`FlightRecorder.span`): ``sched.tick`` around the tick
(stats ``tick_id`` and ``fill``, the requests dequeued), holding
``sched.pack`` (pop, placement lookup, numpy operands) and
``acam_fused_dispatch``, which holds ``sched.launch`` (the uploads and the
jitted call) and ``sched.fetch`` (the wait for the device and the four
copies back).
"""
from __future__ import annotations

import dataclasses
import functools
import time
from collections import deque
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro import match as match_lib
from repro.ft.elastic import StragglerMonitor
from repro.obs import FlightRecorder
from repro.serve.registry import TemplateBankRegistry, TenantEntry


@dataclasses.dataclass
class WorkItem:
    """One admitted classification request, as the scheduler sees it.

    Holds only the tenant *id*: the placement (`TenantEntry`) is resolved
    against the registry at tick time, so hot update/evict between submit
    and dispatch can never serve a request against a stale class window.
    """

    request_id: int
    tenant_id: str
    features: np.ndarray  # (N,) float32, raw front-end features
    submit_t: float
    payload: Any = None  # opaque service-side context (head slot, tau, ...)


@dataclasses.dataclass
class SlotResult:
    """Scheduler output for one served request."""

    item: WorkItem
    entry: TenantEntry | None  # placement at dispatch time; None on error
    pred_local: int  # tenant-local class id (global - tenant offset)
    margin: float  # Eq. 12 winner-vs-runner-up confidence margin
    error: str | None = None  # e.g. tenant evicted while queued
    escalate: bool = False  # in-kernel margin < tau(tenant) cascade bit
    #: winner's absolute per-class score in the backend's native units
    #: (match count 0..N, or matchline fraction 0..1). The margin above is
    #: relative — a one-row class window clamps it to the cap — so absolute
    #: acceptance floors (the semantic cache's hit_score) read this.
    score: float = 0.0


@dataclasses.dataclass
class SchedulerStats:
    slots: int = 0
    ticks: int = 0
    classify_dispatches: int = 0
    served: int = 0
    filled_slots: int = 0
    min_fill: int | None = None
    max_fill: int = 0
    tick_time_s: float = 0.0  # summed dispatch wall time
    slow_ticks: int = 0  # ticks flagged by the straggler monitor
    expired: int = 0  # requests expired past their queue deadline

    def record_tick(self, fill: int, *, dt_s: float = 0.0,
                    slow: bool = False) -> None:
        self.ticks += 1
        self.classify_dispatches += 1
        self.served += fill
        self.filled_slots += fill
        self.max_fill = max(self.max_fill, fill)
        self.min_fill = fill if self.min_fill is None else \
            min(self.min_fill, fill)
        self.tick_time_s += dt_s
        self.slow_ticks += int(slow)

    @property
    def occupancy(self) -> float:
        """Mean batch fill fraction across ticks (1.0 = every slot used)."""
        if self.ticks == 0:
            return 0.0
        return self.filled_slots / (self.ticks * self.slots)

    def as_dict(self) -> dict:
        return {
            "ticks": self.ticks,
            "classify_dispatches": self.classify_dispatches,
            "served": self.served,
            "occupancy": round(self.occupancy, 4),
            "min_fill": self.min_fill or 0,
            "max_fill": self.max_fill,
            "slots": self.slots,
            "tick_time_s": round(self.tick_time_s, 6),
            "slow_ticks": self.slow_ticks,
            "expired": self.expired,
        }


@functools.partial(jax.jit, static_argnames=("config", "mesh_gen"))
def _batched_classify(bank, thr_table, feats, tenant_slot, class_lo, class_hi,
                      tau, *, config, mesh_gen: int):
    """The whole tick on device: ONE `MatchEngine.classify_serve` dispatch
    over the multi-tenant super-bank — the per-slot threshold-row gather,
    binarisation, windowed Eq. 12 decision/margin and the ``margin < tau``
    escalation bit included (a single pallas_call on the kernel backend
    under ``serve_fusion="mega"``).

    ``config`` is the full `repro.match.EngineConfig`, a *static* argument
    resolved eagerly by `tick()` (never the process default read at trace
    time), so switching backends — or any other engine knob, e.g. the
    device-physics noise config of a spec-built service or the mega/compose
    serve fusion — between ticks re-traces instead of replaying a stale
    executable. ``mesh_gen`` (`distributed.context.generation()`, also
    static) does the same for the mesh: the engine bakes its
    `PartitionPlan` — batch over the dp axes, super-bank class rows over
    the model axis — into this trace, and installing a different mesh
    between ticks keys a fresh executable instead of silently replaying the
    stale layout."""
    del mesh_gen  # cache key only: a new mesh generation forces a re-trace
    eng = match_lib.engine_from_config(config)
    return eng.classify_serve(feats, thr_table, tenant_slot, bank, class_lo,
                              class_hi, tau)


class MicroBatchScheduler:
    """Fixed-slot continuous micro-batching over a `TemplateBankRegistry`.

    The matching setup is ONE `repro.match.EngineConfig` (`engine`, the
    spec path: `ServiceSpec.engine` is passed through verbatim). The
    legacy keyword surface (`method`/`alpha`/`backend`) still works and
    builds the same config; `backend=None` keeps its historical meaning —
    re-resolve the process default at every tick.
    """

    def __init__(self, registry: TemplateBankRegistry, *, slots: int = 64,
                 method: str = "feature_count", alpha: float = 1.0,
                 backend: str | None = None,
                 engine: match_lib.EngineConfig | None = None,
                 monitor: StragglerMonitor | None = None,
                 recorder=None):
        if slots < 1:
            raise ValueError("slots must be >= 1")
        self.registry = registry
        self.slots = slots
        if engine is not None:
            self.engine_config = engine
            self.backend = engine.backend
        else:
            self.engine_config = match_lib.EngineConfig(
                method=method, alpha=alpha, backend=backend or "auto",
                margin=True)
            self.backend = backend
        self.stats = SchedulerStats(slots=slots)
        #: every tick's wall time heartbeats into this monitor
        #: (`StragglerMonitor.observe`): a tick blowing past the rolling
        #: median accrues strikes, surfaced via stats.slow_ticks and
        #: `last_verdict` — the service's health() view.
        self.monitor = monitor if monitor is not None else StragglerMonitor(
            n_hosts=1)
        self.last_verdict: dict | None = None
        #: the `repro.obs.FlightRecorder` (the service's; a private one when
        #: none is given): tick ids, queue waits, the registry's scheduler
        #: counters and the tick's spans. `SchedulerStats` stays as a plain
        #: in-object mirror (cheap, and directly inspectable).
        self.recorder = recorder if recorder is not None \
            else FlightRecorder()
        #: optional tenant_id -> margin threshold (float | None). Installed
        #: by the service layer; feeds the per-slot ``tau`` operand so the
        #: cascade's ``margin < tau`` compare runs inside the serve kernel.
        #: None (or a None return) pins tau to -inf: never escalate.
        self.tau_fn = None
        self._queue: deque[WorkItem] = deque()

    @property
    def method(self) -> str:
        return self.engine_config.method

    @property
    def alpha(self) -> float:
        return self.engine_config.alpha

    def set_engine(self, engine: match_lib.EngineConfig) -> None:
        """Live engine swap (the control plane's backend transition): the
        next tick dispatches under the new config — a fresh static jit key,
        so it re-traces instead of replaying the old executable."""
        self.engine_config = engine
        self.backend = engine.backend

    @property
    def qsize(self) -> int:
        return len(self._queue)

    def submit(self, item: WorkItem) -> None:
        self._queue.append(item)

    def expire(self, deadline_s: float,
               now: float | None = None) -> list[WorkItem]:
        """Pop queued items older than ``deadline_s`` (the cascade's
        per-request deadline). The queue is FIFO, so expired items are a
        prefix; the service answers them with a deadline error instead of
        serving them uselessly late."""
        now = time.perf_counter() if now is None else now
        out: list[WorkItem] = []
        while self._queue and now - self._queue[0].submit_t > deadline_s:
            out.append(self._queue.popleft())
        self.stats.expired += len(out)
        if out:
            self.recorder.record_expired(len(out))
        return out

    def tick(self) -> list[SlotResult]:
        """Serve one micro-batch; returns [] when the queue is empty.

        Contract the double-buffered reshard (`repro.fleet.reshard`)
        leans on: every tick re-resolves placements via
        `registry.lookup` and re-reads `device_bank()` /
        `thresholds_table()` (generation-cached), and queued `WorkItem`s
        hold only tenant ids — so swapping the registry's arrays +
        offsets BETWEEN two ticks is invisible to queued work, and a
        bank flip needs no drain."""
        if not self._queue:
            return []
        obs = self.recorder
        t0 = time.perf_counter()
        n = min(self.slots, len(self._queue))
        with obs.span("sched.tick", tick_id=obs.next_tick_id(), fill=n):
            with obs.span("sched.pack"):
                dead, batch, operands = self._pack(n, t0)
            if not batch:
                return dead
            from repro.distributed import context

            cfg = self.engine_config._replace(
                backend=self.backend or match_lib.default_backend())
            with obs.span("acam_fused_dispatch"):
                with obs.span("sched.launch"):
                    out = _batched_classify(
                        self.registry.device_bank(),
                        self.registry.thresholds_table(),
                        *(jnp.asarray(a) for a in operands), config=cfg,
                        mesh_gen=context.generation())
                with obs.span("sched.fetch"):
                    pred, per_class, margin, esc = (np.asarray(a)
                                                    for a in out)
            dt = time.perf_counter() - t0
            self.last_verdict = self.monitor.observe(0, dt)
            slow = bool(self.last_verdict["stragglers"])
            self.stats.record_tick(len(batch), dt_s=dt, slow=slow)
            obs.record_tick_dispatch(len(batch), dt, slow)

            # winner's absolute score: per_class is logically (slots, C_cap)
            # under every plan, so per_class[i, pred[i]] is uniform. An
            # empty window's pred is 0 and its score -inf; clamp to 0.0.
            score = per_class[np.arange(len(batch)), pred[:len(batch)]]
            score = np.where(np.isfinite(score), score, 0.0)
            return dead + [
                SlotResult(item=item, entry=entry,
                           pred_local=int(pred[i]) - entry.offset,
                           margin=float(margin[i]), escalate=bool(esc[i]),
                           score=float(score[i]))
                for i, (item, entry) in enumerate(batch)]

    def _pack(self, n: int, t0: float):
        """Pop ``n`` requests and build the tick's numpy operands. ->
        (results of requests whose tenant is gone, [(item, entry)] to
        serve, (feats, tenant slot, window lo, window hi, tau))."""
        popped = [self._queue.popleft() for _ in range(n)]
        self.recorder.record_queue_wait(popped, t0)
        # resolve placements NOW: queued requests must see the tenant's
        # current class window, not the one from submit time
        dead = []
        batch: list[tuple[WorkItem, TenantEntry]] = []
        for item in popped:
            entry = self.registry.lookup(item.tenant_id)
            if entry is None:
                dead.append(SlotResult(
                    item=item, entry=None, pred_local=-1, margin=0.0,
                    error=f"tenant {item.tenant_id!r} evicted while queued"))
            else:
                batch.append((item, entry))
        if not batch:
            return dead, batch, ()
        feats = np.zeros((self.slots, self.registry.num_features),
                         np.float32)
        slot_idx = np.zeros((self.slots,), np.int32)
        lo = np.zeros((self.slots,), np.int32)
        hi = np.zeros((self.slots,), np.int32)  # padding: empty window [0, 0)
        tau = np.full((self.slots,), -np.inf, np.float32)  # never escalate
        for i, (item, entry) in enumerate(batch):
            feats[i] = item.features
            slot_idx[i] = entry.slot
            lo[i], hi[i] = entry.window
            if self.tau_fn is not None:
                t = self.tau_fn(item.tenant_id)
                if t is not None:
                    tau[i] = t
        return dead, batch, (feats, slot_idx, lo, hi, tau)

    def drain(self) -> list[SlotResult]:
        out: list[SlotResult] = []
        while self._queue:
            out.extend(self.tick())
        return out
