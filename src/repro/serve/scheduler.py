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
``sched.pack`` (pop, placement lookup, the packed operand buffer) and
``acam_fused_dispatch``, which holds ``sched.launch`` (one upload and the
jitted call) and ``sched.fetch`` (the wait for the device and one copy
back); both carry the bytes they move as the span stat ``bytes``. On the
first tick after a registry or mesh generation change ``sched.launch``
also holds ``registry.place`` (stats ``bytes`` and ``shards``): the
super-bank and thresholds table placed on the plan's shardings.

Each tick crosses the host-device boundary once each way. `_pack` writes
the tick's operands into one int32 ``(slots, N + 4)`` buffer — the f32
features and tau as their bit patterns, then tenant slot and class window —
uploaded by one `jax.device_put`; `_packed_tick` unpacks it on the device,
runs `_batched_classify`, picks each slot's winner score out of
``per_class`` there, and returns one int32 ``(4, slots)`` array (pred,
escalate, and the bit patterns of margin and score) fetched by one
`jax.device_get`. ``per_class`` never leaves the device. The counters
`SchedulerStats.h2d_transfers` / ``d2h_transfers`` / ``bytes_back`` show it.
The cascade's dense head rides in the same call: the service installs a
``head_fn`` (placement -> the head and its stacked tables, or None with no
head table) and `_packed_tick` then computes every slot's head class over
the features it has unpacked, as a fifth result row (`SlotResult.head_pred`)
— a tick that escalates crosses the boundary no more often than one that
does not.
Under a sharded plan the buffer is uploaded on the plan's batch sharding
and the registry's arrays already sit on theirs (`repro.match.Placement`),
so the jitted tick reshards nothing between devices.
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
from jax import lax

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
    #: the dense head's tenant-local class for this slot, computed in the
    #: tick (`MicroBatchScheduler.head_fn`); -1 when the tick ran no head
    head_pred: int = -1


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
    h2d_transfers: int = 0  # host-to-device uploads (one per dispatch)
    d2h_transfers: int = 0  # device-to-host copies back (one per dispatch)
    bytes_back: int = 0  # bytes copied back, summed over ticks

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
            "h2d_transfers": self.h2d_transfers,
            "d2h_transfers": self.d2h_transfers,
            "bytes_back": self.bytes_back,
        }


@functools.partial(jax.jit, static_argnames=("config", "mesh_gen"))
def _batched_classify(bank, thr_table, feats, tenant_slot, class_lo, class_hi,
                      tau, *, config, mesh_gen: int):
    """The whole tick on device: ONE `MatchEngine.classify_serve` dispatch
    over the multi-tenant super-bank — the per-slot threshold-row gather,
    binarisation, windowed Eq. 12 decision/margin and the ``margin < tau``
    escalation bit included (a single pallas_call on the kernel backend).

    ``config`` is the full `repro.match.EngineConfig`, a *static* argument
    resolved eagerly by `tick()` (never the process default read at trace
    time), so switching backends — or any other engine knob, e.g. the
    device-physics noise config of a spec-built service — between ticks
    re-traces instead of replaying a stale executable. ``mesh_gen``
    (`distributed.context.generation()`, also static) does the same for
    the mesh: the engine bakes its `PartitionPlan` — batch over the dp
    axes, super-bank class rows over the model axis — into this trace, and
    installing a different mesh between ticks keys a fresh executable
    instead of silently replaying the stale layout."""
    del mesh_gen  # cache key only: a new mesh generation forces a re-trace
    eng = match_lib.engine_from_config(config)
    return eng.classify_serve(feats, thr_table, tenant_slot, bank, class_lo,
                              class_hi, tau)


def _as_f32(bits):
    return lax.bitcast_convert_type(bits, jnp.float32)


def _as_i32(x):
    return lax.bitcast_convert_type(x, jnp.int32)


@functools.partial(jax.jit, static_argnames=("classify", "head", "config",
                                             "mesh_gen"))
def _packed_tick(bank, thr_table, packed, w_table=None, b_table=None, *,
                 classify, head=None, config, mesh_gen: int):
    """The tick between one upload and one copy back. ``packed`` is the
    int32 ``(slots, N + 4)`` buffer of `MicroBatchScheduler._pack`:
    features and tau as f32 bit patterns, tenant slot, window lo and hi.
    -> int32 ``(4, slots)``: pred, escalate, and the bit patterns of the
    margin and of the winner's score ``per_class[i, pred[i]]``, a
    non-finite one (an unprogrammed class row's -inf) clamped to 0.0.
    ``per_class`` is logically (slots, C_cap) under every plan, so the
    gather is the same whatever the sharding.

    ``classify`` is `_batched_classify` as the module holds it at call time
    (static: replacing it keys a fresh trace); ``config`` and ``mesh_gen``
    are passed through to it, static here too. With a ``head`` (static
    too: the service's `_escalate_heads` as its module holds it) and its
    stacked tables, every slot's dense-head class over the same features
    comes back as a fifth row, ``(5, slots)``: the head of the slot's
    tenant, over the tenant's class count (the window's width; 1 for a
    padding slot's empty window)."""
    n = packed.shape[1] - 4
    feats = _as_f32(packed[:, :n])
    pred, per_class, margin, esc = classify(
        bank, thr_table, feats, packed[:, n + 1], packed[:, n + 2],
        packed[:, n + 3], _as_f32(packed[:, n]), config=config,
        mesh_gen=mesh_gen)
    score = jnp.take_along_axis(per_class, pred[:, None], axis=1)[:, 0]
    score = jnp.where(jnp.isfinite(score), score, 0.0)
    rows = [pred.astype(jnp.int32), esc.astype(jnp.int32), _as_i32(margin),
            _as_i32(score)]
    if head is not None:
        ncls = jnp.maximum(packed[:, n + 3] - packed[:, n + 2], 1)
        rows.append(head(w_table, b_table, feats, packed[:, n + 1],
                         ncls).astype(jnp.int32))
    return jnp.stack(rows)


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
        #: optional placement -> (head, w_table, b_table) or None: the
        #: cascade's dense head and its stacked tables on the placement's
        #: table sharding, installed by the service layer beside
        #: ``tau_fn``. When it gives a head, the tick computes every slot's
        #: head class in the same call (`SlotResult.head_pred`).
        self.head_fn = None
        self._queue: deque[WorkItem] = deque()
        self._place_key = None
        self._place = None

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
        `thresholds_table()` (placed once per registry and mesh
        generation), and queued `WorkItem`s
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
                dead, batch, packed = self._pack(n, t0)
            if not batch:
                return dead
            cfg = self.engine_config._replace(
                backend=self.backend or match_lib.default_backend())
            place, mesh_gen = self._placement(cfg)
            reg = self.registry
            with obs.span("acam_fused_dispatch"):
                with obs.span("sched.launch", bytes=packed.nbytes):
                    if not reg.is_placed(place):
                        shards = 1 if place is None else place.shards
                        with obs.span("registry.place", shards=shards,
                                      bytes=reg.placement_bytes()):
                            reg.device_bank(place)
                    head = None if self.head_fn is None \
                        else self.head_fn(place)
                    dev = jax.device_put(
                        packed, None if place is None else place.batch)
                    self.stats.h2d_transfers += 1
                    out = _packed_tick(
                        reg.device_bank(place), reg.thresholds_table(place),
                        dev, *(head[1:] if head else ()),
                        classify=_batched_classify,
                        head=head[0] if head else None, config=cfg,
                        mesh_gen=mesh_gen)
                with obs.span("sched.fetch", bytes=out.nbytes):
                    res = jax.device_get(out)
                    self.stats.d2h_transfers += 1
                    self.stats.bytes_back += res.nbytes
            dt = time.perf_counter() - t0
            self.last_verdict = self.monitor.observe(0, dt)
            slow = bool(self.last_verdict["stragglers"])
            self.stats.record_tick(len(batch), dt_s=dt, slow=slow)
            obs.record_tick_dispatch(len(batch), dt, slow)

            pred, esc = res[0], res[1]
            margin, score = res[2].view(np.float32), res[3].view(np.float32)
            head_pred = res[4] if len(res) > 4 else np.full_like(pred, -1)
            return dead + [
                SlotResult(item=item, entry=entry,
                           pred_local=int(pred[i]) - entry.offset,
                           margin=float(margin[i]), escalate=bool(esc[i]),
                           score=float(score[i]), head_pred=int(head_pred[i]))
                for i, (item, entry) in enumerate(batch)]

    def _placement(self, cfg: match_lib.EngineConfig):
        """(the tick's `repro.match.Placement` or None, mesh generation):
        where the super-bank, the thresholds table and the packed buffer
        must live for the engine's plan under ``cfg``, derived again only
        when the config, the mesh or the bank's capacity changes."""
        from repro.distributed import context

        gen = context.generation()
        key = (cfg, gen, self.registry.capacity_classes)
        if self._place_key != key:
            be = match_lib.engine_from_config(cfg).backend()
            self._place = match_lib.placement_for(
                batch=self.slots, num_classes=key[2],
                bank_shardable=be.supports_bank_sharding)
            self._place_key = key
        return self._place, gen

    def _pack(self, n: int, t0: float):
        """Pop ``n`` requests and pack the tick's operands. -> (results of
        requests whose tenant is gone, [(item, entry)] to serve, the int32
        ``(slots, N + 4)`` buffer: features, tau, tenant slot, window lo,
        window hi; f32 columns as their bit patterns)."""
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
            return dead, batch, None
        nf = self.registry.num_features
        # padding rows: zero features, never escalate, empty window [0, 0)
        packed = np.zeros((self.slots, nf + 4), np.int32)
        feats = packed[:, :nf].view(np.float32)
        tau = packed[:, nf].view(np.float32)
        tau[:] = -np.inf
        for i, (item, entry) in enumerate(batch):
            feats[i] = item.features
            packed[i, nf + 1] = entry.slot
            packed[i, nf + 2:] = entry.window
            if self.tau_fn is not None:
                t = self.tau_fn(item.tenant_id)
                if t is not None:
                    tau[i] = t
        return dead, batch, packed

    def drain(self) -> list[SlotResult]:
        out: list[SlotResult] = []
        while self._queue:
            out.extend(self.tick())
        return out
