"""Multi-tenant ACAM classification service (the hybrid cascade core).

The intended front door is the spec path — ONE declarative
`repro.serve.spec.ServiceSpec` handed to
`repro.serve.control.HybridService.from_spec`, which owns construction
order (mesh -> registry -> scheduler -> cascade) and live transitions
(`reconfigure`: reshard / backend swap / tau retune). The keyword
constructor below survives as a deprecated shim that builds the same spec
(`repro.serve.spec.from_legacy`).

Turns the fused Pallas classify kernel into a service tier:

    submit -> admission (known tenant, feature dim, queue bound)
           -> micro-batching scheduler (ONE fused classify dispatch per
              tick over the registry's super-bank; `repro.serve.scheduler`)
           -> confidence cascade: the per-request Eq. 12 winner-vs-runner-up
              **margin** decides
                accept-at-ACAM   (margin >= tau): charge E_backend only
                escalate         (margin <  tau): run the tenant's CNN
                                 logits head on the same features; charge
                                 E_frontend + E_backend (paper §V-D via
                                 `repro.core.energy`)
           -> per-request `ClassifyResponse` + aggregated service metrics
              (throughput, p50/p99 latency, escalation rate, nJ/request).

Every number the service reports lives in its `repro.obs.FlightRecorder`
(`self.obs`): `metrics()` and `health()` are thin reads over its metric
registry, every admitted request is counted in and out of its conservation
totals, the SS V-D energy ledger aggregates there, and — when the spec sets
`obs.telemetry_dir` — a JSONL event log records every tick and lifecycle
event. The shed_p99_ms overload check reads the SAME histogram quantile
`metrics()` reports (one source of truth, not three reservoirs).

With `obs.profile_annotations` on, a step is traced in the recorder's spans
(`FlightRecorder.span`): ``acam.step`` around the whole step, holding the
scheduler's ``sched.tick`` (`repro.serve.scheduler`), ``acam.escalate``
(the escalated answers read from the tick's result; stat ``n`` escalated)
and ``acam.finalize`` (the per-request accounting). What ``acam.step``
holds outside them is the cascade loop and the building of the responses.

The dense head is answered inside the scheduler's tick: the service
installs ``head_fn`` beside ``tau_fn``, and the tick's one jitted call
computes every slot's head class over the features it already holds
(`SlotResult.head_pred`), so a step that escalates makes one upload, one
call and one copy back, as one that does not. The stacked head tables are
placed once per head generation and mesh placement. Tenants without a
registered head never escalate.

`make_synthetic_tenant` / `sample_tenant_queries` build deterministic
per-tenant banks + matching nearest-centroid heads without training a CNN —
the launcher (`repro.launch.serve --workload acam`), the serving benchmark
(`benchmarks/serving_bench.py`) and the tests all share them. For a real
front-end, fit a bank with `repro.core.hybrid.fit_acam_head` and pass the
model's dense head weights (see `examples/serve_batched.py`).
"""
from __future__ import annotations

import dataclasses
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import energy as energy_lib
from repro.core import templates
from repro.core.templates import TemplateBank
from repro.obs import FlightRecorder
from repro.serve.registry import RegistryError, TemplateBankRegistry
from repro.serve.scheduler import MicroBatchScheduler, SlotResult, WorkItem


class AdmissionError(ValueError):
    """Request rejected at admission (unknown tenant, bad shape, overload)."""


@dataclasses.dataclass(frozen=True)
class ClassifyRequest:
    """One classification request: a tenant's raw front-end feature map."""

    tenant_id: str
    features: np.ndarray  # (N,) float32


@dataclasses.dataclass
class ClassifyResponse:
    request_id: int
    tenant_id: str
    pred: int  # tenant-local class id; -1 on error
    margin: float  # Eq. 12 confidence margin at the ACAM
    escalated: bool  # False: accepted at the ACAM back-end
    energy_j: float  # E_backend, or E_frontend + E_backend if escalated
    latency_s: float  # submit -> response wall time
    error: str | None = None  # e.g. tenant evicted while the request queued
    #: True: overload degraded this answer — the margin asked for CNN
    #: escalation but load-shed mode served the ACAM winner instead
    shed: bool = False
    #: winner's absolute per-class score in native units (0 on error).
    #: The semantic-cache router's hit_score floor reads this; plain
    #: classification traffic can ignore it.
    score: float = 0.0


@dataclasses.dataclass(frozen=True)
class ServiceConfig:
    slots: int = 64  # scheduler micro-batch size
    method: str = "feature_count"
    alpha: float = 1.0
    #: cascade accept threshold, always in match-count units (0..N). The
    #: device-physics backend senses matchline *fractions* (0..1) — the
    #: service rescales tau by 1/N automatically when constructed with
    #: backend="device", so callers never convert units themselves.
    margin_tau: float = 8.0
    max_queue: int = 4096  # admission bound
    # paper §V-D energy attribution (repro.core.energy.hybrid_report defaults)
    frontend_macs: int = 23_785_120
    frontend_sparsity: float = 0.80
    softmax_head_ops: int = 7_850
    paper_faithful: bool = True


@dataclasses.dataclass
class _TenantRuntime:
    has_head: bool  # False: cascade disabled (no escalation target)
    raw_tau: float | None  # per-tenant override in the spec's tau_units
    margin_tau: float | None  # resolved to native units; None: no head
    backend_j: float  # Eq. 14 energy of this tenant's programmed rows


@jax.jit
def _escalate_heads(w_table, b_table, feats, head_slot, n_classes):
    """The dense head over a batch of slots: traced into the scheduler's
    tick (`MicroBatchScheduler.head_fn`), or called alone.

    Gathers each slot's tenant head from the stacked table and masks class
    columns beyond the tenant's true class count.
    """
    w = jnp.take(w_table, head_slot, axis=0)  # (S, N, C)
    b = jnp.take(b_table, head_slot, axis=0)  # (S, C)
    logits = jnp.einsum("sn,snc->sc", feats, w) + b
    cols = jnp.arange(logits.shape[-1])[None, :]
    logits = jnp.where(cols < n_classes[:, None], logits, -jnp.inf)
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


class ACAMService:
    """Request/response front for multi-tenant hybrid ACAM classification."""

    def __init__(self, num_features: int, *,
                 config: ServiceConfig = ServiceConfig(), k_max: int = 2,
                 class_bucket: int = 16, backend: str | None = None,
                 bank_shards: int | None = None):
        """DEPRECATED shim over the spec path: prefer
        `repro.serve.control.HybridService.from_spec(ServiceSpec(...))`,
        which owns mesh install order and enables live `reconfigure`. These
        keywords are bridged 1:1 through `repro.serve.spec.from_legacy`.

        ``backend`` pins the scheduler's `repro.match` engine backend
        ("reference" | "kernel" | "device" | "auto"); None resolves the
        process default ONCE, here. "device" serves every tick through the
        RRAM-CMOS physics models — margins are then matchline fractions,
        and every margin_tau (given in match-count units) is rescaled by
        1/num_features (`ServiceSpec.tau_scale`).

        ``bank_shards`` aligns the registry's tenant placement to the bank
        shards the engine's `PartitionPlan` cuts the super-bank into. None
        infers it from the installed mesh — which is the ordering footgun
        this constructor is deprecated for: with no mesh installed it
        silently resolves to 1, so it now warns. `from_spec` makes the
        shard count explicit and installs the mesh itself."""
        from repro import match as match_lib
        from repro.serve import spec as spec_lib

        if bank_shards is None:
            from repro.distributed import context

            if context.get_mesh() is None:
                warnings.warn(
                    "ACAMService(bank_shards=None) with no mesh installed: "
                    "bank_shards silently resolves to 1. If you meant to "
                    "shard the super-bank, install the serving mesh BEFORE "
                    "constructing the service — or switch to the spec path "
                    "(repro.serve.control.HybridService.from_spec), which "
                    "owns mesh install order and makes this impossible.",
                    UserWarning, stacklevel=2)
            bank_shards = match_lib.bank_shards_in_mesh()
        self._build(spec_lib.from_legacy(
            num_features, config=config, k_max=k_max,
            class_bucket=class_bucket, backend=backend,
            bank_shards=bank_shards))

    def _build(self, spec) -> None:
        """Construct every tier from a validated `ServiceSpec` in the one
        correct order: registry -> scheduler -> cascade. (The mesh, when
        the spec owns it, is installed before this runs —
        `HybridService.from_spec`.)"""
        spec.validate()
        self.spec = spec
        #: the flight recorder: metric registry + conservation totals +
        #: energy ledger + event log + serving spans. Lives as long as the
        #: service (reconfigure rebuilds schedulers, never this).
        self.obs = FlightRecorder(spec.obs)
        self.registry = TemplateBankRegistry(
            spec.registry.num_features, k_max=spec.registry.k_max,
            class_bucket=spec.registry.class_bucket,
            initial_classes=spec.registry.initial_classes,
            initial_tenants=spec.registry.initial_tenants,
            bank_shards=spec.mesh.bank_shards)
        self.scheduler = MicroBatchScheduler(
            self.registry, slots=spec.scheduler.slots, engine=spec.engine,
            recorder=self.obs)
        # the cascade's tau rides into the serve kernel: the scheduler asks
        # this per dispatched request and the margin < tau compare happens
        # in the fused dispatch (SlotResult.escalate), not here in python
        self.scheduler.tau_fn = self._margin_tau_of
        self.scheduler.head_fn = self._head_of_tick
        self.scheduler.monitor.sink = self.obs.record_straggler
        self.obs.slots_gauge.set(spec.scheduler.slots)
        #: control-plane failure state (simulated device loss): None = every
        #: jax device is healthy; else the surviving device list every mesh
        #: (re)install is built over (`HybridService.handle_device_loss`)
        self._devices = None
        self._lost_devices: set[int] = set()
        self._tenants: dict[str, _TenantRuntime] = {}
        self._head_w: np.ndarray | None = None  # (T_cap, N, C_head)
        self._head_b: np.ndarray | None = None  # (T_cap, C_head)
        #: ((head generation, mesh generation), placement, (W, b)): the one
        #: copy of the head tables on the device
        self._head_cache: tuple | None = None
        self._head_gen = 0
        #: head-table placements on the device: one per head generation,
        #: mesh generation and placement that served, none per tick
        self.head_placements = 0
        self._next_id = 0
        self._apply_cascade(spec)

    def _apply_cascade(self, spec) -> None:
        """(Re)derive everything the cascade spec controls: the legacy
        `ServiceConfig` view, tau unit conversion, the §V-D front-end
        energy, and every registered tenant's resolved threshold. Called at
        build AND by the control plane's live transitions."""
        casc = spec.cascade
        self.spec = spec
        self.config = ServiceConfig(
            slots=spec.scheduler.slots, method=spec.engine.method,
            alpha=spec.engine.alpha, margin_tau=casc.tau,
            max_queue=casc.max_queue, frontend_macs=casc.frontend_macs,
            frontend_sparsity=casc.frontend_sparsity,
            softmax_head_ops=casc.softmax_head_ops,
            paper_faithful=casc.paper_faithful)
        self._tau_scale = spec.tau_scale()
        effective = int(round(casc.frontend_macs
                              * (1.0 - casc.frontend_sparsity)))
        effective -= casc.softmax_head_ops
        self._frontend_j = energy_lib.frontend_energy(
            effective, paper_faithful=casc.paper_faithful)
        for rt in self._tenants.values():
            rt.margin_tau = self._resolve_tau(rt.raw_tau) if rt.has_head \
                else None

    def _resolve_tau(self, raw: float | None) -> float:
        """Spec-units tau (per-tenant override or the cascade default) ->
        the served backend's native margin units."""
        tau = self.spec.cascade.tau if raw is None else raw
        return tau * self._tau_scale

    def _margin_tau_of(self, tenant_id: str) -> float | None:
        """The scheduler's `tau_fn`: resolved margin threshold for one
        tenant (None = no CNN head registered, never escalate)."""
        rt = self._tenants.get(tenant_id)
        return None if rt is None else rt.margin_tau

    # -- tenant lifecycle ---------------------------------------------------

    def register_tenant(self, tenant_id: str, bank: TemplateBank, *,
                        head: tuple[np.ndarray, np.ndarray] | None = None,
                        margin_tau: float | None = None) -> None:
        """Hot-register a tenant: templates into the super-bank, optional
        (W, b) CNN logits head enabling the escalation path."""
        head = self._check_head(head)  # validate BEFORE mutating the registry
        entry = self.registry.register(tenant_id, bank)
        self._install(tenant_id, entry.slot, entry.valid_rows, head,
                      margin_tau)

    def update_tenant(self, tenant_id: str, bank: TemplateBank, *,
                      head: tuple[np.ndarray, np.ndarray] | None = None,
                      margin_tau: float | None = None) -> None:
        head = self._check_head(head)
        entry = self.registry.update(tenant_id, bank)
        self._install(tenant_id, entry.slot, entry.valid_rows, head,
                      margin_tau)

    def evict_tenant(self, tenant_id: str) -> None:
        self.registry.evict(tenant_id)
        del self._tenants[tenant_id]

    def retune_tenant(self, tenant_id: str, *,
                      margin_tau: float | None) -> None:
        """Change ONLY a tenant's cascade threshold (spec tau_units; None
        reverts to the cascade default) — no registry touch, no head
        change, no retrace. The manifest path's tau-only transition."""
        rt = self._tenants[tenant_id]
        rt.raw_tau = margin_tau
        rt.margin_tau = self._resolve_tau(margin_tau) if rt.has_head \
            else None

    def _check_head(self, head):
        if head is None:
            return None
        w = np.asarray(head[0], np.float32)
        b = np.asarray(head[1], np.float32)
        if w.shape[0] != self.registry.num_features:
            raise RegistryError(
                f"head expects {w.shape[0]} features, registry serves "
                f"{self.registry.num_features}")
        if w.shape[1] != b.shape[0]:
            raise RegistryError(
                f"head shapes disagree: W {w.shape} vs b {b.shape}")
        return w, b

    def _install(self, tenant_id, slot, valid_rows, head, margin_tau):
        if head is not None:
            self._head_store(slot, head[0], head[1])
        self._tenants[tenant_id] = _TenantRuntime(
            has_head=head is not None, raw_tau=margin_tau,
            margin_tau=self._resolve_tau(margin_tau)
            if head is not None else None,
            backend_j=energy_lib.backend_energy(valid_rows,
                                                self.registry.num_features))

    def head_of(self, tenant_id: str) -> tuple[np.ndarray, np.ndarray]:
        """The tenant's (W (N, C), b (C,)) escalation head, read back from
        the stacked tables (the single source of truth the escalation
        dispatch gathers from)."""
        entry = self.registry.get(tenant_id)
        c = entry.num_classes
        if self._head_w is None or not self._tenants[tenant_id].has_head:
            raise RegistryError(f"tenant {tenant_id!r} has no head")
        return (self._head_w[entry.slot, :, :c].copy(),
                self._head_b[entry.slot, :c].copy())

    def _head_store(self, slot: int, w: np.ndarray, b: np.ndarray) -> None:
        t_cap = self.registry.capacity_tenants
        n = self.registry.num_features
        c = w.shape[1]
        c_head = c if self._head_w is None else \
            max(c, self._head_w.shape[-1])
        if (self._head_w is None or self._head_w.shape[0] < t_cap
                or self._head_w.shape[-1] < c_head):
            new_w = np.zeros((t_cap, n, c_head), np.float32)
            new_b = np.full((t_cap, c_head), -np.inf, np.float32)
            if self._head_w is not None:
                ow, ob = self._head_w, self._head_b
                new_w[:ow.shape[0], :, :ow.shape[-1]] = ow
                new_b[:ob.shape[0], :ob.shape[-1]] = ob
            self._head_w, self._head_b = new_w, new_b
        self._head_w[slot, :, :c] = w
        self._head_w[slot, :, c:] = 0.0
        self._head_b[slot, :c] = b
        self._head_b[slot, c:] = -np.inf
        self._head_gen += 1
        self._head_cache = None

    def _head_tables(self, placement=None) -> tuple[jax.Array, jax.Array]:
        """The stacked (W, b) head tables on the device, put from host
        memory once per head generation and mesh generation: whole on every
        device of a sharded plan (``placement.table``), else uncommitted on
        the default device. The device holds one copy: a call without a
        placement is given the copy already placed, and a new placement
        replaces the old copy."""
        from repro.distributed import context

        key = (self._head_gen, context.generation())
        cache = self._head_cache
        if cache is None or cache[0] != key or \
                placement not in (None, cache[1]):
            table = None if placement is None else placement.table
            cache = self._head_cache = (
                key, placement, (jax.device_put(self._head_w, table),
                                 jax.device_put(self._head_b, table)))
            self.head_placements += 1
        return cache[2]

    def _head_of_tick(self, placement):
        """The scheduler's `head_fn`: the dense head (this module's
        `_escalate_heads` as it stands at call time) and its tables on the
        tick's placement; None while no tenant has registered a head."""
        if self._head_w is None:
            return None
        return (_escalate_heads, *self._head_tables(placement))

    # -- request path -------------------------------------------------------

    def submit(self, request: ClassifyRequest) -> int:
        """Admit one request into the scheduler queue; returns request id.
        Admission counts the request into the recorder's conservation
        totals."""
        if request.tenant_id not in self.registry:
            self.obs.record_rejected()
            raise AdmissionError(f"unknown tenant {request.tenant_id!r}")
        feats = np.asarray(request.features, np.float32).reshape(-1)
        if feats.shape[0] != self.registry.num_features:
            self.obs.record_rejected()
            raise AdmissionError(
                f"expected {self.registry.num_features} features, got "
                f"{feats.shape[0]}")
        if self.scheduler.qsize >= self.config.max_queue:
            self.obs.record_rejected()
            raise AdmissionError(
                f"queue full ({self.config.max_queue} pending)")
        self._next_id += 1
        t_admit = time.perf_counter()
        self.scheduler.submit(WorkItem(
            request_id=self._next_id, tenant_id=request.tenant_id,
            features=feats, submit_t=t_admit))
        self.obs.record_submit()
        self.obs.set_queue_depth(self.scheduler.qsize)
        return self._next_id

    def overloaded(self) -> bool:
        """Is the service past its overload thresholds RIGHT NOW? True when
        the queue has grown to ``cascade.shed_queue`` or the rolling p99
        latency exceeds ``cascade.shed_p99_ms`` — the next tick then runs
        in load-shed mode (ACAM stage alone, no CNN escalation: the paper's
        E_backend << E_frontend asymmetry as an overload policy).

        The p99 here is `FlightRecorder.latency_quantile_ms(0.99)` — the
        IDENTICAL read `metrics()['latency_p99_ms']` reports, from the
        histogram's rolling window (bounded, so a burst's tail stops
        poisoning the estimate once the service recovers; it also survives
        `reset_metrics()`, which must never blind this check)."""
        casc = self.spec.cascade
        if casc.shed_queue is not None \
                and self.scheduler.qsize >= casc.shed_queue:
            return True
        if casc.shed_p99_ms is not None \
                and self.obs.latency.window_count >= 32 \
                and self.obs.latency_quantile_ms(0.99) > casc.shed_p99_ms:
            return True
        return False

    def step(self) -> list[ClassifyResponse]:
        """One scheduler tick + the cascade over its results.

        Resilience duties run first: requests older than the cascade's
        per-request deadline are expired with an error (serving them
        uselessly late helps nobody), and an overloaded tick degrades
        gracefully — every slot is answered from the ACAM stage alone
        (``shed=True`` where the margin asked for escalation) instead of
        queueing CNN head work behind a growing backlog."""
        with self.obs.span("acam.step"):
            return self._step()

    def _step(self) -> list[ClassifyResponse]:
        t0 = time.perf_counter()
        responses: list[ClassifyResponse] = []
        casc = self.spec.cascade
        if casc.deadline_ms is not None:
            for item in self.scheduler.expire(casc.deadline_ms / 1e3):
                responses.append(ClassifyResponse(
                    request_id=item.request_id, tenant_id=item.tenant_id,
                    pred=-1, margin=0.0, escalated=False, energy_j=0.0,
                    latency_s=time.perf_counter() - item.submit_t,
                    error=f"deadline exceeded ({casc.deadline_ms} ms "
                          "in queue)"))
        n_expired = len(responses)
        shedding = self.overloaded()
        self.obs.set_shed_mode(shedding, queue_depth=self.scheduler.qsize)
        results = self.scheduler.tick()
        if not results:
            if responses:
                self._finalize_step(responses, t0, shedding, fill=0,
                                    n_expired=n_expired, dispatched=False,
                                    escalation=False)
            return responses
        if shedding:
            self.obs.record_shed_tick()
        escalate: list[SlotResult] = []
        keep: list[tuple[SlotResult, bool, bool]] = []
        for r in results:
            rt = self._tenants.get(r.item.tenant_id) if r.error is None \
                else None
            # the margin < tau compare already ran inside the serve kernel
            # (SlotResult.escalate); rt guards tenants evicted mid-flight.
            # _wants_escalation is the routing-policy hook: the base
            # cascade trusts the in-kernel bit verbatim, the semantic
            # cache adds its absolute hit_score floor on top.
            wants = rt is not None and self._wants_escalation(r)
            if wants and not shedding:
                escalate.append(r)
                keep.append((r, True, False))
            else:
                # shed: the margin asked for the CNN head but overload says
                # answer from the ACAM stage alone
                keep.append((r, False, wants))

        esc_pred: dict[int, int] = {}
        if escalate:
            esc_pred = self._run_escalation(escalate)

        now = time.perf_counter()
        fcost: dict[int, float] = {}
        for r, escalated, shed in keep:
            if r.error is not None:
                responses.append(ClassifyResponse(
                    request_id=r.item.request_id,
                    tenant_id=r.item.tenant_id, pred=-1, margin=0.0,
                    escalated=False, energy_j=0.0,
                    latency_s=now - r.item.submit_t, error=r.error))
                continue
            rt = self._tenants[r.item.tenant_id]
            pred = esc_pred[r.item.request_id] if escalated else r.pred_local
            fj = self._frontend_cost(r.item.request_id) if escalated else 0.0
            fcost[r.item.request_id] = fj
            e = rt.backend_j + fj
            responses.append(ClassifyResponse(
                request_id=r.item.request_id,
                tenant_id=r.item.tenant_id, pred=pred,
                margin=r.margin, escalated=escalated, energy_j=e,
                latency_s=now - r.item.submit_t, shed=shed,
                score=r.score))
        self._finalize_step(responses, t0, shedding, fill=len(results),
                            n_expired=n_expired, dispatched=True,
                            escalation=bool(escalate), now=now,
                            frontend=fcost)
        return responses

    def _finalize_step(self, responses: list[ClassifyResponse], t0: float,
                       shedding: bool, *, fill: int, n_expired: int,
                       dispatched: bool, escalation: bool,
                       now: float | None = None,
                       frontend: dict[int, float] | None = None) -> None:
        """Book one step into the flight recorder: close every response
        (disposition + latency + SS V-D energy split), bump the busy clock
        and queue gauge, and — when the event log is on — append the
        step's "tick" line. Pure accounting: preds/margins/escalations are
        already fixed by the time this runs, so telemetry can never change
        a served answer."""
        obs = self.obs
        with obs.span("acam.finalize"):
            if escalation:
                obs.record_escalation_dispatch()
            for r in responses:
                if r.error is not None:
                    obs.finish_request(r, 0.0, 0.0)
                else:
                    rt = self._tenants[r.tenant_id]
                    if frontend is not None:
                        fj = frontend.get(r.request_id, 0.0)
                    else:
                        fj = self._frontend_j if r.escalated else 0.0
                    obs.finish_request(r, rt.backend_j, fj)
            now = time.perf_counter() if now is None else now
            obs.add_busy(now - t0)
            obs.set_queue_depth(self.scheduler.qsize)
            if obs.events.enabled:
                obs.emit(
                    "tick",
                    tick_id=obs.tick_seq - 1 if dispatched else -1,
                    fill=fill,
                    served=sum(r.error is None for r in responses),
                    escalated=sum(r.escalated for r in responses),
                    shed=sum(r.shed for r in responses),
                    expired=n_expired,
                    dt_ms=(round(obs.last_dispatch_ms, 4)
                           if dispatched else 0.0),
                    queue_depth=self.scheduler.qsize,
                    shed_mode=int(shedding),
                    energy_j=sum(r.energy_j for r in responses))

    def _wants_escalation(self, r: SlotResult) -> bool:
        """Routing-policy hook: should this served slot escalate to the
        expensive backend? The base cascade trusts the in-kernel
        ``margin < tau`` bit verbatim; `repro.serve.semantic_cache`
        overrides this to stack its absolute winner-score floor on top."""
        return r.escalate

    def _frontend_cost(self, request_id: int) -> float:
        """Energy charged for ONE escalated request. The base cascade's
        CNN head costs the same §V-D figure for every request; the
        semantic cache overrides this with the request's actual per-token
        decode cost. Only consulted for escalated requests — hits are
        charged E_backend alone."""
        del request_id
        return self._frontend_j

    def _run_escalation(self, escalate: list[SlotResult]) -> dict[int, int]:
        """The escalated slots' dense-head answers, read from the tick's
        head row (span ``acam.escalate``, stat ``n``).

        Every escalated slot has one: a slot escalates only if its tenant
        had a head when the tick packed it, so the head table existed when
        the same tick asked ``head_fn`` for it."""
        with self.obs.span("acam.escalate", n=len(escalate)):
            missing = [r.item.request_id for r in escalate
                       if r.head_pred < 0]
            if missing:
                raise RuntimeError(
                    f"escalated requests {missing} have no head row: the "
                    "scheduler ran its tick without the service's head_fn")
            self.obs.record_head_in_tick(len(escalate))
            return {r.item.request_id: r.head_pred for r in escalate}

    def drain(self) -> list[ClassifyResponse]:
        """Run ticks until the queue empties (the control plane's quiesce
        step: every pending request is served under the CURRENT config
        before a live transition switches anything)."""
        out: list[ClassifyResponse] = []
        while self.scheduler.qsize:
            out.extend(self.step())
        return out

    def serve(self, requests: list[ClassifyRequest]) -> list[ClassifyResponse]:
        """Submit a burst and run ticks until the queue drains."""
        for req in requests:
            self.submit(req)
        return self.drain()

    def metrics(self) -> dict:
        """The service's aggregate view — every value is a read over the
        flight recorder's registry/ledger (no service-private counters,
        no reservoirs): counters for the totals, the energy ledger for
        joules, and the ONE latency histogram for p50/p99 (the same
        quantile the shed_p99_ms overload check compares against)."""
        o = self.obs
        completed = int(o.responses.total())
        done = max(completed, 1)
        escalated = int(o.responses.value(disposition="escalated"))
        shed = int(o.responses.value(disposition="shed"))
        failed = int(o.responses.value(disposition="expired")
                     + o.responses.value(disposition="error"))
        busy = o.busy_seconds.value()
        ticks = int(o.ticks.value())
        slots = self.scheduler.slots
        energy_j = o.ledger.fleet_j()
        return {
            "submitted": int(o.submitted.value()),
            "completed": completed,
            "rejected": int(o.rejected.value()),
            "failed": failed,
            "escalated": escalated,
            "escalation_rate": round(escalated / done, 4),
            "shed": shed,
            "shed_rate": round(shed / done, 4),
            "load_shed_ticks": int(o.load_shed_ticks.value()),
            "escalation_dispatches": int(o.esc_dispatches.value()),
            "head_in_tick": int(o.head_in_tick.value()),
            "requests_per_s": round(completed / busy, 2) if busy else 0.0,
            "latency_p50_ms": round(o.latency_quantile_ms(0.50), 3),
            "latency_p99_ms": round(o.latency_quantile_ms(0.99), 3),
            "energy_total_j": energy_j,
            "nj_per_request": round(energy_j / done * 1e9, 4),
            "ticks": ticks,
            "classify_dispatches": int(o.dispatches.value()),
            "served": int(o.served.value()),
            "occupancy": round(o.filled_slots.value() / (ticks * slots), 4)
            if ticks else 0.0,
            "min_fill": int(o.fill_min.value()),
            "max_fill": int(o.fill_max.value()),
            "slots": slots,
            "tick_time_s": round(o.tick_seconds.value(), 6),
            "slow_ticks": int(o.slow_ticks.value()),
            "expired": int(o.expired.value()),
        }

    def health(self) -> dict:
        """Liveness view for operators, the chaos harness AND the fleet
        controller: straggler strikes, queue depth, load-shed state — plus
        the autoscaling policy's inputs as first-class fields (per-shard
        registered rows vs capacity, the fused kernel's VMEM row budget
        and the per-shard resident row count against it, rolling batch
        fill, the exact rolling p99, and the ledger's energy split), so
        `repro.fleet.policy.view_of` never reaches into private registry
        state."""
        from repro.kernels import layout
        from repro.match.backends import MAX_FUSED_ROWS

        verdict = self.scheduler.last_verdict or {}
        stats = self.registry.stats()
        rows = self.registry.rows_per_shard
        devices = len(self._devices) if self._devices is not None \
            else len(jax.devices())
        return {
            "queue_depth": self.scheduler.qsize,
            "load_shedding": self.overloaded(),
            "slow_ticks": int(self.obs.slow_ticks.value()),
            "straggler_strikes": {
                int(labels["host"]): int(v)
                for labels, v in self.obs.straggler_strikes.items()},
            "evict_verdict": list(verdict.get("evict", ())),
            # -- fleet-controller inputs (repro.fleet.policy) --
            "tenants": stats["tenants"],
            "bank_shards": stats["bank_shards"],
            "capacity_classes": stats["capacity_classes"],
            "rows_per_shard": rows,
            "shard_rows_used": self.registry.shard_rows_used(),
            # super-bank placements on the mesh: one per registry or mesh
            # generation that served, none per tick
            "bank_placements": stats["bank_placements"],
            "bank_bytes_placed": stats["bank_bytes_placed"],
            # stacked head tables on the device: one per head generation
            # and mesh placement, none per tick
            "head_placements": self.head_placements,
            # the resident serve kernel holds k_max * padded(rows/shard)
            # template rows in VMEM; past MAX_FUSED_ROWS it falls back to
            # the class-chunked path — headroom is the policy's VMEM signal
            "fused_rows_per_shard":
                self.registry.k_max * layout.padded_classes(rows),
            "vmem_budget_rows": MAX_FUSED_ROWS,
            "rolling_batch_fill": round(self.obs.rolling_batch_fill(), 3),
            "slots": self.scheduler.slots,
            "devices": devices,
            "p99_ms": round(self.obs.latency_quantile_ms(0.99), 4),
            "energy_backend_j": self.obs.ledger.backend_j(),
            "energy_frontend_j": self.obs.ledger.frontend_j(),
        }

    def reset_metrics(self) -> None:
        """Zero the run counters (e.g. after a warmup burst). Exact
        semantics, enforced by a regression test:

        CLEARED    counters (submitted/completed/rejected/..., scheduler
                   tick counters), cumulative latency-histogram counts,
                   the energy ledger, per-run fill aggregates
                   (min/max batch fill), and the scheduler's mirror
                   `SchedulerStats`.
        SURVIVING  gauges (queue depth, shed mode, straggler strikes —
                   they describe the service NOW), the latency
                   histogram's ROLLING window (the shed_p99_ms overload
                   signal: a metrics reset must never blind load
                   shedding), the request conservation totals (started,
                   finished, in flight), the tick-id sequence, straggler-monitor history, and
                   the append-only event log."""
        from repro.serve.scheduler import SchedulerStats

        self.obs.reset()
        self.scheduler.stats = SchedulerStats(slots=self.scheduler.slots)


# ---------------------------------------------------------------------------
# Synthetic tenants (launcher / benchmark / test fixtures)
# ---------------------------------------------------------------------------

def make_synthetic_tenant(
    seed: int, *, num_classes: int = 10, k: int = 1, num_features: int = 64,
    samples_per_class: int = 24, spread: float = 0.6,
) -> tuple[TemplateBank, tuple[np.ndarray, np.ndarray], np.ndarray]:
    """A deterministic per-tenant classifier without training a CNN.

    Draws class prototype feature maps, fits a `TemplateBank` from noisy
    samples around them (the per-device calibration of the wearable
    scenario), and pairs it with the matching nearest-centroid dense head
    ``logits_c = f . p_c - |p_c|^2 / 2`` for the escalation path.

    Returns (bank, (head_w (N, C), head_b (C,)), prototypes (C, N)).
    """
    rng = np.random.RandomState(seed)
    protos = rng.randn(num_classes, num_features).astype(np.float32)
    n = num_classes * samples_per_class
    labels = np.repeat(np.arange(num_classes, dtype=np.int32),
                       samples_per_class)
    feats = protos[labels] + spread * rng.randn(n, num_features).astype(
        np.float32)
    bank = templates.generate_templates(
        jnp.asarray(feats), jnp.asarray(labels), num_classes, k=k)
    head_w = protos.T.astype(np.float32)  # (N, C)
    head_b = (-0.5 * np.sum(protos**2, axis=1)).astype(np.float32)
    return bank, (head_w, head_b), protos


def sample_tenant_queries(
    seed: int, protos: np.ndarray, n: int, *, noise: float = 0.8,
) -> tuple[np.ndarray, np.ndarray]:
    """Draw n query feature maps around a tenant's prototypes.

    `noise` controls how many land near class boundaries (and therefore how
    often the cascade escalates). Returns (features (n, N), labels (n,)).
    """
    rng = np.random.RandomState(seed)
    num_classes, num_features = protos.shape
    labels = rng.randint(0, num_classes, size=n).astype(np.int32)
    feats = protos[labels] + noise * rng.randn(n, num_features).astype(
        np.float32)
    return feats.astype(np.float32), labels
