"""The hybrid service's control plane: build-from-spec + live transitions.

`HybridService` is the front door the launchers, benchmarks and examples
construct through. It owns the two things the scattered constructor surface
never could:

**Construction order.** `HybridService.from_spec(spec)` executes the one
correct boot sequence — mesh install -> registry -> scheduler -> cascade —
so the old footgun (constructing `ACAMService` before `install_acam_mesh`
and silently getting `bank_shards=1`) cannot happen: the shard count comes
from the spec, and the spec's mesh is installed first.

**Runtime transitions.** `service.reconfigure(new_spec)` diffs the current
spec against the new one and executes the minimal live transition over a
drained scheduler:

    bank_shards change   LIVE RESHARD: drain -> `registry.reshard` re-packs
                         bucket runs to the new shard boundaries (zero
                         tenant re-registrations; slots, thresholds, head
                         tables and template rows survive) -> install the
                         new (data, model) mesh (the mesh generation
                         counter forces the scheduler's re-trace) -> the
                         next tick gathers the re-packed super-bank and
                         dispatches under the new `PartitionPlan`.
                         Predictions, margins and escalation decisions are
                         bit-identical across the transition (the engine's
                         cross-shard reduce contract). One documented
                         exception: the device backend under
                         `device_noise="per_shard"` with `sigma_program > 0`
                         — there the shard count IS the physical tiling
                         (one programmed array per shard, keyed
                         fold_in(seed, s)), so resharding re-programs the
                         arrays and legitimately re-realises the write
                         noise, exactly as re-tiling real RRAM would.
    engine change        backend/method/noise swap: the scheduler's next
                         tick dispatches under the new `EngineConfig` (a
                         fresh static jit key); taus are re-resolved into
                         the new backend's native margin units.
    scheduler change     new tick size: the scheduler is rebuilt over the
                         same registry (the queue is empty post-drain).
    cascade change       taus / energy attribution / admission bound are
                         re-derived for every registered tenant in place.

Transitions the spec cannot express live (a different feature dim, k_max
or bucket size — the banks themselves would change shape) raise
`ReconfigureError` before anything mutates.

**Durability & failover** (PR 6). `snapshot(ckpt)` / `restore(ckpt)`
persist and rebuild the full service through the atomic-rename
checkpointer (`repro.serve.snapshot`) — a killed service restarts
bit-identical, optionally onto a different mesh. `handle_device_loss`
degrades the live service onto the surviving devices (largest shard count
they can form, as an ordinary reconfigure transition); `restore_devices`
heals back to the full fleet.

The report returned by `reconfigure` carries the drained responses, the
action log, and the drain->resume wall time (`downtime_s`) — the number
`benchmarks/serving_bench.py --reshard` tracks.
"""
from __future__ import annotations

import dataclasses
import time

from repro.serve.acam_service import ACAMService, ClassifyResponse
from repro.serve.spec import MeshSpec, ServiceSpec


class ReconfigureError(ValueError):
    """The requested spec transition cannot be executed live."""


#: registry fields that shape the banks themselves — never live-mutable.
_FROZEN_REGISTRY_FIELDS = ("num_features", "k_max", "class_bucket")


@dataclasses.dataclass
class ReconfigureReport:
    """What a live transition did (and what it cost)."""

    spec: ServiceSpec  # the spec now in force
    actions: tuple[str, ...]  # human-readable transition log
    drained: list[ClassifyResponse]  # requests served during the quiesce
    downtime_s: float  # drain start -> resume wall time
    tenants_moved: int = 0  # reshard: tenants whose class offset changed


@dataclasses.dataclass
class ManifestReport:
    """What `apply_manifest` did: the tenant-set analogue of
    `ReconfigureReport` (ids per transition kind, no drain involved —
    every transition rides the hot register/update/evict paths)."""

    manifest: "object"  # the FleetManifest now in force
    added: tuple[str, ...]
    evicted: tuple[str, ...]
    updated: tuple[str, ...]
    retuned: tuple[str, ...]
    duration_s: float

    @property
    def empty(self) -> bool:
        return not (self.added or self.evicted or self.updated
                    or self.retuned)


def install_mesh(mesh: MeshSpec, devices=None):
    """Build and install the (data = devices/bank_shards, model =
    bank_shards) serving mesh described by a `MeshSpec`. Returns the mesh.

    This is the spec path's replacement for the old order-sensitive
    launcher helper: `HybridService.from_spec` calls it BEFORE any service
    tier exists, so registry placement and the engine's `PartitionPlan`
    can never disagree about the shard count.

    ``devices`` restricts the mesh to a survivor subset — the degraded
    path `handle_device_loss` takes after a simulated device failure.
    """
    from repro.distributed import context
    from repro.launch.mesh import make_serving_mesh

    built = make_serving_mesh(bank_shards=mesh.bank_shards,
                              axis_names=(mesh.data_axis, mesh.model_axis),
                              devices=devices)
    context.set_mesh_axes(mesh.data_axis, mesh.model_axis, built)
    return built


class HybridService(ACAMService):
    """`ACAMService` + the declarative lifecycle: one spec in, live
    transitions after. (The inherited legacy keyword constructor still
    works; `from_spec` is the intended front door.)"""

    @classmethod
    def from_spec(cls, spec: ServiceSpec) -> "HybridService":
        """Validate, install the spec's mesh (when it owns one), then build
        registry -> scheduler -> cascade in order."""
        spec.validate()
        svc = cls.__new__(cls)
        if spec.mesh.install:
            install_mesh(spec.mesh)
        svc._build(spec)
        return svc

    def reconfigure(self, new_spec: ServiceSpec) -> ReconfigureReport:
        """Diff specs and execute the minimal live transition (see module
        docstring). Pending requests are drained — served under the OLD
        config — before anything switches; their responses are returned in
        the report so no work is lost."""
        new_spec.validate()
        old = self.spec
        for field in _FROZEN_REGISTRY_FIELDS:
            if getattr(new_spec.registry, field) != \
                    getattr(old.registry, field):
                raise ReconfigureError(
                    f"registry.{field} cannot change live "
                    f"({getattr(old.registry, field)} -> "
                    f"{getattr(new_spec.registry, field)}): the registered "
                    "banks would change shape; build a fresh service")
        if new_spec.mesh.install:
            # fail BEFORE any mutation: a mesh the devices cannot form must
            # not strand a resharded registry behind the old mesh (after a
            # device loss, "available" means the survivors)
            ndev = len(self._avail_devices())
            if ndev % new_spec.mesh.bank_shards:
                raise ReconfigureError(
                    f"mesh.bank_shards={new_spec.mesh.bank_shards} does not "
                    f"divide the {ndev} available devices; nothing was "
                    "changed")
        if new_spec == old:
            return ReconfigureReport(spec=old, actions=(), drained=[],
                                     downtime_s=0.0)

        t0 = time.perf_counter()
        drained = self.drain()
        actions: list[str] = []
        moved = 0

        reshard = new_spec.mesh.bank_shards != old.mesh.bank_shards
        if reshard:
            moved = self.registry.reshard(new_spec.mesh.bank_shards)
            actions.append(
                f"resharded super-bank {old.mesh.bank_shards} -> "
                f"{new_spec.mesh.bank_shards} ({moved} tenant runs "
                f"re-packed, 0 re-registrations)")
            self.obs.emit("reshard",
                          bank_shards_from=old.mesh.bank_shards,
                          bank_shards_to=new_spec.mesh.bank_shards)
        if new_spec.mesh != old.mesh or reshard:
            if new_spec.mesh.install:
                install_mesh(new_spec.mesh, devices=self._devices)
                actions.append(
                    f"installed ({new_spec.mesh.data_axis}, "
                    f"{new_spec.mesh.model_axis}={new_spec.mesh.bank_shards})"
                    " mesh (generation bump -> scheduler re-trace)")

        if new_spec.engine != old.engine:
            self.scheduler.set_engine(new_spec.engine)
            actions.append(f"engine {old.engine.backend}/{old.engine.method}"
                           f" -> {new_spec.engine.backend}/"
                           f"{new_spec.engine.method}")
        if new_spec.scheduler != old.scheduler:
            from repro.serve.scheduler import MicroBatchScheduler

            stats = self.scheduler.stats  # cumulative view stays coherent
            self.scheduler = MicroBatchScheduler(
                self.registry, slots=new_spec.scheduler.slots,
                engine=new_spec.engine, monitor=self.scheduler.monitor,
                recorder=self.obs)
            self.scheduler.tau_fn = self._margin_tau_of
            self.scheduler.head_fn = self._head_of_tick
            stats.slots = new_spec.scheduler.slots
            self.scheduler.stats = stats
            self.obs.slots_gauge.set(new_spec.scheduler.slots)
            actions.append(f"scheduler slots {old.scheduler.slots} -> "
                           f"{new_spec.scheduler.slots}")
        if new_spec.cascade != old.cascade:
            actions.append("cascade re-derived (tau/energy/admission)")
        # always re-derive the cascade view: tau units depend on the engine
        # backend/method as much as on the cascade block itself
        self._apply_cascade(new_spec)
        self.spec = new_spec
        downtime_s = time.perf_counter() - t0
        self.obs.emit("reconfigure", actions=list(actions),
                      drained=len(drained),
                      duration_ms=round(downtime_s * 1e3, 3))
        return ReconfigureReport(spec=new_spec, actions=tuple(actions),
                                 drained=drained,
                                 downtime_s=downtime_s,
                                 tenants_moved=moved)

    # ------------------------------------------------- fleet (repro.fleet)

    def apply_manifest(self, manifest) -> ManifestReport:
        """Diff a `FleetManifest` against the one in force and execute the
        minimal tenant transitions — the tenant-set analogue of
        `reconfigure`:

            only in new        register (bank from seed/checkpoint + head)
            only in old        evict
            bank source moved  hot update in place (checkpoint-path or
                               seed/shape change forces the bank reload)
            epoch bumped       evict + re-register (forced fresh placement)
            tau-only change    retune the threshold (registry untouched)

        All transitions ride the hot paths, so bucketed shapes — and every
        jitted caller's trace cache — stay untouched in the steady state;
        a no-op manifest produces zero transitions and zero retraces.
        Per-tenant taus are converted from the MANIFEST'S declared units
        into the spec's `cascade.tau_units` before installation
        (`fleet.manifest.tau_in_units`), so one manifest serves specs in
        either unit system."""
        from repro.fleet import manifest as manifest_lib

        new = manifest.validate().normalized()
        old = getattr(self, "_manifest", None) or \
            manifest_lib.FleetManifest()
        diff = manifest_lib.diff_manifests(old, new)
        t0 = time.perf_counter()
        n = self.registry.num_features
        units = self.spec.cascade.tau_units
        by_id = new.by_id()

        def _tau(t):
            return manifest_lib.tau_in_units(t.tau, t.tau_units, units, n)

        for tid in diff.evict:
            if tid in self.registry:
                self.evict_tenant(tid)
        for tid in diff.add:
            t = by_id[tid]
            bank, head = manifest_lib.materialize(t, n)
            if tid in self.registry:  # adopting an imperatively-registered
                self.update_tenant(tid, bank, head=head,  # tenant
                                   margin_tau=_tau(t))
            else:
                self.register_tenant(tid, bank, head=head,
                                     margin_tau=_tau(t))
        for tid in diff.update:
            t = by_id[tid]
            bank, head = manifest_lib.materialize(t, n)
            self.update_tenant(tid, bank, head=head, margin_tau=_tau(t))
        for tid in diff.retune:
            self.retune_tenant(tid, margin_tau=_tau(by_id[tid]))
        self._manifest = new
        duration_s = time.perf_counter() - t0
        if not diff.empty:
            self.obs.emit("manifest_apply", added=list(diff.add),
                          evicted=list(diff.evict),
                          updated=list(diff.update),
                          retuned=list(diff.retune),
                          duration_ms=round(duration_s * 1e3, 3))
        return ManifestReport(manifest=new, added=diff.add,
                              evicted=diff.evict, updated=diff.update,
                              retuned=diff.retune, duration_s=duration_s)

    def rolling_reshard(self, new_spec: ServiceSpec, *,
                        prepared=None) -> ReconfigureReport:
        """The double-buffered reshard (`repro.fleet.reshard`): build the
        re-packed super-bank alongside the live one, then flip between
        ticks — NO drain, downtime is the flip + mesh install alone.
        Bit-identical preds/margins/escalations to the drained
        `reconfigure` path. Pass ``prepared`` (from `fleet.reshard.
        prepare`) to flip a buffer built earlier, overlapped with
        serving; without it this prepares and flips back to back."""
        from repro.fleet import reshard as reshard_lib

        if prepared is None:
            prepared = reshard_lib.prepare(self, new_spec)
        return reshard_lib.flip(self, prepared)

    def compact_registry(self) -> int:
        """Reclaim eviction debt: re-pack the super-bank into its smallest
        shard-aligned capacity (`TemplateBankRegistry.compact`). The
        fleet policy triggers this when occupancy drops below its
        threshold (`fleet.policy.should_compact`); safe live — queued
        requests resolve placements at tick time. Returns class rows
        freed."""
        return self.registry.compact()

    # ------------------------------------------------------- durability

    def snapshot(self, ckpt, step: int | None = None, *,
                 blocking: bool = True) -> int:
        """Persist the full service state (registry, placements, taus, head
        tables, spec) through the atomic-rename checkpointer. Returns the
        step written. See `repro.serve.snapshot`."""
        from repro.serve import snapshot as snapshot_lib

        step = snapshot_lib.save_snapshot(self, ckpt, step,
                                          blocking=blocking)
        self.obs.emit("snapshot", step=step, path=str(ckpt.dir))
        return step

    @classmethod
    def restore(cls, ckpt, step: int | None = None, *,
                mesh: MeshSpec | None = None):
        """Rebuild a ready-to-serve service from its latest (or a given)
        snapshot — bit-identical preds/margins/escalations, zero tenant
        re-registrations. ``mesh`` restores onto a DIFFERENT mesh (elastic
        shrink/grow across a restart). Returns ``(service,
        RestoreReport)``."""
        from repro.serve import snapshot as snapshot_lib

        svc, report = snapshot_lib.restore_service(ckpt, step, mesh=mesh,
                                                   cls=cls)
        svc.obs.emit("restore", step=report.step,
                     resharded=report.resharded,
                     duration_ms=round(report.restore_s * 1e3, 3))
        return svc, report

    # --------------------------------------------------- elastic failover

    def _avail_devices(self) -> list:
        """The devices the control plane may build meshes over: all of
        `jax.devices()` minus any reported lost."""
        import jax

        if self._devices is not None:
            return list(self._devices)
        return list(jax.devices())

    def handle_device_loss(self, lost) -> ReconfigureReport:
        """Degrade gracefully after a (simulated) device failure: drop the
        lost devices, pick the largest shard count the survivors can form,
        and reshard the live service onto them.

        ``lost`` is an iterable of device indices into the full
        `jax.devices()` list. Losses accumulate across calls (a second
        failure shrinks further); `restore_devices` heals the fleet. The
        reshard is the ordinary `reconfigure` transition — zero tenant
        re-registrations, bit-identical results after the shrink.
        """
        import jax

        all_devs = list(jax.devices())
        for i in lost:
            if not 0 <= i < len(all_devs):
                raise ReconfigureError(
                    f"device index {i} out of range (fleet has "
                    f"{len(all_devs)} devices)")
            self._lost_devices.add(int(i))
        survivors = [d for i, d in enumerate(all_devs)
                     if i not in self._lost_devices]
        if not survivors:
            raise ReconfigureError("all devices lost; nothing to serve on")
        self._devices = survivors

        # largest shard count the survivors can still form, capped at the
        # current one (device loss never widens the model axis)
        shards = min(self.spec.mesh.bank_shards, len(survivors))
        while len(survivors) % shards:
            shards -= 1
        target = self.spec._replace(
            mesh=self.spec.mesh._replace(bank_shards=shards))
        if target != self.spec:
            report = self.reconfigure(target)
        else:
            # same spec, fewer devices: the mesh itself must still shrink
            t0 = time.perf_counter()
            drained = self.drain()
            actions: tuple[str, ...] = ()
            if self.spec.mesh.install:
                install_mesh(self.spec.mesh, devices=survivors)
                actions = (f"reinstalled mesh on {len(survivors)} "
                           "surviving devices (generation bump -> "
                           "scheduler re-trace)",)
            report = ReconfigureReport(
                spec=self.spec, actions=actions, drained=drained,
                downtime_s=time.perf_counter() - t0)
        self.obs.emit("device_loss", lost=sorted(self._lost_devices),
                      survivors=len(survivors))
        return dataclasses.replace(
            report, actions=report.actions + (
                f"device loss: {len(self._lost_devices)} down, "
                f"{len(survivors)} surviving, bank_shards={shards}",))

    def restore_devices(self) -> ReconfigureReport:
        """Heal the fleet: forget recorded losses and rebuild the spec's
        mesh over the full device set (the repair-complete transition)."""
        self._lost_devices.clear()
        self._devices = None
        t0 = time.perf_counter()
        drained = self.drain()
        actions: tuple[str, ...] = ()
        if self.spec.mesh.install:
            install_mesh(self.spec.mesh)
            actions = ("restored full fleet: mesh reinstalled over all "
                       "devices",)
        self.obs.emit("device_heal", restored=len(self._avail_devices()))
        return ReconfigureReport(spec=self.spec, actions=actions,
                                 drained=drained,
                                 downtime_s=time.perf_counter() - t0)
