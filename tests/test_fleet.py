"""Self-driving fleet (`repro.fleet`): manifests, policy, autopilot.

Five layers of coverage:

  * `FleetManifest`/`TenantSpec` as value objects: validation, JSON
    round-trip, order-insensitive identity, tau unit conversion, and the
    `.npz` bank-checkpoint round-trip behind `materialize`;
  * `apply_manifest` edge cases on a live service: tau-unit-only change
    (pure retune), evict + re-add of the same id in one apply (epoch
    bump), a checkpoint-path change forcing the bank reload, and the
    no-op manifest (zero transitions, zero retraces — jit cache size
    asserted);
  * the satellite bugfix: registry eviction debt is reclaimed by
    `compact()`, placement-invariant (served results and surviving banks
    bit-identical across the re-pack);
  * the policy as a pure function: per-rule unit tests from hand-built
    frozen views, purity/determinism property-tested, `RegistryView`
    JSON round-trip (what every logged `policy_decision` carries);
  * the autopilot loop: double-buffered rolling reshard (prepare between
    ticks, flip at a boundary, no drain, bit-identical), stale-buffer
    rejection, drained-responses FIFO contract (`take_drained`), and
    log-only reconstruction — replaying `explain` over the JSONL
    event log's frozen views reproduces the executed action stream.

The forced-mesh (2x2) flip runs as a subprocess, mirroring
`test_service_spec.TestForcedMeshControlPlane`.
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from _hypothesis_fallback import given, settings, st
from repro.distributed import context
from repro.fleet import (Autopilot, FleetManifest, ManifestError, PolicySpec,
                         RegistryView, TenantSpec, decide, diff_manifests,
                         explain, load_bank, materialize, save_bank,
                         should_compact, view_of)
from repro.fleet import reshard as reshard_lib
from repro.match.config import EngineConfig
from repro.serve.acam_service import (ClassifyRequest, make_synthetic_tenant,
                                      sample_tenant_queries)
from repro.serve.control import HybridService, ReconfigureError
from repro.serve.registry import RegistryError, TemplateBankRegistry
from repro.serve.spec import (CascadeSpec, MeshSpec, ObsSpec, RegistrySpec,
                              SchedulerSpec, ServiceSpec)

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
N = 64


def _spec(backend="reference", *, bank_shards=1, slots=16, tau=6.0,
          telemetry_dir=None, **engine_kw):
    return ServiceSpec(
        registry=RegistrySpec(num_features=N, initial_classes=256),
        engine=EngineConfig(backend=backend, margin=True, **engine_kw),
        mesh=MeshSpec(bank_shards=bank_shards, install=False),
        scheduler=SchedulerSpec(slots=slots),
        cascade=CascadeSpec(tau=tau, tau_units="count"),
        obs=ObsSpec(telemetry_dir=telemetry_dir),
    )


def _manifest(tenants=4, classes=40, **tenant_kw):
    """Seeds match `_protos`, so manifest-registered tenants serve the
    same queries as imperatively-registered ones."""
    return FleetManifest(tenants=tuple(
        TenantSpec(f"t{t}", seed=1000 + 17 * t, num_classes=classes,
                   **tenant_kw)
        for t in range(tenants)))


def _protos(tenants=4, classes=40):
    return {f"t{t}": make_synthetic_tenant(1000 + 17 * t,
                                           num_classes=classes,
                                           num_features=N)[2]
            for t in range(tenants)}


def _requests(protos, per_tenant=30, noise=0.9):
    reqs = []
    for i, (tid, p) in enumerate(sorted(protos.items())):
        f, _ = sample_tenant_queries(7 + i, p, per_tenant, noise=noise)
        reqs += [ClassifyRequest(tid, f[j]) for j in range(per_tenant)]
    return reqs


def _signature(responses):
    return [(r.tenant_id, r.pred, r.escalated, round(r.margin, 6))
            for r in responses]


@pytest.fixture
def no_mesh():
    saved_axes, saved_mesh = context.get(), context.get_mesh()
    context.clear()
    try:
        yield
    finally:
        context.clear()
        if saved_axes is not None:
            context.set_mesh_axes(saved_axes.dp, saved_axes.model,
                                  saved_mesh)


# ---------------------------------------------------------------------------
# Manifest value objects
# ---------------------------------------------------------------------------


class TestManifestValue:
    def test_json_roundtrip_and_hash(self):
        m = _manifest(3).validate()
        again = FleetManifest.from_json(m.to_json())
        assert again == m.normalized()
        assert hash(again) == hash(m.normalized())

    def test_order_insensitive_identity(self):
        a = _manifest(3)
        b = FleetManifest(tenants=tuple(reversed(a.tenants)))
        assert a != b  # raw tuples differ...
        assert a.normalized() == b.normalized()  # ...the identity doesn't

    def test_file_roundtrip(self, tmp_path):
        m = _manifest(2, tau=5.0, tau_units="count")
        path = tmp_path / "fleet.json"
        path.write_text(m.to_json())
        assert FleetManifest.from_file(str(path)) == m.normalized()

    def test_validate_rejects_bad_tenants(self, tmp_path):
        with pytest.raises(ManifestError, match="exactly one bank source"):
            TenantSpec("t", seed=1, checkpoint="x.npz").validate()
        with pytest.raises(ManifestError, match="exactly one bank source"):
            TenantSpec("t").validate()
        with pytest.raises(ManifestError, match="non-empty"):
            TenantSpec("", seed=1).validate()
        with pytest.raises(ManifestError, match="tau_units"):
            TenantSpec("t", seed=1, tau_units="volts").validate()
        with pytest.raises(ManifestError, match="tau must be"):
            TenantSpec("t", seed=1, tau=-1.0).validate()
        with pytest.raises(ManifestError, match="duplicate"):
            FleetManifest(tenants=(TenantSpec("t", seed=1),
                                   TenantSpec("t", seed=2))).validate()

    def test_tau_in_units(self):
        from repro.fleet import tau_in_units

        assert tau_in_units(None, "count", "fraction", N) is None
        assert tau_in_units(6.0, "count", "count", N) == 6.0
        assert tau_in_units(6.0, "count", "fraction", N) == \
            pytest.approx(6.0 / N)
        assert tau_in_units(0.1, "fraction", "count", N) == \
            pytest.approx(0.1 * N)


class TestManifestDiff:
    def test_add_evict_update_retune(self):
        old = _manifest(3)
        by = old.by_id()
        new = FleetManifest(tenants=(
            by["t0"],                                   # unchanged
            by["t1"]._replace(seed=999),                # bank source moved
            by["t2"]._replace(tau=3.0),                 # tau-only
            TenantSpec("t9", seed=5),                   # new
        ))
        d = diff_manifests(old, new)
        assert d.add == ("t9",)
        assert d.evict == ()
        assert d.update == ("t1",)
        assert d.retune == ("t2",)
        assert not d.empty

    def test_tau_units_only_change_is_retune(self):
        old = _manifest(1, tau=6.0, tau_units="count")
        new = FleetManifest(tenants=(
            old.tenants[0]._replace(tau=6.0 / N, tau_units="fraction"),))
        d = diff_manifests(old, new)
        assert d.retune == ("t0",) and not d.update and not d.add

    def test_epoch_bump_is_evict_plus_add(self):
        old = _manifest(2)
        new = FleetManifest(tenants=(
            old.tenants[0]._replace(epoch=1), old.tenants[1]))
        d = diff_manifests(old, new)
        assert d.evict == ("t0",) and d.add == ("t0",)

    def test_checkpoint_path_change_is_update(self):
        a = TenantSpec("t0", checkpoint="a.npz")
        d = diff_manifests(FleetManifest(tenants=(a,)),
                           FleetManifest(tenants=(
                               a._replace(checkpoint="b.npz"),)))
        assert d.update == ("t0",)

    def test_noop_diff_is_empty(self):
        m = _manifest(3)
        assert diff_manifests(m, m).empty
        assert diff_manifests(m.normalized(), m).empty


class TestBankCheckpoint:
    def test_save_load_roundtrip(self, tmp_path):
        bank, head, _ = make_synthetic_tenant(7, num_classes=12,
                                              num_features=N)
        path = str(tmp_path / "t.npz")
        save_bank(path, bank, head=head)
        loaded, lhead = load_bank(path)
        np.testing.assert_array_equal(np.asarray(bank.templates),
                                      loaded.templates)
        np.testing.assert_array_equal(np.asarray(bank.valid), loaded.valid)
        np.testing.assert_array_equal(np.asarray(head[0]), lhead[0])

    def test_load_headless_and_missing_fields(self, tmp_path):
        bank, _, _ = make_synthetic_tenant(7, num_classes=4,
                                           num_features=N)
        path = str(tmp_path / "t.npz")
        save_bank(path, bank)
        _, head = load_bank(path)
        assert head is None
        bad = str(tmp_path / "bad.npz")
        np.savez(bad, templates=np.zeros((1, 1, N)))
        with pytest.raises(ManifestError, match="missing arrays"):
            load_bank(bad)

    def test_materialize_seed_matches_fixture(self):
        t = TenantSpec("t0", seed=42, num_classes=8)
        bank, head = materialize(t, N)
        ref, ref_head, _ = make_synthetic_tenant(42, num_classes=8,
                                                 num_features=N)
        np.testing.assert_array_equal(np.asarray(bank.templates),
                                      np.asarray(ref.templates))
        assert head is not None
        assert materialize(t._replace(head=False), N)[1] is None

    def test_materialize_feature_mismatch(self, tmp_path):
        bank, _, _ = make_synthetic_tenant(7, num_classes=4,
                                           num_features=32)
        path = str(tmp_path / "t.npz")
        save_bank(path, bank)
        with pytest.raises(ManifestError, match="features"):
            materialize(TenantSpec("t0", checkpoint=path), N)


# ---------------------------------------------------------------------------
# apply_manifest on a live service (the satellite edge cases)
# ---------------------------------------------------------------------------


class TestApplyManifest:
    def test_initial_apply_registers_and_serves(self, no_mesh):
        svc = HybridService.from_spec(_spec())
        rep = svc.apply_manifest(_manifest())
        assert rep.added == ("t0", "t1", "t2", "t3")
        assert len(svc.registry) == 4
        sig = _signature(svc.serve(_requests(_protos())))
        assert any(s[2] for s in sig) and any(not s[2] for s in sig)

    def test_manifest_matches_imperative_registration(self, no_mesh):
        """A manifest-born fleet serves bit-identically to the same
        tenants registered by hand (same seeds, same placements)."""
        reqs = _requests(_protos())
        a = HybridService.from_spec(_spec())
        a.apply_manifest(_manifest())
        b = HybridService.from_spec(_spec())
        for t in range(4):
            bank, head, _ = make_synthetic_tenant(1000 + 17 * t,
                                                  num_classes=40,
                                                  num_features=N)
            b.register_tenant(f"t{t}", bank, head=head)
        assert _signature(a.serve(reqs)) == _signature(b.serve(reqs))

    def test_noop_apply_zero_transitions_zero_retraces(self, no_mesh):
        from repro.serve import scheduler as sched_lib

        svc = HybridService.from_spec(_spec())
        svc.apply_manifest(_manifest())
        reqs = _requests(_protos())
        base = _signature(svc.serve(reqs))  # compiles every bucket shape
        gen0 = svc.registry.generation
        size0 = sched_lib._packed_tick._cache_size()
        rep = svc.apply_manifest(_manifest())  # equal manifest, re-applied
        assert rep.empty
        assert rep.added == rep.evicted == rep.updated == rep.retuned == ()
        assert svc.registry.generation == gen0  # no device-cache bump
        assert _signature(svc.serve(reqs)) == base
        assert sched_lib._packed_tick._cache_size() == size0

    def test_tau_unit_change_retunes_without_reload(self, no_mesh):
        svc = HybridService.from_spec(_spec())
        svc.apply_manifest(_manifest(tau=6.0, tau_units="count"))
        reqs = _requests(_protos())
        base = _signature(svc.serve(reqs))
        gen0 = svc.registry.generation
        # the SAME threshold written in fraction units: a retune-only diff
        # that must not move the cascade (6 counts == 6/N fraction)
        rep = svc.apply_manifest(_manifest(tau=6.0 / N,
                                           tau_units="fraction"))
        assert rep.retuned == ("t0", "t1", "t2", "t3")
        assert rep.updated == () and rep.added == () and rep.evicted == ()
        assert svc.registry.generation == gen0  # registry untouched
        assert _signature(svc.serve(reqs)) == base
        # a genuinely different tau DOES move the cascade
        svc.apply_manifest(_manifest(tau=float(N), tau_units="count"))
        assert _signature(svc.serve(reqs)) != base

    def test_epoch_bump_evicts_and_readds_in_one_apply(self, no_mesh):
        svc = HybridService.from_spec(_spec())
        svc.apply_manifest(_manifest())
        reqs = _requests(_protos())
        base = _signature(svc.serve(reqs))
        m = _manifest()
        bumped = FleetManifest(tenants=(
            m.tenants[0]._replace(epoch=1),) + m.tenants[1:])
        rep = svc.apply_manifest(bumped)
        assert rep.evicted == ("t0",) and rep.added == ("t0",)
        assert len(svc.registry) == 4  # same population after the cycle
        assert _signature(svc.serve(reqs)) == base  # same bank, same result

    def test_checkpoint_path_change_forces_bank_reload(self, no_mesh,
                                                       tmp_path):
        bank_a, head_a, proto_a = make_synthetic_tenant(
            11, num_classes=12, num_features=N)
        bank_b, head_b, _ = make_synthetic_tenant(
            22, num_classes=12, num_features=N)
        pa, pb = str(tmp_path / "a.npz"), str(tmp_path / "b.npz")
        save_bank(pa, bank_a, head=head_a)
        save_bank(pb, bank_b, head=head_b)
        svc = HybridService.from_spec(_spec())
        svc.apply_manifest(FleetManifest(tenants=(
            TenantSpec("t0", checkpoint=pa),)))
        np.testing.assert_array_equal(
            np.asarray(svc.registry.bank_of("t0").templates),
            np.asarray(bank_a.templates))
        rep = svc.apply_manifest(FleetManifest(tenants=(
            TenantSpec("t0", checkpoint=pb),)))
        assert rep.updated == ("t0",)
        np.testing.assert_array_equal(
            np.asarray(svc.registry.bank_of("t0").templates),
            np.asarray(bank_b.templates))

    def test_apply_adopts_imperatively_registered_tenants(self, no_mesh):
        svc = HybridService.from_spec(_spec())
        bank, head, _ = make_synthetic_tenant(1000, num_classes=40,
                                              num_features=N)
        svc.register_tenant("t0", bank, head=head)
        rep = svc.apply_manifest(_manifest(1))  # same t0, declared now
        assert rep.added == ("t0",)  # adopted via the hot update path
        assert len(svc.registry) == 1

    def test_validate_runs_at_apply(self, no_mesh):
        svc = HybridService.from_spec(_spec())
        with pytest.raises(ManifestError):
            svc.apply_manifest(FleetManifest(tenants=(TenantSpec("x"),)))


# ---------------------------------------------------------------------------
# Compaction (the eviction-debt bugfix)
# ---------------------------------------------------------------------------


class TestCompaction:
    def test_eviction_never_reclaimed_then_compact_does(self):
        reg = TemplateBankRegistry(N, class_bucket=16, initial_classes=128,
                                   bank_shards=1)
        for t in range(6):  # 6 x 48 rows: grows 128 -> 256 -> 512
            bank, _, _ = make_synthetic_tenant(600 + t, num_classes=40,
                                               num_features=N)
            reg.register(f"t{t}", bank)
        assert reg.capacity_classes == 512
        for t in (0, 1, 2, 3):
            reg.evict(f"t{t}")
        # the bug: eviction frees buckets but capacity never shrinks
        assert reg.capacity_classes == 512
        banks_before = {t: np.asarray(reg.bank_of(t).templates)
                        for t in ("t4", "t5")}
        freed = reg.compact()
        assert freed > 0
        assert reg.capacity_classes == 96  # 2 x 48 rows re-packed tight
        for t in ("t4", "t5"):
            np.testing.assert_array_equal(
                np.asarray(reg.bank_of(t).templates), banks_before[t])

    def test_compact_noop_when_tight(self):
        reg = TemplateBankRegistry(N, class_bucket=16, initial_classes=128,
                                   bank_shards=1)
        for t in range(2):  # 2 x 64 rows: capacity fully used
            bank, _, _ = make_synthetic_tenant(1 + t, num_classes=64,
                                               num_features=N)
            reg.register(f"t{t}", bank)
        assert reg.compact() == 0
        assert reg.capacity_classes == 128
        # unused initial slack IS reclaimable, even with no eviction debt
        half = TemplateBankRegistry(N, class_bucket=16, initial_classes=128,
                                    bank_shards=1)
        bank, _, _ = make_synthetic_tenant(9, num_classes=40,
                                           num_features=N)
        half.register("t0", bank)
        assert half.compact() == 80
        assert half.capacity_classes == 48

    def test_placement_invariant_round_trip(self, no_mesh):
        """The acceptance shape: register -> evict -> compact -> serve is
        bit-identical to never having had the evicted tenants at all."""
        svc = HybridService.from_spec(_spec())
        svc.apply_manifest(_manifest(6))
        for t in (1, 4):
            svc.evict_tenant(f"t{t}")
        survivors = {t: p for t, p in _protos(6).items()
                     if t not in ("t1", "t4")}
        reqs = _requests(survivors)
        before = _signature(svc.serve(reqs))
        cap0 = svc.registry.capacity_classes
        freed = svc.compact_registry()
        assert freed > 0 and svc.registry.capacity_classes < cap0
        assert _signature(svc.serve(reqs)) == before
        # re-registering an evicted tenant lands in the compacted bank
        svc.apply_manifest(_manifest(6))
        assert _signature(svc.serve(reqs)) == before


# ---------------------------------------------------------------------------
# Double-buffered rolling reshard
# ---------------------------------------------------------------------------


class TestRollingReshard:
    def _boot(self):
        svc = HybridService.from_spec(_spec())
        svc.apply_manifest(_manifest())
        return svc, _requests(_protos())

    def test_flip_no_drain_bit_identity(self, no_mesh):
        """The tentpole contract: queued work rides across the flip
        untouched, and the flipped bank serves bit-identically to the
        drained `reconfigure` transition."""
        svc, reqs = self._boot()
        base = _signature(svc.serve(reqs))

        # the drained alternative on a twin service
        twin = HybridService.from_spec(_spec())
        twin.apply_manifest(_manifest())
        twin.serve(reqs)
        for r in reqs[:16]:
            twin.submit(r)
        twin_report = twin.reconfigure(twin.spec._replace(
            mesh=twin.spec.mesh._replace(bank_shards=2)))
        drained_then = _signature(twin_report.drained) \
            + _signature(twin.serve(reqs[16:]))

        # the rolling path: queue the same burst, flip, then serve
        for r in reqs[:16]:
            svc.submit(r)
        prep = reshard_lib.prepare(svc, svc.spec._replace(
            mesh=svc.spec.mesh._replace(bank_shards=2)))
        report = svc.rolling_reshard(prep.spec, prepared=prep)
        assert report.drained == []  # NO drain: that's the point
        assert svc.registry.bank_shards == 2
        flipped = []
        while svc.scheduler.qsize:
            flipped.extend(svc.step())
        rolled = _signature(flipped) + _signature(svc.serve(reqs[16:]))
        assert rolled == drained_then
        assert _signature(svc.serve(reqs)) == base

    def test_prepare_rejects_non_mesh_deltas(self, no_mesh):
        svc, _ = self._boot()
        with pytest.raises(ReconfigureError, match="rolling reshard"):
            reshard_lib.prepare(svc, svc.spec._replace(
                engine=svc.spec.engine._replace(backend="kernel"),
                mesh=svc.spec.mesh._replace(bank_shards=2)))

    def test_stale_buffer_rejected(self, no_mesh):
        svc, _ = self._boot()
        prep = reshard_lib.prepare(svc, svc.spec._replace(
            mesh=svc.spec.mesh._replace(bank_shards=2)))
        assert not prep.stale
        # tenant churn between prepare and flip invalidates the buffer
        bank, head, _ = make_synthetic_tenant(9999, num_classes=8,
                                              num_features=N)
        svc.register_tenant("late", bank, head=head)
        assert prep.stale
        with pytest.raises(RegistryError, match="re-prepare"):
            svc.rolling_reshard(prep.spec, prepared=prep)
        assert svc.registry.bank_shards == 1  # live bank untouched

    def test_rolling_reshard_prepares_inline_when_not_given(self, no_mesh):
        svc, reqs = self._boot()
        base = _signature(svc.serve(reqs))
        report = svc.rolling_reshard(svc.spec._replace(
            mesh=svc.spec.mesh._replace(bank_shards=2)))
        assert report.drained == [] and svc.spec.mesh.bank_shards == 2
        assert _signature(svc.serve(reqs)) == base


# ---------------------------------------------------------------------------
# The policy: pure function from frozen telemetry to the next spec
# ---------------------------------------------------------------------------


def _view(spec=None, **kw):
    spec = spec or _spec()
    base = dict(spec=spec, tenants=4, shard_rows_used=(128,),
                rows_per_shard=256, capacity_classes=256,
                fused_rows_per_shard=512, vmem_budget_rows=2048,
                queue_depth=0, p99_ms=1.0, rolling_fill=8.0, slots=16,
                devices=4, backend_j=1e-7, frontend_j=1e-5)
    base.update(kw)
    return RegistryView(**base)


class TestPolicy:
    def test_hold_below_every_threshold(self):
        v = _view()
        action, reason, spec = explain(v)
        assert action == "hold" and spec == v.spec
        assert decide(v) == v.spec

    def test_escalate_on_row_pressure(self):
        v = _view(shard_rows_used=(224,))  # 224/256 = 0.875 >= 0.75
        action, reason, spec = explain(v)
        assert action == "escalate_shards"
        assert spec.mesh.bank_shards == 2
        assert "fullest shard" in reason
        spec.validate()  # proposed spec is always a valid spec

    def test_escalate_on_vmem_pressure(self):
        v = _view(fused_rows_per_shard=2048)  # at MAX_FUSED_ROWS
        action, _, spec = explain(v)
        assert action == "escalate_shards" and spec.mesh.bank_shards == 2

    def test_escalation_respects_device_divisibility(self):
        inst = _spec()._replace(mesh=MeshSpec(bank_shards=4, install=True))
        v = _view(spec=inst, shard_rows_used=(64, 64, 64, 60),
                  rows_per_shard=64)
        # doubling to 8 shards needs 8 | devices: held at 4 devices...
        assert explain(v)[0] == "hold"
        # ...allowed at 8, capped by max_bank_shards regardless
        assert explain(_view(spec=inst, shard_rows_used=(64,) * 4,
                             rows_per_shard=64, devices=8)
                       )[0] == "escalate_shards"
        assert explain(_view(spec=inst, shard_rows_used=(64,) * 4,
                             rows_per_shard=64, devices=8),
                       PolicySpec(max_bank_shards=4))[0] == "hold"

    def test_swap_backend_when_ledger_dominated(self):
        v = _view(spec=_spec("kernel"), backend_j=9.5e-6, frontend_j=5e-7)
        action, reason, spec = explain(v)
        assert action == "swap_backend"
        assert spec.engine.backend == "device"
        assert spec.engine.device_noise == "per_shard"  # shard-legal
        # already on the device backend: nothing to swap
        assert explain(_view(spec=_spec("device"), backend_j=9.5e-6,
                             frontend_j=5e-7))[0] == "hold"
        # below the energy floor the ledger is ignored
        assert explain(v, PolicySpec(min_energy_j=1.0))[0] == "hold"

    def test_widen_slots_under_saturation(self):
        v = _view(rolling_fill=16.0, queue_depth=64)
        action, _, spec = explain(v)
        assert action == "widen_slots" and spec.scheduler.slots == 32
        # saturation without a queue is steady state, not pressure
        assert explain(_view(rolling_fill=16.0, queue_depth=8))[0] == "hold"
        # at the slot ceiling there is nothing to widen
        assert explain(_view(rolling_fill=16.0, queue_depth=64),
                       PolicySpec(max_slots=16))[0] == "hold"

    def test_priority_order_is_fixed(self):
        # row pressure AND saturation: shards win (rule 1 before rule 3)
        v = _view(shard_rows_used=(224,), rolling_fill=16.0,
                  queue_depth=64)
        assert explain(v)[0] == "escalate_shards"

    def test_should_compact(self):
        assert should_compact(_view(shard_rows_used=(64,),
                                    capacity_classes=256))
        assert not should_compact(_view(shard_rows_used=(224,),
                                        capacity_classes=256))
        # minimal aligned capacity: nothing to give back
        assert not should_compact(_view(shard_rows_used=(4,),
                                        capacity_classes=16))

    def test_view_json_roundtrip(self):
        v = _view(shard_rows_used=(96, 128), rows_per_shard=128)
        d = json.loads(json.dumps(v.to_dict()))
        assert RegistryView.from_dict(d) == v

    def test_view_of_reads_only_health(self, no_mesh):
        svc = HybridService.from_spec(_spec())
        svc.apply_manifest(_manifest())
        v = view_of(svc)
        h = svc.health()
        assert v.tenants == h["tenants"] == 4
        assert v.shard_rows_used == tuple(h["shard_rows_used"])
        assert v.capacity_classes == h["capacity_classes"]
        assert v.vmem_budget_rows == h["vmem_budget_rows"]
        assert v.spec == svc.spec

    @settings(max_examples=50, deadline=None)
    @given(used=st.integers(0, 256), queue=st.integers(0, 512),
           fill=st.floats(0.0, 16.0), fused=st.integers(0, 4096),
           backend_j=st.floats(0.0, 1e-4), devices=st.integers(1, 16))
    def test_decide_pure_and_deterministic(self, used, queue, fill, fused,
                                           backend_j, devices):
        """The acceptance property: same frozen view + policy in, same
        spec out, no mutation, and the proposal is always a valid spec
        drawn from the fixed action set."""
        v = _view(spec=_spec("kernel"), shard_rows_used=(used,),
                  queue_depth=queue, rolling_fill=fill,
                  fused_rows_per_shard=fused, backend_j=backend_j,
                  devices=devices)
        pol = PolicySpec()
        first, second = explain(v, pol), explain(v, pol)
        assert first == second
        assert decide(v, pol) == first[2]
        assert first[0] in ("hold", "escalate_shards", "swap_backend",
                            "widen_slots")
        first[2].validate()
        # the view the decision was logged with replays identically
        assert explain(RegistryView.from_dict(
            json.loads(json.dumps(v.to_dict()))), pol) == first


# ---------------------------------------------------------------------------
# Autopilot
# ---------------------------------------------------------------------------


class TestAutopilot:
    def _drive(self, svc, pilot, reqs, burst=8):
        responses, executed, i = [], [], 0
        while i < len(reqs) or svc.scheduler.qsize:
            for r in reqs[i:i + burst]:
                svc.submit(r)
            i += burst
            responses.extend(svc.step())
            act = pilot.observe_tick()
            if act:
                executed.append(act)
            responses.extend(pilot.take_drained())
        return responses, executed

    def test_escalates_via_buffer_flip_and_reconstructs(self, no_mesh,
                                                        tmp_path):
        """End-to-end: row pressure -> escalate_shards (shadow prepared
        between ticks) -> buffer_flip at the next boundary, bit-identical
        to a pinned service — and the whole action stream reconstructs
        from the JSONL event log alone."""
        from repro.obs import read_events

        reqs = _requests(_protos(), per_tenant=40)
        pinned = HybridService.from_spec(_spec())
        pinned.apply_manifest(_manifest())
        base = _signature(pinned.serve(reqs))

        svc = HybridService.from_spec(_spec(
            telemetry_dir=str(tmp_path)))
        svc.apply_manifest(_manifest())  # 192/256 rows: at the threshold
        pol = PolicySpec(interval=2, hysteresis=1, cooldown=4,
                         max_bank_shards=4)
        pilot = Autopilot(svc, policy=pol)
        responses, executed = self._drive(svc, pilot, reqs)

        assert "escalate_shards" in executed
        assert "buffer_flip" in executed
        assert svc.registry.bank_shards > 1
        assert _signature(responses) == base

        events = read_events(svc.obs.events.path)
        flips = [e for e in events if e["kind"] == "buffer_flip"]
        decisions = [e for e in events if e["kind"] == "policy_decision"]
        assert len(flips) == executed.count("buffer_flip")
        assert len(decisions) == len(pilot.actions)
        # log-only reconstruction: replay the pure policy over each
        # logged frozen view; the action stream must match exactly
        for e, recorded in zip(decisions, pilot.actions):
            view = RegistryView.from_dict(e["view"])
            act = explain(view, pol)[0]
            if act == "hold" and should_compact(view, pol):
                act = "compact"
            assert act == e["action"] == recorded["action"]
            assert e["tick"] == recorded["tick"]

    def test_hysteresis_and_cooldown_gate_actions(self, no_mesh):
        svc = HybridService.from_spec(_spec())
        svc.apply_manifest(_manifest())  # at the escalation threshold
        pol = PolicySpec(interval=1, hysteresis=3, cooldown=100,
                         max_bank_shards=4)
        pilot = Autopilot(svc, policy=pol)
        assert pilot.observe_tick() is None  # streak 1
        assert pilot.observe_tick() is None  # streak 2
        assert pilot.observe_tick() == "escalate_shards"  # streak 3: act
        assert pilot.observe_tick() == "buffer_flip"  # pending flip lands
        # cooldown: no further evaluation despite standing pressure
        for _ in range(10):
            assert pilot.observe_tick() is None

    def test_widen_slots_drains_through_take_drained(self, no_mesh):
        """The FIFO contract around drained reconfigures: every submitted
        request surfaces exactly once, in submission order."""
        svc = HybridService.from_spec(_spec(slots=4))
        svc.apply_manifest(_manifest(2, classes=10))  # low occupancy
        reqs = _requests(_protos(2, classes=10), per_tenant=40)
        pinned = HybridService.from_spec(_spec(slots=4))
        pinned.apply_manifest(_manifest(2, classes=10))
        base = _signature(pinned.serve(reqs))

        pol = PolicySpec(interval=2, hysteresis=1, cooldown=4)
        pilot = Autopilot(svc, policy=pol)
        # flood the queue so rule 3 fires (fill saturated + queue standing)
        responses, executed = self._drive(svc, pilot, reqs, burst=20)
        assert "widen_slots" in executed
        assert svc.spec.scheduler.slots > 4
        assert _signature(responses) == base

    def test_stale_pending_reprepared_after_churn(self, no_mesh):
        svc = HybridService.from_spec(_spec())
        svc.apply_manifest(_manifest())
        pol = PolicySpec(interval=1, hysteresis=1, cooldown=2,
                         max_bank_shards=4)
        pilot = Autopilot(svc, policy=pol)
        assert pilot.observe_tick() == "escalate_shards"  # shadow prepared
        # churn lands between prepare and flip: the buffer goes stale
        bank, head, _ = make_synthetic_tenant(4242, num_classes=8,
                                              num_features=N)
        svc.register_tenant("late", bank, head=head)
        assert pilot.observe_tick() is None  # stale: re-prepared, no flip
        assert pilot.observe_tick() == "buffer_flip"  # fresh buffer lands
        assert svc.registry.bank_shards == 2
        assert "late" in svc.registry


# ---------------------------------------------------------------------------
# health() carries the controller inputs (satellite 1)
# ---------------------------------------------------------------------------


class TestHealthControllerInputs:
    def test_fleet_fields_present_and_consistent(self, no_mesh):
        svc = HybridService.from_spec(_spec())
        svc.apply_manifest(_manifest())
        svc.serve(_requests(_protos(), per_tenant=8))
        h = svc.health()
        assert h["tenants"] == 4
        assert h["bank_shards"] == 1
        assert len(h["shard_rows_used"]) == 1
        assert sum(h["shard_rows_used"]) == 4 * 48  # 40 -> 48-row buckets
        assert h["rows_per_shard"] == h["capacity_classes"] == 256
        assert h["vmem_budget_rows"] == 2048
        assert h["fused_rows_per_shard"] > 0
        assert h["rolling_batch_fill"] > 0
        assert h["slots"] == 16 and h["devices"] >= 1
        assert h["p99_ms"] >= 0
        assert h["energy_backend_j"] > 0
        assert h["energy_frontend_j"] >= 0

    def test_shard_rows_used_splits_by_shard(self):
        reg = TemplateBankRegistry(N, class_bucket=16, initial_classes=256,
                                   bank_shards=2)
        bank, _, _ = make_synthetic_tenant(5, num_classes=40,
                                           num_features=N)
        reg.register("t0", bank)
        used = reg.shard_rows_used()
        assert len(used) == 2
        assert sum(used) == 48 and used[0] == 48  # first-fit: shard 0


# ---------------------------------------------------------------------------
# Forced 2x2 mesh: the flip under a real (data, model) mesh
# ---------------------------------------------------------------------------


def run_sub(code: str, timeout=600) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    env.pop("REPRO_FORCE_MESH", None)
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=timeout,
                         env=env)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    return out.stdout


class TestForcedMeshRollingReshard:
    def test_flip_bit_identity_on_2x2(self):
        """The tentpole acceptance under a real mesh: the double-buffered
        flip 1 -> 2 shards re-installs the (data, model) mesh with NO
        drain and serves bit-identically."""
        out = run_sub("""
            import os
            os.environ["XLA_FLAGS"] = \
                "--xla_force_host_platform_device_count=4"
            from repro import match
            from repro.fleet import FleetManifest, TenantSpec
            from repro.fleet import reshard as reshard_lib
            from repro.match.config import EngineConfig
            from repro.serve.acam_service import (ClassifyRequest,
                                                  make_synthetic_tenant,
                                                  sample_tenant_queries)
            from repro.serve.control import HybridService
            from repro.serve.spec import (CascadeSpec, MeshSpec,
                                          RegistrySpec, SchedulerSpec,
                                          ServiceSpec)

            spec = ServiceSpec(
                registry=RegistrySpec(num_features=64, initial_classes=256),
                engine=EngineConfig(backend="kernel", margin=True),
                mesh=MeshSpec(bank_shards=1),  # install=True: spec owns it
                scheduler=SchedulerSpec(slots=16),
                cascade=CascadeSpec(tau=6.0, tau_units="count"))
            svc = HybridService.from_spec(spec)
            svc.apply_manifest(FleetManifest(tenants=tuple(
                TenantSpec(f"t{t}", seed=1000 + 17 * t, num_classes=40)
                for t in range(4))))
            protos = {f"t{t}": make_synthetic_tenant(
                          1000 + 17 * t, num_classes=40,
                          num_features=64)[2] for t in range(4)}
            reqs = []
            for i, (tid, p) in enumerate(sorted(protos.items())):
                f, _ = sample_tenant_queries(7 + i, p, 24, noise=0.9)
                reqs += [ClassifyRequest(tid, f[j]) for j in range(24)]
            sig = lambda rs: [(r.tenant_id, r.pred, r.escalated,
                               round(r.margin, 6)) for r in rs]
            base = sig(svc.serve(reqs))
            assert match.bank_shards_in_mesh() == 1

            for r in reqs[:16]:
                svc.submit(r)
            prep = reshard_lib.prepare(svc, spec._replace(
                mesh=MeshSpec(bank_shards=2)))
            report = svc.rolling_reshard(prep.spec, prepared=prep)
            assert report.drained == []          # no drain across the flip
            assert match.bank_shards_in_mesh() == 2
            assert svc.registry.bank_shards == 2
            flipped = []
            while svc.scheduler.qsize:
                flipped.extend(svc.step())
            assert sig(flipped) == base[:16]     # queued work, sharded bank
            assert sig(svc.serve(reqs)) == base  # full stream bit-identity
            plan, _ = match.plan_for(
                batch=16, num_classes=svc.registry.capacity_classes)
            assert plan.bank_shards == 2, plan
            print("OK flip", report.downtime_s)
            """, timeout=900)
        assert "OK flip" in out
