"""ServiceSpec + the HybridService control plane (PR 5).

Four layers of coverage:

  * `ServiceSpec` as a value object: hashable, JSON-round-trippable across
    every backend (`spec == ServiceSpec.from_json(spec.to_json())`), with
    eager cross-field `validate()` (device sharding under "global"
    sigma_program noise, capacity vs shards, tau units vs the matchline
    cap);
  * the legacy shims: `ACAMService(...)` keywords delegate to the spec
    path unchanged, and the mesh-ordering footgun now warns loudly
    (bank_shards=None with no mesh installed -> silent 1);
  * live transitions (in-process): `reconfigure` resharding 1 -> 2 -> 1 on
    a populated registry with bit-identical served results and ZERO tenant
    re-registrations, live backend swap, tau retune, frozen-field guard,
    and `TemplateBankRegistry.reshard` re-packing direct;
  * forced 2x2 CPU mesh (subprocess): the spec path owns the mesh end to
    end — boot at bank_shards=1, reconfigure to 2 (sharded dispatch, one
    per tick), back to 1, bit-identical preds/margins/escalations at every
    step; and the per-shard device-noise semantics: a bank-sharded
    `device_noise="per_shard"` run equals the replicated S-array emulation
    (`program_bank(..., bank_shards=S)`) bit for bit.
"""
import os
import subprocess
import sys
import textwrap
import warnings

import numpy as np
import pytest

from repro.core.acam import ACAMConfig
from repro.distributed import context
from repro.match.config import EngineConfig
from repro.serve.acam_service import (ACAMService, ClassifyRequest,
                                      ServiceConfig, make_synthetic_tenant,
                                      sample_tenant_queries)
from repro.serve.control import HybridService, ReconfigureError
from repro.serve.registry import TemplateBankRegistry
from repro.serve.spec import (CascadeSpec, MeshSpec, RegistrySpec,
                              SchedulerSpec, ServiceSpec)

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
N = 64


def _spec(backend="reference", *, bank_shards=1, slots=16, tau=6.0,
          install=False, **engine_kw):
    return ServiceSpec(
        registry=RegistrySpec(num_features=N, initial_classes=256),
        engine=EngineConfig(backend=backend, margin=True, **engine_kw),
        mesh=MeshSpec(bank_shards=bank_shards, install=install),
        scheduler=SchedulerSpec(slots=slots),
        cascade=CascadeSpec(tau=tau, tau_units="count"),
    )


def _populate(svc, classes=(40, 40, 40, 40)):
    protos = {}
    for t, c in enumerate(classes):
        bank, head, p = make_synthetic_tenant(1000 + 17 * t, num_classes=c,
                                              num_features=N)
        svc.register_tenant(f"t{t}", bank, head=head)
        protos[f"t{t}"] = p
    return protos


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
    """Run with a cleared mesh context (REPRO_FORCE_MESH installs one
    session-wide); restores whatever was installed afterwards."""
    saved_axes, saved_mesh = context.get(), context.get_mesh()
    context.clear()
    try:
        yield
    finally:
        context.clear()
        if saved_axes is not None:
            context.set_mesh_axes(saved_axes.dp, saved_axes.model,
                                  saved_mesh)


class TestServiceSpecValue:
    @pytest.mark.parametrize("backend", ("auto", "reference", "kernel",
                                         "device"))
    def test_json_roundtrip_every_backend(self, backend):
        device = ACAMConfig(cell="3T1R", sigma_program=0.15) \
            if backend == "device" else None
        spec = ServiceSpec(
            registry=RegistrySpec(num_features=128, k_max=3,
                                  initial_classes=192),
            engine=EngineConfig(method="similarity", alpha=0.5,
                                backend=backend, block=(8, 16, 32),
                                margin=True, device=device, seed=11,
                                device_noise="per_shard"),
            mesh=MeshSpec(bank_shards=2, data_axis="dp", model_axis="mp",
                          install=False),
            scheduler=SchedulerSpec(slots=7),
            cascade=CascadeSpec(tau=0.25, tau_units="fraction",
                                max_queue=99, frontend_sparsity=0.5),
        )
        again = ServiceSpec.from_json(spec.to_json())
        assert again == spec
        assert hash(again) == hash(spec)
        assert isinstance(again.engine.block, tuple)
        if device is not None:
            assert isinstance(again.engine.device, ACAMConfig)

    def test_defaults_roundtrip_and_validate(self):
        spec = ServiceSpec()
        assert ServiceSpec.from_json(spec.to_json()) == spec
        assert spec.validate() is spec

    def test_file_roundtrip(self, tmp_path):
        spec = _spec("kernel", bank_shards=2)
        path = tmp_path / "service.json"
        path.write_text(spec.to_json())
        assert ServiceSpec.from_file(str(path)) == spec

    def test_validate_device_global_noise_shard_conflict(self):
        bad = _spec("device", bank_shards=2,
                    device=ACAMConfig(sigma_program=0.1))
        with pytest.raises(ValueError, match="per_shard"):
            bad.validate()
        # per-shard programming keys lift the refusal
        bad._replace(engine=bad.engine._replace(
            device_noise="per_shard")).validate()
        # ...as does an ideal array
        _spec("device", bank_shards=2,
              device=ACAMConfig(sigma_program=0.0)).validate()

    def test_validate_capacity_vs_shards(self):
        bad = _spec()
        bad = bad._replace(registry=bad.registry._replace(
            initial_classes=120), mesh=bad.mesh._replace(bank_shards=2))
        with pytest.raises(ValueError, match="whole"):
            bad.validate()

    def test_validate_misc_conflicts(self):
        with pytest.raises(ValueError, match="tau_units"):
            _spec()._replace(cascade=CascadeSpec(
                tau_units="volts")).validate()
        with pytest.raises(ValueError, match="fraction"):
            _spec()._replace(cascade=CascadeSpec(
                tau=8.0, tau_units="fraction")).validate()
        with pytest.raises(ValueError, match="max_queue"):
            _spec()._replace(cascade=CascadeSpec(
                max_queue=0)).validate()
        with pytest.raises(ValueError, match="method"):
            _spec()._replace(engine=EngineConfig(
                method="cosine")).validate()
        with pytest.raises(ValueError, match="axes"):
            _spec()._replace(mesh=MeshSpec(data_axis="x",
                                           model_axis="x")).validate()

    def test_tau_scale_explicit_units(self):
        # digital feature-count margins are match counts: no conversion
        assert _spec("kernel").tau_scale() == 1.0
        # device senses matchline fractions: count taus divide by N
        assert _spec("device").tau_scale() == pytest.approx(1.0 / N)
        # fraction taus serve the device backend unconverted
        frac = _spec("device")._replace(
            cascade=CascadeSpec(tau=0.1, tau_units="fraction"))
        assert frac.tau_scale() == 1.0
        # ...and scale UP to counts for the digital backends
        frac_k = _spec("kernel")._replace(
            cascade=CascadeSpec(tau=0.1, tau_units="fraction"))
        assert frac_k.tau_scale() == pytest.approx(float(N))
        # similarity margins live in [0, 1] whatever the backend
        sim = _spec("kernel", method="similarity")
        assert sim.native_tau_units == "fraction"


class TestLegacyShims:
    def test_legacy_constructor_delegates_to_spec(self, no_mesh):
        svc = ACAMService(N, config=ServiceConfig(slots=8, margin_tau=5.0),
                          backend="reference", bank_shards=1)
        assert svc.spec.engine.backend == "reference"
        assert svc.spec.scheduler.slots == 8
        assert svc.spec.cascade == CascadeSpec(tau=5.0, tau_units="count")
        assert svc.spec.mesh == MeshSpec(bank_shards=1, install=False)
        assert svc.config.margin_tau == 5.0  # legacy view preserved
        assert svc.scheduler.method == "feature_count"

    def test_silent_bank_shards_warns(self, no_mesh):
        """Satellite regression: bank_shards=None with no mesh installed
        used to silently resolve to 1 — now it says so, loudly."""
        with pytest.warns(UserWarning, match="silently resolves to 1"):
            svc = ACAMService(N)
        assert svc.registry.bank_shards == 1

    def test_no_warning_with_mesh_or_explicit_shards(self, no_mesh):
        import jax

        with warnings.catch_warnings():
            warnings.simplefilter("error")
            ACAMService(N, bank_shards=1)  # explicit: intent is clear
            from repro.launch.mesh import make_mesh
            mesh = make_mesh((1, 1), ("data", "model"))
            context.set_mesh_axes("data", "model", mesh)
            ACAMService(N)  # mesh installed: inference is well-defined

    def test_from_spec_makes_the_footgun_impossible(self, no_mesh):
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            svc = HybridService.from_spec(_spec())
        assert svc.registry.bank_shards == 1

    def test_device_tau_rescale_via_spec(self, no_mesh):
        svc = ACAMService(N, config=ServiceConfig(margin_tau=8.0),
                          backend="device", bank_shards=1)
        bank, head, _ = make_synthetic_tenant(3, num_classes=4,
                                              num_features=N)
        svc.register_tenant("t", bank, head=head)
        assert svc._tenants["t"].margin_tau == pytest.approx(8.0 / N)


class TestRegistryReshard:
    def test_repack_moves_straddlers_and_preserves_rows(self):
        reg = TemplateBankRegistry(N, class_bucket=16, initial_classes=256,
                                   bank_shards=1)
        banks = {}
        for t in range(3):  # 48-row runs at 0, 48, 96 — the third straddles
            bank, _, _ = make_synthetic_tenant(400 + t, num_classes=40,
                                               num_features=N)
            reg.register(f"t{t}", bank)
            banks[f"t{t}"] = bank
        before = {t: reg.get(t) for t in banks}
        moved = reg.reshard(2)
        assert moved >= 1  # t2 ([96, 144)) must hop the row-128 boundary
        assert reg.bank_shards == 2
        rps = reg.rows_per_shard
        for t, old in before.items():
            e = reg.get(t)
            assert e.offset // rps == (e.offset + e.c_bucket - 1) // rps
            assert (e.slot, e.num_classes, e.k, e.valid_rows) == \
                (old.slot, old.num_classes, old.k, old.valid_rows)
            # template rows moved bit-for-bit
            sb = reg.device_bank()
            np.testing.assert_array_equal(
                np.asarray(sb.templates[e.offset:e.offset + e.num_classes,
                                        :e.k]),
                np.asarray(banks[t].templates))

    def test_reshard_grows_capacity_when_fragmented(self):
        reg = TemplateBankRegistry(N, class_bucket=16, initial_classes=128,
                                   bank_shards=1)
        for t in range(2):  # two 48-row runs: 96 of 128 rows used
            bank, _, _ = make_synthetic_tenant(500 + t, num_classes=48,
                                               num_features=N)
            reg.register(f"t{t}", bank)
        reg.reshard(2)  # 64-row shards hold one 48-row run each
        assert reg.capacity_classes == 128
        reg.reshard(4)  # 32-row shards hold NO 48-row run: must grow
        assert reg.capacity_classes == 256
        assert reg.rows_per_shard == 64
        rps = reg.rows_per_shard
        for t in ("t0", "t1"):
            e = reg.get(t)
            assert e.offset // rps == (e.offset + e.c_bucket - 1) // rps

    def test_reshard_noop_and_validation(self):
        reg = TemplateBankRegistry(N, bank_shards=2, initial_classes=128)
        assert reg.reshard(2) == 0
        with pytest.raises(ValueError):
            reg.reshard(0)


class TestReconfigure:
    def _boot(self):
        svc = HybridService.from_spec(_spec())
        protos = _populate(svc)
        reqs = _requests(protos)
        return svc, reqs

    def test_live_reshard_1_2_1_bit_identity(self, no_mesh):
        """The acceptance core (replicated execution; the subprocess test
        repeats it under a real sharded mesh): re-packed placements serve
        bit-identical results with zero re-registrations."""
        svc, reqs = self._boot()
        base = _signature(svc.serve(reqs))
        assert any(s[2] for s in base) and any(not s[2] for s in base)

        registered = {"n": 0}
        orig = TemplateBankRegistry.register

        def counting(self, *a, **kw):
            registered["n"] += 1
            return orig(self, *a, **kw)

        TemplateBankRegistry.register = counting
        try:
            report = svc.reconfigure(svc.spec._replace(
                mesh=svc.spec.mesh._replace(bank_shards=2)))
        finally:
            TemplateBankRegistry.register = orig
        assert registered["n"] == 0
        assert report.tenants_moved >= 1
        assert svc.registry.bank_shards == 2
        assert _signature(svc.serve(reqs)) == base

        svc.reconfigure(svc.spec._replace(
            mesh=svc.spec.mesh._replace(bank_shards=1)))
        assert svc.registry.bank_shards == 1
        assert _signature(svc.serve(reqs)) == base

    def test_reconfigure_drains_pending_under_old_config(self, no_mesh):
        svc, reqs = self._boot()
        for r in reqs[:10]:
            svc.submit(r)
        report = svc.reconfigure(svc.spec._replace(
            mesh=svc.spec.mesh._replace(bank_shards=2)))
        assert len(report.drained) == 10
        assert svc.scheduler.qsize == 0
        assert report.downtime_s > 0

    def test_live_backend_swap_parity_and_retrace(self, no_mesh):
        from repro.serve import scheduler as sched_lib

        svc, reqs = self._boot()
        base = _signature(svc.serve(reqs))
        size0 = sched_lib._packed_tick._cache_size()
        report = svc.reconfigure(svc.spec._replace(
            engine=svc.spec.engine._replace(backend="kernel")))
        assert any("engine" in a for a in report.actions)
        assert _signature(svc.serve(reqs)) == base  # kernel == reference
        # the new EngineConfig is a fresh static jit key: exactly one new
        # trace, not a silent replay of the reference executable
        assert sched_lib._packed_tick._cache_size() == size0 + 1

    def test_tau_retune_moves_the_cascade(self, no_mesh):
        svc, reqs = self._boot()
        base = _signature(svc.serve(reqs))
        svc.reconfigure(svc.spec._replace(cascade=CascadeSpec(
            tau=float(N), tau_units="count")))
        # margins cap below N: every headed request now escalates
        everything = _signature(svc.serve(reqs))
        assert all(s[2] for s in everything)
        # decisions and margins themselves are untouched by the tau move
        assert [(s[0], s[1], s[3]) for s in everything] == \
            [(s[0], s[1], s[3]) for s in base]

    def test_slots_change_rebuilds_scheduler(self, no_mesh):
        svc, reqs = self._boot()
        base = _signature(svc.serve(reqs))
        svc.reconfigure(svc.spec._replace(scheduler=SchedulerSpec(slots=4)))
        assert svc.scheduler.slots == 4
        assert _signature(svc.serve(reqs)) == base

    def test_frozen_registry_fields_raise(self, no_mesh):
        svc, _ = self._boot()
        for field, value in (("num_features", 128), ("k_max", 4),
                             ("class_bucket", 32)):
            with pytest.raises(ReconfigureError, match=field):
                svc.reconfigure(svc.spec._replace(
                    registry=svc.spec.registry._replace(**{field: value})))

    def test_noop_reconfigure(self, no_mesh):
        svc, _ = self._boot()
        report = svc.reconfigure(svc.spec)
        assert report.actions == () and report.downtime_s == 0.0


def run_sub(code: str, timeout=600) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env["JAX_PLATFORMS"] = "cpu"
    # the child pins its own forced device count before importing jax
    env.pop("XLA_FLAGS", None)
    env.pop("REPRO_FORCE_MESH", None)
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=timeout,
                         env=env)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    return out.stdout


class TestForcedMeshControlPlane:
    """The spec path owning a real (data, model) mesh end to end."""

    def test_live_reshard_sharded_bit_identity(self):
        out = run_sub("""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
            import jax
            import numpy as np
            from repro import match
            from repro.match.config import EngineConfig
            from repro.serve.acam_service import (ClassifyRequest,
                                                  make_synthetic_tenant,
                                                  sample_tenant_queries)
            from repro.serve.control import HybridService
            from repro.serve.registry import TemplateBankRegistry
            from repro.serve.spec import (CascadeSpec, MeshSpec,
                                          RegistrySpec, SchedulerSpec,
                                          ServiceSpec)

            spec = ServiceSpec(
                registry=RegistrySpec(num_features=64, initial_classes=256),
                engine=EngineConfig(backend="kernel", margin=True),
                mesh=MeshSpec(bank_shards=1),   # install=True: spec owns it
                scheduler=SchedulerSpec(slots=64),
                cascade=CascadeSpec(tau=6.0, tau_units="count"))
            svc = HybridService.from_spec(spec)
            assert match.bank_shards_in_mesh() == 1  # (data=4, model=1)

            protos = {}
            for t in range(4):  # 40-class tenants: runs straddle row 128
                bank, head, p = make_synthetic_tenant(
                    1000 + 17 * t, num_classes=40, num_features=64)
                svc.register_tenant(f"t{t}", bank, head=head)
                protos[f"t{t}"] = p
            reqs = []
            for i, (tid, p) in enumerate(sorted(protos.items())):
                f, _ = sample_tenant_queries(7 + i, p, 32, noise=0.9)
                reqs += [ClassifyRequest(tid, f[j]) for j in range(32)]
            sig = lambda rs: [(r.tenant_id, r.pred, r.escalated,
                               round(r.margin, 6)) for r in rs]
            base = sig(svc.serve(reqs))
            assert any(s[2] for s in base) and any(not s[2] for s in base)

            registered = {"n": 0}
            orig = TemplateBankRegistry.register
            def counting(self, *a, **kw):
                registered["n"] += 1
                return orig(self, *a, **kw)
            TemplateBankRegistry.register = counting
            try:
                report = svc.reconfigure(spec._replace(
                    mesh=MeshSpec(bank_shards=2)))
            finally:
                TemplateBankRegistry.register = orig
            assert registered["n"] == 0, "reshard re-registered tenants"
            assert report.tenants_moved >= 1
            assert match.bank_shards_in_mesh() == 2  # (data=2, model=2)
            assert svc.registry.bank_shards == 2
            rps = svc.registry.rows_per_shard
            for tid in protos:
                e = svc.registry.get(tid)
                assert e.offset // rps == \
                    (e.offset + e.c_bucket - 1) // rps, (tid, e)

            # the tick shapes now derive a bank-sharded 2D plan (the real
            # sharded-dispatch check; dispatches == ticks is structural)
            plan, _ = match.plan_for(
                batch=64, num_classes=svc.registry.capacity_classes)
            assert plan.bank_shards == 2 and plan.dp_devices == 2, plan

            svc.reset_metrics()
            sharded = sig(svc.serve(reqs))
            assert sharded == base, "reshard changed served results"
            m = svc.metrics()
            assert m["classify_dispatches"] == m["ticks"]  # ONE per tick
            print("OK sharded", m["classify_dispatches"])

            svc.reconfigure(svc.spec._replace(mesh=MeshSpec(bank_shards=1)))
            assert match.bank_shards_in_mesh() == 1
            assert sig(svc.serve(reqs)) == base
            print("OK back-to-one")
            """, timeout=900)
        assert "OK sharded" in out and "OK back-to-one" in out

    def test_per_shard_device_noise_matches_emulated_tiling(self):
        out = run_sub("""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
            import jax, jax.numpy as jnp
            import numpy as np
            from repro import match
            from repro.core import acam
            from repro.core.templates import TemplateBank
            from repro.distributed import context

            key = jax.random.PRNGKey(0)
            c, k, n, b = 64, 1, 32, 16
            tmpl = (jax.random.uniform(key, (c, k, n)) > 0.5
                    ).astype(jnp.float32)
            bank = TemplateBank(tmpl, jnp.zeros_like(tmpl),
                                jnp.ones_like(tmpl), jnp.ones((c, k), bool),
                                jnp.zeros((n,)))
            feats = jax.random.normal(jax.random.fold_in(key, 1), (b, n))

            eng = match.engine_for(
                backend="device",
                device=acam.ACAMConfig(sigma_program=0.2), seed=9,
                device_noise="per_shard")
            assert eng.backend(None).supports_bank_sharding
            # "global" noise still declines sharding at sigma > 0
            glob = match.engine_for(
                backend="device",
                device=acam.ACAMConfig(sigma_program=0.2), seed=9)
            assert not glob.backend(None).supports_bank_sharding

            # replicated emulation of the 2-array tiling (no mesh)
            pe, pce = eng.backend(None).classify_features_keyed(
                feats, bank, jax.random.PRNGKey(9), bank_shards=2)

            from repro.launch.mesh import make_mesh
            mesh = make_mesh((2, 2), ("data", "model"))
            context.set_mesh_axes("data", "model", mesh)
            plan, _ = match.plan_for(batch=b, num_classes=c)
            assert plan.bank_shards == 2, plan
            ps, pcs = eng.classify_features(feats, bank)
            context.clear()
            np.testing.assert_array_equal(np.asarray(ps), np.asarray(pe))
            np.testing.assert_array_equal(np.asarray(pcs), np.asarray(pce))
            # distinct, documented semantics: != the one-array noise field
            pg, pcg = glob.backend(None).classify_features_keyed(
                feats, bank, jax.random.PRNGKey(9))
            assert not np.allclose(np.asarray(pcg), np.asarray(pce))
            print("OK per-shard")
            """, timeout=900)
        assert "OK per-shard" in out
