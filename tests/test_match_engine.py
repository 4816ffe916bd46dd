"""The `repro.match` engine layer (PR 3).

Covers the contracts the multi-layer refactor introduced:

  * engine API: hashable `EngineConfig`, memoised `engine_for`, backend
    registry validation, `use_backend` scoping + env parity;
  * the `set_backend` trace-time footgun fix: `hybrid._fused_forward`
    takes the backend as a *static* jit argument, so changing the default
    between `predict` calls produces a fresh trace (observable via the jit
    cache) instead of silently replaying the old executable;
  * device-backend parity: at `sigma_program = 0` the RRAM-physics backend
    reproduces the reference backend's classify decisions exactly through
    the engine API (both cell flavours), and `acam.soft_sense` stays
    finite/flowing under grad through the `program_bank` bridge;
  * mesh sharding: on a forced 2-device CPU mesh the engine shards the
    batch over the dp axes (queries carry a P(dp) spec; outputs come back
    dp-sharded) and classify output is bit-identical to single-device for
    B in {256, 1024}, for the hybrid classifier and the serving scheduler
    alike (subprocess, XLA_FLAGS must predate jax import).
"""
import functools
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import match
from repro.core import acam, hybrid, matching
from repro.core import templates as templates_lib
from repro.core.templates import TemplateBank

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _bank(key, c=6, k=2, n=64) -> TemplateBank:
    tmpl = (jax.random.uniform(key, (c, k, n)) > 0.5).astype(jnp.float32)
    lo = (jax.random.uniform(jax.random.fold_in(key, 1), (c, k, n)) > 0.6
          ).astype(jnp.float32)
    hi = jnp.maximum(lo, (jax.random.uniform(jax.random.fold_in(key, 2),
                                             (c, k, n)) > 0.4
                          ).astype(jnp.float32))
    valid = jnp.ones((c, k), bool)
    if k > 1:
        valid = valid.at[0, k - 1].set(False).at[c - 1, 0].set(False)
    thr = jax.random.normal(jax.random.fold_in(key, 3), (n,)) * 0.1
    return TemplateBank(tmpl, lo, hi, valid, thr)


class TestEngineAPI:
    def test_engine_for_memoised(self):
        e1 = match.engine_for(method="feature_count", backend="kernel")
        e2 = match.engine_for(method="feature_count", backend="kernel")
        assert e1 is e2
        assert e1 is not match.engine_for(method="similarity",
                                          backend="kernel")

    def test_config_hashable_and_static_jittable(self):
        cfg = match.EngineConfig(backend="reference",
                                 device=acam.ACAMConfig(sigma_program=0.1))
        assert hash(cfg) == hash(match.EngineConfig(
            backend="reference", device=acam.ACAMConfig(sigma_program=0.1)))

        # the whole point of EngineConfig: it works as a static jit arg
        calls = []

        @functools.partial(jax.jit, static_argnames=("cfg",))
        def by_config(x, cfg):
            calls.append(cfg)
            return x + 1

        by_config(jnp.zeros(2), cfg)
        by_config(jnp.zeros(2), cfg)  # cache hit: no retrace
        assert len(calls) == 1
        by_config(jnp.zeros(2), cfg._replace(backend="kernel"))
        assert len(calls) == 2  # different config -> different trace

    def test_unknown_backend_and_method_raise(self):
        with pytest.raises(ValueError):
            match.MatchEngine(match.EngineConfig(backend="cuda"))
        with pytest.raises(ValueError):
            match.MatchEngine(match.EngineConfig(method="cosine"))
        with pytest.raises(ValueError):
            match.engine_for(backend="gpuuu")

    def test_registry_lists_first_class_backends(self):
        names = match.backend_names()
        assert {"reference", "kernel", "device"} <= set(names)
        with pytest.raises(ValueError):
            match.register_backend("auto", lambda cfg: None)

    def test_use_backend_scopes_and_restores(self):
        before = match.default_backend()
        with match.use_backend("reference"):
            assert match.default_backend() == "reference"
            assert matching.get_backend() == "reference"  # shim parity
            with match.use_backend("kernel"):
                assert match.default_backend() == "kernel"
            assert match.default_backend() == "reference"
        assert match.default_backend() == before

    def test_auto_policy_tiny_vs_large(self):
        eng = match.engine_for(backend="auto")
        assert eng.backend(match.TINY_ELEMENTS - 1).name == "reference"
        assert eng.backend(match.TINY_ELEMENTS).name == "kernel"

    def test_margin_config_directed_call(self):
        key = jax.random.PRNGKey(0)
        bank = _bank(key)
        feats = jax.random.normal(jax.random.fold_in(key, 4), (8, 64))
        plain = match.engine_for(backend="reference")
        with_m = match.engine_for(backend="reference", margin=True)
        assert len(plain(feats, bank)) == 2
        pred, per_class, margin = with_m(feats, bank)
        assert margin.shape == (8,)
        np.testing.assert_array_equal(np.asarray(pred),
                                      np.asarray(plain(feats, bank)[0]))


class TestFusedForwardRetrace:
    """Satellite: the `set_backend` trace-time baking footgun is fixed."""

    def test_backend_change_retraces_fused_forward(self):
        key = jax.random.PRNGKey(1)
        x = jax.random.normal(key, (32, 64))
        y = jnp.arange(32) % 4
        bank = templates_lib.generate_templates(x, y, 4, k=1)
        clf = hybrid.HybridClassifier(None, lambda p, q: q,
                                      hybrid.ACAMHead(bank=bank))
        with match.use_backend("reference"):
            p_ref = clf.predict(x)
            size_ref = hybrid._fused_forward._cache_size()
            # same backend again: cache hit, no new trace
            clf.predict(x)
            assert hybrid._fused_forward._cache_size() == size_ref
        with match.use_backend("kernel"):
            # the backend is a static jit argument resolved at call time:
            # a changed default MUST key a different executable
            p_ker = clf.predict(x)
            assert hybrid._fused_forward._cache_size() == size_ref + 1
        np.testing.assert_array_equal(np.asarray(p_ref), np.asarray(p_ker))

    def test_head_backend_field_pins_over_default(self):
        key = jax.random.PRNGKey(2)
        x = jax.random.normal(key, (16, 64))
        y = jnp.arange(16) % 4
        bank = templates_lib.generate_templates(x, y, 4, k=1)
        head = hybrid.ACAMHead(bank=bank, backend="reference")
        assert head.engine().config.backend == "reference"
        with match.use_backend("kernel"):
            assert head.engine().config.backend == "reference"


class TestDeviceBackendParity:
    """Satellite: the acam.py physics models through the engine API."""

    @pytest.mark.parametrize("cell", ["6T4R", "3T1R"])
    def test_decisions_match_reference_at_sigma_zero(self, cell):
        key = jax.random.PRNGKey(3)
        bank = _bank(key, c=10, k=2, n=128)
        feats = jax.random.normal(jax.random.fold_in(key, 5), (37, 128))
        dev = match.engine_for(backend="device",
                               device=acam.ACAMConfig(cell=cell,
                                                      sigma_program=0.0))
        ref = match.engine_for(backend="reference")
        pred_d, pc_d = dev.classify_features(feats, bank)
        pred_r, pc_r = ref.classify_features(feats, bank)
        np.testing.assert_array_equal(np.asarray(pred_d), np.asarray(pred_r))
        # device scores are matchline fractions: count / N exactly at
        # sigma=0 (valid rows; invalid stay -inf on both backends)
        finite = np.isfinite(np.asarray(pc_r))
        np.testing.assert_allclose(np.asarray(pc_d)[finite],
                                   np.asarray(pc_r)[finite] / 128.0,
                                   rtol=1e-5, atol=1e-6)

    def test_margins_are_fraction_scaled(self):
        key = jax.random.PRNGKey(4)
        bank = _bank(key, c=8, k=1, n=64)
        feats = jax.random.normal(jax.random.fold_in(key, 6), (16, 64))
        dev = match.engine_for(backend="device")
        ref = match.engine_for(backend="reference")
        pred_d, _, m_d = dev.classify_features_margin(feats, bank)
        pred_r, _, m_r = ref.classify_features_margin(feats, bank)
        np.testing.assert_array_equal(np.asarray(pred_d), np.asarray(pred_r))
        np.testing.assert_allclose(np.asarray(m_d), np.asarray(m_r) / 64.0,
                                   rtol=1e-5, atol=1e-6)

    def test_similarity_alpha_zero_matches_reference(self):
        # at alpha=0 the reference similarity is the pure Eq. 10 in-window
        # fraction — exactly what the matchline senses
        key = jax.random.PRNGKey(5)
        bank = _bank(key, c=6, k=2, n=96)
        q = (jax.random.uniform(jax.random.fold_in(key, 7), (21, 96)) > 0.5
             ).astype(jnp.float32)
        dev = match.engine_for(method="similarity", alpha=0.0,
                               backend="device")
        ref = match.engine_for(method="similarity", alpha=0.0,
                               backend="reference")
        pred_d, _ = dev.classify(q, bank)
        pred_r, _ = ref.classify(q, bank)
        np.testing.assert_array_equal(np.asarray(pred_d), np.asarray(pred_r))

    def test_sigma_program_perturbs_through_engine(self):
        key = jax.random.PRNGKey(6)
        bank = _bank(key, c=6, k=1, n=64)
        feats = jax.random.normal(jax.random.fold_in(key, 8), (64, 64))
        ideal = match.engine_for(backend="device")
        noisy = match.engine_for(
            backend="device",
            device=acam.ACAMConfig(sigma_program=0.5), seed=11)
        _, pc_i = ideal.classify_features(feats, bank)
        _, pc_n = noisy.classify_features(feats, bank)
        assert not np.allclose(np.asarray(pc_i), np.asarray(pc_n))
        # deterministic per seed
        _, pc_n2 = match.engine_for(
            backend="device",
            device=acam.ACAMConfig(sigma_program=0.5),
            seed=11).classify_features(feats, bank)
        np.testing.assert_array_equal(np.asarray(pc_n), np.asarray(pc_n2))

    def test_service_resolves_default_device_backend_for_tau(self):
        """ACAMService(backend=None) under a process default of "device"
        must rescale margin_tau exactly like a pinned backend="device"
        service — otherwise count-unit taus meet fraction-unit margins and
        the cascade silently escalates everything."""
        from repro.serve import acam_service as svc_lib

        def build(backend):
            svc = svc_lib.ACAMService(
                64, config=svc_lib.ServiceConfig(slots=8), backend=backend)
            bank, head, p = svc_lib.make_synthetic_tenant(
                60, num_classes=6, num_features=64)
            svc.register_tenant("t", bank, head=head)
            return svc, p

        with match.use_backend("device"):
            svc_default, protos = build(None)
        svc_pinned, _ = build("device")
        feats, _ = svc_lib.sample_tenant_queries(2, protos, 24, noise=0.9)
        reqs = [svc_lib.ClassifyRequest("t", feats[i]) for i in range(24)]
        r_default = svc_default.serve(list(reqs))
        r_pinned = svc_pinned.serve(list(reqs))
        assert [(r.pred, r.escalated) for r in r_default] == \
            [(r.pred, r.escalated) for r in r_pinned]
        assert not all(r.escalated for r in r_default)

    def test_soft_sense_grad_finite_through_program_bank(self):
        key = jax.random.PRNGKey(7)
        bank = _bank(key, c=4, k=1, n=32)
        feats = jax.random.uniform(jax.random.fold_in(key, 9), (12, 32))
        be = match.backend_for("device", match.EngineConfig(backend="device"))
        prog = be.program_bank(bank)

        def loss(bounds):
            lo, hi = bounds
            sim = acam.soft_sense(prog._replace(lower=lo, upper=hi), feats)
            return -jnp.mean(jax.nn.log_softmax(sim * 10.0, axis=-1)[:, 0])

        glo, ghi = jax.grad(loss)((prog.lower, prog.upper))
        for g in (glo, ghi):
            arr = np.asarray(g)
            assert np.all(np.isfinite(arr))
            assert np.abs(arr).max() > 0.0


class TestShardSpecs:
    """Unit-level: the engine's shard_map specs put the queries on the dp
    axes and replicate the bank."""

    def test_queries_are_dp_sharded(self):
        in_specs, out_specs = match.batch_specs(("data",), 3, (1, 2, 1))
        assert in_specs[0] == P(("data",))   # features
        assert in_specs[1] == P(("data",))   # class_lo
        assert in_specs[2] == P(("data",))   # class_hi
        assert in_specs[3] == P()            # bank: replicated
        assert out_specs[0] == P(("data",))
        assert out_specs[1] == P(("data",), None)

    def test_multi_axis_dp(self):
        in_specs, out_specs = match.batch_specs(("pod", "data"), 1, (2,))
        assert in_specs[0] == P(("pod", "data"))
        assert out_specs[0] == P(("pod", "data"), None)

    def test_no_mesh_means_no_sharding(self):
        from repro.distributed import context

        # save/restore: under REPRO_FORCE_MESH the suite runs with a mesh
        saved_axes, saved_mesh = context.get(), context.get_mesh()
        context.clear()
        try:
            assert match.dp_axes_in_mesh() == (None, None)
            plan, mesh = match.plan_for(batch=256, num_classes=128)
            assert plan is match.REPLICATED and mesh is None
        finally:
            if saved_axes is not None:
                context.set_mesh_axes(saved_axes.dp, saved_axes.model,
                                      saved_mesh)


class TestPartitionPlan:
    """Unit-level: plan derivation from mesh + static shapes (no devices
    needed — a (1, 1) host mesh exercises the code paths; the forced
    multi-device parity lives in tests/test_bank_sharding.py)."""

    def _with_mesh(self, shape):
        from repro.distributed import context

        from repro.launch.mesh import make_mesh
        mesh = make_mesh(shape, ("data", "model"))
        context.set_mesh_axes("data", "model", mesh)
        return mesh

    def _restore(self, saved):
        from repro.distributed import context

        context.clear()
        if saved[0] is not None:
            context.set_mesh_axes(saved[0].dp, saved[0].model, saved[1])

    def test_plan_replicated_on_trivial_mesh(self):
        from repro.distributed import context

        saved = (context.get(), context.get_mesh())
        try:
            self._with_mesh((1, 1))
            plan, mesh = match.plan_for(batch=256, num_classes=128)
            assert plan is match.REPLICATED and mesh is None
        finally:
            self._restore(saved)

    def test_plan_is_hashable_and_specs(self):
        from jax.sharding import PartitionSpec as PS

        plan = match.PartitionPlan(dp=("data",), model="model",
                                   dp_devices=2, bank_shards=2,
                                   rows_per_shard=64)
        assert hash(plan) == hash(match.PartitionPlan(
            dp=("data",), model="model", dp_devices=2, bank_shards=2,
            rows_per_shard=64))
        assert plan.batch_sharded and plan.bank_sharded and plan.sharded
        assert plan.batch_spec() == PS(("data",))
        assert plan.class_spec() == PS("model")
        assert plan.batch_class_spec(3) == PS(("data",), "model", None)
        bank_sp = match.bank_specs(plan)
        assert bank_sp.templates == PS("model")
        assert bank_sp.thresholds == PS()

    def test_non_divisible_shapes_stay_replicated_axes(self):
        plan = match.PartitionPlan()
        assert not plan.sharded
        assert plan.batch_spec() == jax.sharding.PartitionSpec(None)

    def test_bank_shards_in_mesh(self):
        from repro.distributed import context

        saved = (context.get(), context.get_mesh())
        try:
            context.clear()
            assert match.bank_shards_in_mesh() == 1
            self._with_mesh((1, 1))
            assert match.bank_shards_in_mesh() == 1
        finally:
            self._restore(saved)


class TestMeshGenerationRetrace:
    """Satellite: installing/clearing a mesh re-traces jitted callers that
    bake the engine's PartitionPlan (mirrors the use_backend retrace test —
    a (1, 1) mesh never shards, so only the static mesh_gen key changes)."""

    def test_mesh_change_retraces_fused_forward(self):
        from repro.distributed import context

        key = jax.random.PRNGKey(21)
        x = jax.random.normal(key, (32, 64))
        y = jnp.arange(32) % 4
        bank = templates_lib.generate_templates(x, y, 4, k=1)
        clf = hybrid.HybridClassifier(None, lambda p, q: q,
                                      hybrid.ACAMHead(bank=bank,
                                                      backend="reference"))
        saved = (context.get(), context.get_mesh())
        try:
            p0 = clf.predict(x)
            size0 = hybrid._fused_forward._cache_size()
            clf.predict(x)  # same generation: cache hit
            assert hybrid._fused_forward._cache_size() == size0
            from repro.launch.mesh import make_mesh
            mesh = make_mesh((1, 1), ("data", "model"))
            context.set_mesh_axes("data", "model", mesh)
            p1 = clf.predict(x)
            assert hybrid._fused_forward._cache_size() == size0 + 1
            context.clear()
            p2 = clf.predict(x)
            assert hybrid._fused_forward._cache_size() == size0 + 2
            np.testing.assert_array_equal(np.asarray(p0), np.asarray(p1))
            np.testing.assert_array_equal(np.asarray(p0), np.asarray(p2))
        finally:
            context.clear()
            if saved[0] is not None:
                context.set_mesh_axes(saved[0].dp, saved[0].model, saved[1])

    def test_mesh_change_retraces_scheduler_tick(self):
        import time

        from repro.distributed import context
        from repro.serve import acam_service as svc_lib
        from repro.serve import scheduler as sched_lib

        saved = (context.get(), context.get_mesh())

        def tick_once(sched, feats):
            sched.submit(sched_lib.WorkItem(0, "t", feats,
                                            time.perf_counter()))
            return [(r.pred_local, round(r.margin, 6)) for r in sched.tick()]

        try:
            bank, _, protos = svc_lib.make_synthetic_tenant(
                77, num_classes=6, num_features=64)
            from repro.serve.registry import TemplateBankRegistry

            reg = TemplateBankRegistry(64)
            reg.register("t", bank)
            sched = sched_lib.MicroBatchScheduler(reg, slots=4,
                                                  backend="reference")
            feats, _ = svc_lib.sample_tenant_queries(3, protos, 1)
            out0 = tick_once(sched, feats[0])
            size0 = sched_lib._packed_tick._cache_size()
            tick_once(sched, feats[0])  # same generation: cache hit
            assert sched_lib._packed_tick._cache_size() == size0
            from repro.launch.mesh import make_mesh
            mesh = make_mesh((1, 1), ("data", "model"))
            context.set_mesh_axes("data", "model", mesh)
            out1 = tick_once(sched, feats[0])
            # mesh_gen is a static jit arg: a new mesh keys a fresh trace
            assert sched_lib._packed_tick._cache_size() == size0 + 1
            assert out1 == out0
        finally:
            context.clear()
            if saved[0] is not None:
                context.set_mesh_axes(saved[0].dp, saved[0].model, saved[1])


class TestSweepProgramNoise:
    """Satellite: Monte-Carlo programming-noise sweep through the engine."""

    def test_per_key_predictions_shape_and_determinism(self):
        key = jax.random.PRNGKey(31)
        bank = _bank(key, c=6, k=1, n=64)
        feats = jax.random.normal(jax.random.fold_in(key, 1), (40, 64))
        eng = match.engine_for(
            backend="device", device=acam.ACAMConfig(sigma_program=0.4),
            seed=5)
        pred, per_class = eng.sweep_program_noise(feats, bank, 4)
        assert pred.shape == (4, 40)
        assert per_class.shape == (4, 40, 6)
        # draws differ between keys...
        accs = np.asarray(per_class)
        assert not np.allclose(accs[0], accs[1])
        # ...and the sweep is deterministic per config seed
        pred2, _ = eng.sweep_program_noise(feats, bank, 4)
        np.testing.assert_array_equal(np.asarray(pred), np.asarray(pred2))

    def test_sigma_zero_draws_collapse_to_ideal(self):
        key = jax.random.PRNGKey(32)
        bank = _bank(key, c=5, k=1, n=32)
        feats = jax.random.normal(jax.random.fold_in(key, 2), (16, 32))
        eng = match.engine_for(backend="device")
        pred, per_class = eng.sweep_program_noise(feats, bank, 3)
        ideal_pred, ideal_pc = eng.classify_features(feats, bank)
        for m in range(3):
            np.testing.assert_array_equal(np.asarray(pred[m]),
                                          np.asarray(ideal_pred))
            np.testing.assert_allclose(np.asarray(per_class[m]),
                                       np.asarray(ideal_pc), rtol=1e-6)

    def test_explicit_keys_and_backend_guard(self):
        key = jax.random.PRNGKey(33)
        bank = _bank(key, c=4, k=1, n=32)
        feats = jax.random.normal(jax.random.fold_in(key, 3), (8, 32))
        eng = match.engine_for(
            backend="device", device=acam.ACAMConfig(sigma_program=0.2))
        keys = jax.random.split(jax.random.PRNGKey(7), 5)
        pred, _ = eng.sweep_program_noise(feats, bank, keys)
        assert pred.shape == (5, 8)
        with pytest.raises(ValueError):
            match.engine_for(backend="kernel").sweep_program_noise(
                feats, bank, 2)

    def test_per_shard_noise_is_a_distinct_deterministic_semantics(self):
        """Satellite: `device_noise="per_shard"` programs one array per
        bank shard (fold_in(seed, s)) — the sweep covers the tiled layout
        without a mesh via `bank_shards=S` emulation."""
        key = jax.random.PRNGKey(34)
        bank = _bank(key, c=8, k=1, n=32)
        feats = jax.random.normal(jax.random.fold_in(key, 4), (20, 32))
        dev = acam.ACAMConfig(sigma_program=0.3)
        tiled = match.engine_for(backend="device", device=dev, seed=5,
                                 device_noise="per_shard")
        mono = match.engine_for(backend="device", device=dev, seed=5)
        # per-shard noise lifts the backend's bank-sharding refusal
        assert tiled.backend(None).supports_bank_sharding
        assert not mono.backend(None).supports_bank_sharding
        _, pc2 = tiled.sweep_program_noise(feats, bank, 3, bank_shards=2)
        _, pc2b = tiled.sweep_program_noise(feats, bank, 3, bank_shards=2)
        np.testing.assert_array_equal(np.asarray(pc2), np.asarray(pc2b))
        # a 2-array tiling realises a different noise field than 1 array
        _, pc1 = tiled.sweep_program_noise(feats, bank, 3, bank_shards=1)
        assert not np.allclose(np.asarray(pc1), np.asarray(pc2))
        # ...and than the "global" one-array semantics (fold_in vs raw key)
        _, pcg = mono.sweep_program_noise(feats, bank, 3)
        assert not np.allclose(np.asarray(pcg), np.asarray(pc2))
        # indivisible class counts fall back to one array, not an error
        _, pc_odd = tiled.sweep_program_noise(feats, bank, 3, bank_shards=3)
        np.testing.assert_array_equal(np.asarray(pc_odd), np.asarray(pc1))


def run_sub(code: str, timeout=600) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    # pin the CPU platform: without it jax probes the TPU runtime in this
    # container and stalls for minutes before falling back. XLA_FLAGS
    # (forced host device count) is set inside the child before jax import.
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=timeout,
                         env=env)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    return out.stdout


class TestMeshSharded:
    """Forced 2-device CPU mesh (subprocess: XLA_FLAGS precedes jax)."""

    def test_engine_bit_identical_and_dp_sharded_2dev(self):
        out = run_sub("""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
            import jax, jax.numpy as jnp
            import numpy as np
            from repro import match
            from repro.core.templates import TemplateBank
            from repro.distributed import context

            key = jax.random.PRNGKey(0)
            c, k, n = 10, 2, 784
            tmpl = (jax.random.uniform(key, (c, k, n)) > 0.5
                    ).astype(jnp.float32)
            bank = TemplateBank(tmpl, jnp.zeros_like(tmpl),
                                jnp.ones_like(tmpl), jnp.ones((c, k), bool),
                                jnp.zeros((n,)))
            eng = match.engine_for(backend="kernel")
            eng_m = match.engine_for(backend="kernel", margin=True)

            for b in (256, 1024):
                feats = jax.random.normal(jax.random.fold_in(key, b), (b, n))
                lo = jnp.zeros((b,), jnp.int32)
                hi = jnp.full((b,), c, jnp.int32)

                context.clear()
                pred1, pc1 = eng.classify_features(feats, bank)
                p1, _, m1 = eng_m.classify_features_margin(feats, bank,
                                                           lo, hi)
                s1 = eng.scores(feats, bank)

                from repro.launch.mesh import make_mesh
                mesh = make_mesh((2, 1), ("data", "model"))
                context.set_mesh_axes("data", "model", mesh)
                assert match.dp_axes_in_mesh()[1] == ("data",)
                pred2, pc2 = eng.classify_features(feats, bank)
                p2, _, m2 = eng_m.classify_features_margin(feats, bank,
                                                           lo, hi)
                s2 = eng.scores(feats, bank)
                context.clear()

                # outputs came back dp-sharded: the batch really ran
                # split across the two devices
                spec = pred2.sharding.spec
                assert tuple(spec)[:1] in ((("data",),), ("data",)), spec
                assert len(pred2.sharding.device_set) == 2

                assert np.array_equal(np.asarray(pred1), np.asarray(pred2))
                assert np.array_equal(np.asarray(pc1), np.asarray(pc2))
                assert np.array_equal(np.asarray(p1), np.asarray(p2))
                assert np.array_equal(np.asarray(m1), np.asarray(m2))
                assert np.array_equal(np.asarray(s1), np.asarray(s2))
                print("OK", b)
            """)
        assert out.count("OK") == 2

    def test_hybrid_predict_and_scheduler_2dev(self):
        out = run_sub("""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
            import jax, jax.numpy as jnp
            import numpy as np
            from repro import match
            from repro.core import hybrid, templates
            from repro.distributed import context
            from repro.serve import acam_service as svc_lib

            key = jax.random.PRNGKey(0)
            x = jax.random.normal(key, (256, 64))
            y = jnp.arange(256) % 8
            bank = templates.generate_templates(x, y, 8, k=1)
            clf = hybrid.HybridClassifier(None, lambda p, q: q,
                                          hybrid.ACAMHead(bank=bank))

            def serve_once():
                svc = svc_lib.ACAMService(
                    64, config=svc_lib.ServiceConfig(slots=16))
                protos = {}
                for t in range(3):
                    b, h, p = svc_lib.make_synthetic_tenant(
                        50 + t, num_classes=6, num_features=64)
                    svc.register_tenant(f"t{t}", b, head=h)
                    protos[f"t{t}"] = p
                reqs = []
                for t in range(3):
                    f, _ = svc_lib.sample_tenant_queries(
                        9 + t, protos[f"t{t}"], 16)
                    reqs += [svc_lib.ClassifyRequest(f"t{t}", f[i])
                             for i in range(16)]
                rs = svc.serve(reqs)
                return [(r.pred, r.escalated) for r in rs]

            context.clear()
            pred1 = clf.predict(x)
            served1 = serve_once()

            from repro.launch.mesh import make_mesh
            mesh = make_mesh((2, 1), ("data", "model"))
            context.set_mesh_axes("data", "model", mesh)
            pred2 = clf.predict(x)
            served2 = serve_once()
            context.clear()

            assert np.array_equal(np.asarray(pred1), np.asarray(pred2))
            assert served1 == served2
            print("OK hybrid+scheduler")
            """)
        assert "OK hybrid+scheduler" in out
