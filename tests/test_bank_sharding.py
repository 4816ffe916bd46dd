"""2D-sharded matching: the PartitionPlan bank-sharding contracts (PR 4).

Three layers of coverage:

  * shard-aligned registry (in-process): `TemplateBankRegistry(bank_shards=S)`
    keeps capacity divisible by S, never places a tenant's bucket run across
    a shard boundary (allocations skip to the next shard instead), and
    preserves alignment across capacity growth and evict/re-register churn;
  * class-chunked serve kernel (in-process): banks past `MAX_FUSED_ROWS`
    stay a single pallas_call, and its single-tenant face agrees bit-for-bit
    with the jnp oracle at resident and chunked bank sizes;
  * forced 2x2 CPU mesh (subprocess, XLA_FLAGS before jax import): the
    bank-sharded engine and the FULL ACAMService tick are bit-identical to
    replicated execution — predictions, margins, escalation set — for
    B in {256, 1024}, for tenant windows adjacent to shard edges, bucket-
    padded rows, shard-straddling layouts the allocator must re-place, and
    evict/re-register across a shard, with exactly ONE sharded dispatch per
    scheduler tick; the XOR-butterfly tree reduce (`PartitionPlan.reduce`,
    REPRO_REDUCE_STRATEGY=tree) agrees bit-for-bit with the all-gather fold
    and with replicated execution.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import match
from repro.core.templates import TemplateBank
from repro.kernels import layout
from repro.serve import acam_service as svc_lib
from repro.serve.registry import TemplateBankRegistry

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
N_FEATURES = 64


def _tenant(seed, classes):
    return svc_lib.make_synthetic_tenant(seed, num_classes=classes,
                                         num_features=N_FEATURES)


def _no_straddle(reg):
    rps = reg.rows_per_shard
    for tid in list(reg._tenants):
        e = reg.get(tid)
        first, last = e.offset, e.offset + e.c_bucket - 1
        assert first // rps == last // rps, (tid, e, rps)


class TestShardAlignedRegistry:
    def test_capacity_rounds_up_to_shard_multiple(self):
        reg = TemplateBankRegistry(N_FEATURES, class_bucket=16,
                                   initial_classes=48, bank_shards=4)
        assert reg.capacity_classes % (4 * 16) == 0
        assert reg.rows_per_shard * 4 == reg.capacity_classes
        assert reg.stats()["bank_shards"] == 4

    def test_bad_bank_shards_raises(self):
        with pytest.raises(ValueError):
            TemplateBankRegistry(N_FEATURES, bank_shards=0)

    def test_allocations_never_straddle_a_shard(self):
        # 48-row tenants on a 256-row, 2-shard bank: the third tenant would
        # span rows [96, 144) across the row-128 boundary — the allocator
        # must skip it to offset 128 (rows 96..128 stay masked padding)
        reg = TemplateBankRegistry(N_FEATURES, class_bucket=16,
                                   initial_classes=256, bank_shards=2)
        offsets = []
        for t in range(3):
            bank, _, _ = _tenant(400 + t, 40)
            offsets.append(reg.register(f"t{t}", bank).offset)
        assert offsets == [0, 48, 128]
        _no_straddle(reg)
        # the skipped rows are not programmed
        sb = reg.device_bank()
        assert not np.asarray(sb.valid[96:128]).any()

    def test_growth_preserves_alignment(self):
        reg = TemplateBankRegistry(N_FEATURES, class_bucket=16,
                                   initial_classes=64, bank_shards=2)
        for t in range(6):  # 6 x 16-row buckets > 64 rows: forces growth
            bank, _, _ = _tenant(500 + t, 10)
            reg.register(f"t{t}", bank)
        assert reg.capacity_classes == 128
        assert reg.capacity_classes % (2 * 16) == 0
        _no_straddle(reg)

    def test_churn_keeps_alignment(self):
        reg = TemplateBankRegistry(N_FEATURES, class_bucket=16,
                                   initial_classes=128, bank_shards=2)
        for t in range(4):
            bank, _, _ = _tenant(600 + t, 24)
            reg.register(f"t{t}", bank)
        reg.evict("t1")
        big, _, _ = _tenant(660, 40)  # bigger than the freed 32-row range
        reg.register("big", big)
        small, _, _ = _tenant(661, 10)
        reg.register("re", small)
        _no_straddle(reg)

    def test_unsharded_default_unchanged(self):
        reg = TemplateBankRegistry(N_FEATURES)
        assert reg.bank_shards == 1
        assert reg.rows_per_shard == reg.capacity_classes


class TestChunkedMarginsKernel:
    def test_class_chunk_selection(self):
        assert layout.class_chunk(1152, 2, 2048) == 384
        assert layout.class_chunk(256, 2, 2048) == 256
        assert layout.class_chunk(4096, 1, 2048) == 2048
        # even one lane tile of K slices over budget: lane fallback
        assert layout.class_chunk(128, 32, 2048) == 128

    def test_stack_kcp_roundtrip(self):
        key = jax.random.PRNGKey(0)
        arr = jax.random.normal(key, (10, 2, 8))
        stacked = layout.stack_kcp(arr, 10)
        assert stacked.shape == (2, 128, 8)
        np.testing.assert_array_equal(np.asarray(stacked[1, :10]),
                                      np.asarray(arr[:, 1, :]))
        assert not np.asarray(stacked[:, 10:]).any()

    @pytest.mark.parametrize("c,k", [(1100, 2), (300, 8)])
    def test_big_bank_margins_single_dispatch_parity(self, c, k):
        # both shapes exceed MAX_FUSED_ROWS: Cp(1100)*2 = 2304,
        # Cp(300)*8 = 3072
        key = jax.random.PRNGKey(4)
        n, b = 96, 16
        tmpl = (jax.random.uniform(key, (c, k, n)) > 0.5).astype(jnp.float32)
        valid = jnp.ones((c, k), bool).at[2, k - 1].set(False)
        valid = valid.at[c - 1, :].set(False)
        bank = TemplateBank(tmpl, jnp.zeros_like(tmpl), jnp.ones_like(tmpl),
                            valid,
                            jax.random.normal(jax.random.fold_in(key, 1),
                                              (n,)) * 0.1)
        assert k * layout.padded_classes(c) > match.MAX_FUSED_ROWS
        feats = jax.random.normal(jax.random.fold_in(key, 2), (b, n))
        rng = np.random.RandomState(c)
        lo = jnp.asarray(rng.randint(0, c - 4, size=b), jnp.int32)
        hi = jnp.minimum(lo + rng.randint(1, 100, size=b), c).astype(jnp.int32)
        hi = hi.at[0].set(lo[0])  # empty window: pred 0, margin 0

        ker = match.engine_for(backend="kernel")
        ref = match.engine_for(backend="reference")
        p_k, pc_k, m_k = ker.classify_features_margin(feats, bank, lo, hi)
        p_r, pc_r, m_r = ref.classify_features_margin(feats, bank, lo, hi)
        np.testing.assert_array_equal(np.asarray(p_k), np.asarray(p_r))
        np.testing.assert_array_equal(np.asarray(pc_k), np.asarray(pc_r))
        np.testing.assert_array_equal(np.asarray(m_k), np.asarray(m_r))
        assert float(m_k[0]) == 0.0 and int(p_k[0]) == 0

    def test_matches_resident_fused_kernel_bit_for_bit(self):
        """The feature_count face equals the jnp oracle bit for bit, at a
        resident bank (one chunk) and a class-chunked one."""
        from repro.kernels.acam_match import ops as match_ops

        key = jax.random.PRNGKey(5)
        n, b = 64, 8
        ref = match.engine_for(backend="reference")
        for c, k in ((900, 2), (1100, 2)):
            cp = layout.padded_classes(c)
            assert (cp // layout.class_chunk(cp, k, match.MAX_FUSED_ROWS)
                    > 1) == (c == 1100)
            tmpl = (jax.random.uniform(jax.random.fold_in(key, c),
                                       (c, k, n)) > 0.5).astype(jnp.float32)
            valid = jnp.ones((c, k), bool).at[3, 1].set(False)
            thr = jax.random.normal(jax.random.fold_in(key, 2), (n,)) * 0.1
            feats = jax.random.normal(jax.random.fold_in(key, 1), (b, n))
            lo = jnp.zeros((b,), jnp.int32)
            hi = jnp.full((b,), c, jnp.int32)
            got = match_ops.classify_fused_margins(
                feats, thr, tmpl, valid, lo, hi,
                max_rows=match.MAX_FUSED_ROWS)
            bank = TemplateBank(tmpl, jnp.zeros_like(tmpl),
                                jnp.ones_like(tmpl), valid, thr)
            want = ref.classify_features_margin(feats, bank, lo, hi)
            for g, w in zip(got, want):
                np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


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


class TestForced2x2Mesh:
    """Bank-sharded vs replicated bit-identity on a forced 2x2 CPU mesh."""

    def test_engine_bit_identical_2d_sharded(self):
        out = run_sub("""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
            import jax, jax.numpy as jnp
            import numpy as np
            from repro import match
            from repro.core.templates import TemplateBank
            from repro.distributed import context

            key = jax.random.PRNGKey(0)
            c, k, n = 256, 2, 128
            tmpl = (jax.random.uniform(key, (c, k, n)) > 0.5
                    ).astype(jnp.float32)
            valid = jnp.ones((c, k), bool).at[0, 1].set(False)
            valid = valid.at[c - 1, 0].set(False)
            bank = TemplateBank(tmpl, jnp.zeros_like(tmpl),
                                jnp.ones_like(tmpl), valid, jnp.zeros((n,)))
            eng = match.engine_for(backend="kernel")

            for b in (256, 1024):
                feats = jax.random.normal(jax.random.fold_in(key, b), (b, n))
                rng = np.random.RandomState(b)
                # windows adjacent to AND straddling the row-128 shard edge
                lo = rng.randint(0, c - 8, size=b)
                lo[:4] = (120, 128, 100, 0)
                hi = np.minimum(lo + rng.randint(1, 64, size=b), c)
                hi[:4] = (128, 160, 156, c)
                lo, hi = jnp.asarray(lo, jnp.int32), jnp.asarray(hi, jnp.int32)

                context.clear()
                pred1, pc1 = eng.classify_features(feats, bank)
                p1, _, m1 = eng.classify_features_margin(feats, bank, lo, hi)

                from repro.launch.mesh import make_mesh
                mesh = make_mesh((2, 2), ("data", "model"))
                context.set_mesh_axes("data", "model", mesh)
                plan, _ = match.plan_for(batch=b, num_classes=c)
                assert plan.bank_shards == 2 and plan.dp_devices == 2, plan
                assert plan.rows_per_shard == 128
                pred2, pc2 = eng.classify_features(feats, bank)
                p2, _, m2 = eng.classify_features_margin(feats, bank, lo, hi)
                context.clear()

                # the batch really ran split over the data axis
                assert len(pred2.sharding.device_set) >= 2
                assert np.array_equal(np.asarray(pred1), np.asarray(pred2))
                assert np.array_equal(np.asarray(pc1), np.asarray(pc2))
                assert np.array_equal(np.asarray(p1), np.asarray(p2))
                assert np.array_equal(np.asarray(m1), np.asarray(m2))
                print("OK", b)
            """)
        assert out.count("OK") == 2

    def test_service_bit_identical_one_dispatch_per_tick(self):
        out = run_sub("""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
            import jax
            import numpy as np
            from repro import match
            from repro.distributed import context
            from repro.serve import acam_service as svc_lib

            # two shard-straddling tenant layouts: (a) 24-class tenants
            # packing shard 0 edge-to-edge (windows adjacent to row 128),
            # (b) 40-class tenants whose third placement would straddle the
            # boundary and must be re-placed to shard 1 (rows 96-128 become
            # masked padding)
            LAYOUTS = {"edge_packed": [24] * 7, "straddle_skip": [40] * 4}

            def build_and_serve(layout, slots, churn):
                svc = svc_lib.ACAMService(
                    64, config=svc_lib.ServiceConfig(slots=slots,
                                                     margin_tau=6.0))
                protos = {}
                for t, classes in enumerate(LAYOUTS[layout]):
                    bank, head, p = svc_lib.make_synthetic_tenant(
                        1000 + 17 * t, num_classes=classes, num_features=64)
                    svc.register_tenant(f"t{t}", bank, head=head)
                    protos[f"t{t}"] = p
                if churn:
                    # evict from shard 0, re-register landing across a shard
                    svc.evict_tenant("t1")
                    bank, head, p = svc_lib.make_synthetic_tenant(
                        2000, num_classes=40, num_features=64)
                    svc.register_tenant("tx", bank, head=head)
                    protos["tx"] = p
                    del protos["t1"]
                calls = {"n": 0}
                orig = match.MatchEngine.classify_serve
                def counting(self, *a, **kw):
                    calls["n"] += 1
                    return orig(self, *a, **kw)
                match.MatchEngine.classify_serve = counting
                try:
                    reqs = []
                    for i, (tid, p) in enumerate(sorted(protos.items())):
                        f, _ = svc_lib.sample_tenant_queries(
                            7 + i, p, 40, noise=0.9)
                        reqs += [svc_lib.ClassifyRequest(tid, f[j])
                                 for j in range(40)]
                    rs = svc.serve(reqs)
                finally:
                    match.MatchEngine.classify_serve = orig
                stats = svc.scheduler.stats
                assert stats.classify_dispatches == stats.ticks
                assert 1 <= calls["n"] <= stats.ticks  # one engine
                # dispatch per tick: traces <= ticks, replays otherwise
                return svc, [(r.tenant_id, r.pred, r.escalated,
                              round(r.margin, 6)) for r in rs]

            for layout in LAYOUTS:
                for slots, churn in ((64, False), (16, True)):
                    context.clear()
                    svc1, out1 = build_and_serve(layout, slots, churn)
                    assert svc1.registry.bank_shards == 1

                    from repro.launch.mesh import make_mesh
                    mesh = make_mesh((2, 2), ("data", "model"))
                    context.set_mesh_axes("data", "model", mesh)
                    svc2, out2 = build_and_serve(layout, slots, churn)
                    context.clear()
                    assert svc2.registry.bank_shards == 2
                    rps = svc2.registry.rows_per_shard
                    for tid in list(svc2.registry._tenants):
                        e = svc2.registry.get(tid)
                        assert e.offset // rps == \
                            (e.offset + e.c_bucket - 1) // rps, (tid, e)
                    assert out1 == out2, layout
                    assert any(esc for _, _, esc, _ in out1)
                    assert any(not esc for _, _, esc, _ in out1)
                    print("OK", layout, slots, churn)
            """, timeout=900)
        assert out.count("OK") == 4

    def test_tree_reduce_bit_identical_to_allgather(self):
        """The XOR-butterfly cross-shard reduce (REPRO_REDUCE_STRATEGY=tree)
        yields the same bits as the all-gather fold AND as replicated
        execution — winner, margins, per-class scores, escalation set."""
        out = run_sub("""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
            import jax, jax.numpy as jnp
            import numpy as np
            from repro import match
            from repro.core.templates import TemplateBank
            from repro.distributed import context

            assert match.reduce_strategy(8) == "tree"       # default past 8
            assert match.reduce_strategy(2) == "allgather"  # small axis
            assert match.reduce_strategy(6) == "allgather"  # not a pow2
            os.environ["REPRO_REDUCE_STRATEGY"] = "tree"
            assert match.reduce_strategy(2) == "tree"       # env override
            assert match.reduce_strategy(6) == "allgather"  # pow2 required

            key = jax.random.PRNGKey(7)
            c, k, n, b, T = 256, 2, 128, 256, 8
            tmpl = (jax.random.uniform(key, (c, k, n)) > 0.5
                    ).astype(jnp.float32)
            valid = jnp.ones((c, k), bool).at[0, 1].set(False)
            bank = TemplateBank(tmpl, jnp.zeros_like(tmpl),
                                jnp.ones_like(tmpl), valid, jnp.zeros((n,)))
            eng = match.engine_for(backend="kernel")
            feats = jax.random.normal(jax.random.fold_in(key, 1), (b, n))
            thr_table = jax.random.normal(jax.random.fold_in(key, 2),
                                          (T, n)) * 0.1
            rng = np.random.RandomState(3)
            slot = jnp.asarray(rng.randint(0, T, b), jnp.int32)
            lo = rng.randint(0, c - 8, size=b)
            lo[:2] = (120, 100)  # windows straddling the row-128 shard edge
            hi = np.minimum(lo + rng.randint(1, 64, size=b), c)
            hi[:2] = (160, 156)
            lo, hi = jnp.asarray(lo, jnp.int32), jnp.asarray(hi, jnp.int32)
            tau = jnp.asarray(rng.uniform(0, 12, b), jnp.float32)

            context.clear()
            rep = eng.classify_serve(feats, thr_table, slot, bank, lo, hi,
                                     tau)
            pm_rep = eng.classify_features_margin(feats, bank, lo, hi)

            results = {}
            for strat in ("allgather", "tree"):
                os.environ["REPRO_REDUCE_STRATEGY"] = strat
                from repro.launch.mesh import make_mesh
                mesh = make_mesh((2, 2), ("data", "model"))
                context.set_mesh_axes("data", "model", mesh)
                plan, _ = match.plan_for(batch=b, num_classes=c)
                assert plan.bank_shards == 2 and plan.reduce == strat, plan
                results[strat] = (
                    eng.classify_serve(feats, thr_table, slot, bank, lo, hi,
                                       tau),
                    eng.classify_features_margin(feats, bank, lo, hi))
                context.clear()

            for strat, (serve, pm) in results.items():
                for a, b_ in zip(rep, serve):
                    assert np.array_equal(np.asarray(a), np.asarray(b_)), \
                        (strat, "serve")
                for a, b_ in zip(pm_rep, pm):
                    assert np.array_equal(np.asarray(a), np.asarray(b_)), \
                        (strat, "margin")
            esc = np.asarray(rep[3])
            assert esc.any() and not esc.all()
            print("OK tree")
            """)
        assert "OK tree" in out

    def test_repro_force_mesh_env_path(self):
        """The CI entry: REPRO_FORCE_MESH=2x2 via forcemesh two-phase."""
        out = run_sub("""
            import os
            os.environ["REPRO_FORCE_MESH"] = "2x2"
            from repro.distributed import forcemesh
            assert forcemesh.apply_xla_flags()
            import jax
            mesh = forcemesh.install()
            assert mesh is not None and len(jax.devices()) == 4
            from repro import match
            assert match.bank_shards_in_mesh() == 2
            plan, _ = match.plan_for(batch=64, num_classes=128)
            assert plan.bank_shards == 2 and plan.dp_devices == 2
            print("OK env")
            """)
        assert "OK env" in out
