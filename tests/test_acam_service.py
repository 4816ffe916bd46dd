"""Multi-tenant ACAM serving subsystem tests (registry/scheduler/service).

Asserts the serving contract from the margins kernel up:

  * ONE bank gather + ONE fused classify dispatch per micro-batch tick,
    regardless of how many tenants the batch mixes;
  * per-tenant predictions match the reference backend applied to each
    tenant's own bank (class windows never leak across tenants);
  * the confidence cascade escalates exactly the below-margin requests;
  * hot register / update / evict leave device shapes (and so jit caches)
    untouched in the steady state;
  * the margins kernel variant agrees with the jnp `window_margin` oracle.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import matching
from repro.serve import acam_service as svc_lib
from repro.serve.acam_service import (ACAMService, AdmissionError,
                                      ClassifyRequest, ServiceConfig)
from repro.serve.registry import RegistryError, TemplateBankRegistry

N_FEATURES = 64
N_CLASSES = 6
N_TENANTS = 8
SLOTS = 16


def _make_service(margin_tau=5.0, slots=SLOTS, max_queue=4096):
    svc = ACAMService(
        N_FEATURES,
        config=ServiceConfig(slots=slots, margin_tau=margin_tau,
                             max_queue=max_queue))
    banks, protos = {}, {}
    for t in range(N_TENANTS):
        bank, head, p = svc_lib.make_synthetic_tenant(
            200 + t, num_classes=N_CLASSES, num_features=N_FEATURES)
        tid = f"tenant-{t}"
        svc.register_tenant(tid, bank, head=head)
        banks[tid], protos[tid] = bank, p
    return svc, banks, protos


def _mixed_requests(protos, per_tenant=12, *, noise=0.9, seed=3):
    rng = np.random.RandomState(seed)
    reqs, truth = [], []
    for ti, (tid, p) in enumerate(protos.items()):
        feats, labels = svc_lib.sample_tenant_queries(
            seed + 31 * ti, p, per_tenant, noise=noise)
        for i in range(per_tenant):
            reqs.append(ClassifyRequest(tid, feats[i]))
            truth.append(int(labels[i]))
    order = rng.permutation(len(reqs))
    return [reqs[i] for i in order], [truth[i] for i in order]


class TestServiceEndToEnd:
    @pytest.fixture(scope="class")
    def served(self):
        svc, banks, protos = _make_service()
        reqs, truth = _mixed_requests(protos)
        responses = svc.serve(reqs)
        return svc, banks, reqs, truth, responses

    def test_one_gather_one_kernel_call_per_batch(self, served):
        svc, _, reqs, _, responses = served
        stats = svc.scheduler.stats
        expected_ticks = -(-len(reqs) // SLOTS)
        assert stats.ticks == expected_ticks
        # one fused dispatch per tick, and each crosses the host-device
        # boundary once each way: one upload of the packed operands, one
        # copy back of 20 bytes per slot (pred, escalate, margin, score and
        # the dense head's class: every tenant here has a head)
        assert stats.classify_dispatches == stats.h2d_transfers == \
            stats.d2h_transfers == expected_ticks
        assert stats.bytes_back == 20 * SLOTS * expected_ticks
        assert len(responses) == len(reqs)

    def test_per_tenant_predictions_match_reference(self, served):
        # accepted-at-ACAM responses must equal the reference backend run on
        # the tenant's own bank (escalated ones carry the CNN-head decision,
        # asserted in test_escalated_predictions_use_cnn_head)
        _, banks, reqs, _, responses = served
        checked = 0
        for req, resp in zip(reqs, responses):
            if resp.escalated:
                continue
            bank = banks[req.tenant_id]
            want, _ = matching.classify_features(
                jnp.asarray(req.features)[None, :], bank,
                backend="reference")
            assert resp.pred == int(want[0]), req.tenant_id
            checked += 1
        assert checked > 0

    def test_cascade_escalates_exactly_below_margin(self, served):
        svc, banks, reqs, _, responses = served
        tau = svc.config.margin_tau
        for req, resp in zip(reqs, responses):
            bank = banks[req.tenant_id]
            _, per_class = matching.classify_features(
                jnp.asarray(req.features)[None, :], bank,
                backend="reference")
            _, margin = matching.window_margin(per_class,
                                               cap=float(N_FEATURES))
            want_escalate = float(margin[0]) < tau
            assert resp.escalated == want_escalate
            np.testing.assert_allclose(resp.margin, float(margin[0]),
                                       rtol=1e-5, atol=1e-5)
        assert any(r.escalated for r in responses)
        assert any(not r.escalated for r in responses)

    def test_escalated_predictions_use_cnn_head(self, served):
        svc, _, reqs, _, responses = served
        for req, resp in zip(reqs, responses):
            if not resp.escalated:
                continue
            w, b = svc.head_of(req.tenant_id)
            logits = req.features @ w + b
            assert resp.pred == int(np.argmax(logits))

    def test_energy_attribution(self, served):
        svc, _, reqs, _, responses = served
        for req, resp in zip(reqs, responses):
            rt = svc._tenants[req.tenant_id]
            want = rt.backend_j + (svc._frontend_j if resp.escalated else 0.0)
            assert resp.energy_j == pytest.approx(want)
        m = svc.metrics()
        assert m["nj_per_request"] > 0
        assert 0 < m["escalation_rate"] < 1
        assert m["occupancy"] > 0

    def test_mixed_tenants_in_one_tick(self, served):
        _, _, reqs, _, _ = served
        # the shuffled stream really does put several tenants in one batch
        assert len({r.tenant_id for r in reqs[:SLOTS]}) > 1


class TestCascadeAccuracy:
    def test_escalation_recovers_low_margin_requests(self):
        """With noisy queries the cascade (ACAM + CNN head on low-margin)
        must be at least as accurate as ACAM alone."""
        svc, _, protos = _make_service(margin_tau=10.0)
        reqs, truth = _mixed_requests(protos, per_tenant=16, noise=1.2,
                                      seed=11)
        responses = svc.serve(reqs)
        acc = np.mean([r.pred == y for r, y in zip(responses, truth)])

        svc2, _, _ = _make_service(margin_tau=-1.0)  # never escalate
        responses2 = svc2.serve(reqs)
        acc2 = np.mean([r.pred == y for r, y in zip(responses2, truth)])
        assert acc >= acc2
        assert acc > 0.5


class TestAdmission:
    def test_unknown_tenant_and_bad_shape(self):
        svc, _, protos = _make_service()
        feats = np.zeros(N_FEATURES, np.float32)
        with pytest.raises(AdmissionError):
            svc.submit(ClassifyRequest("nope", feats))
        with pytest.raises(AdmissionError):
            svc.submit(ClassifyRequest("tenant-0", np.zeros(3, np.float32)))
        assert svc.metrics()["rejected"] == 2

    def test_queue_bound(self):
        svc, _, protos = _make_service(max_queue=4)
        feats = np.zeros(N_FEATURES, np.float32)
        for _ in range(4):
            svc.submit(ClassifyRequest("tenant-0", feats))
        with pytest.raises(AdmissionError, match="queue full"):
            svc.submit(ClassifyRequest("tenant-0", feats))


class TestInFlightLifecycle:
    """Hot tenant churn with requests already queued (the scheduler must
    resolve placements at tick time, not submit time)."""

    def test_evict_while_queued_yields_error_response(self):
        svc, _, protos = _make_service(slots=4)
        feats, _ = svc_lib.sample_tenant_queries(1, protos["tenant-0"], 3)
        for i in range(3):
            svc.submit(ClassifyRequest("tenant-0", feats[i]))
        svc.submit(ClassifyRequest("tenant-1",
                                   svc_lib.sample_tenant_queries(
                                       2, protos["tenant-1"], 1)[0][0]))
        svc.evict_tenant("tenant-0")
        responses = []
        while svc.scheduler.qsize:
            responses.extend(svc.step())
        assert len(responses) == 4
        dead = [r for r in responses if r.tenant_id == "tenant-0"]
        live = [r for r in responses if r.tenant_id == "tenant-1"]
        assert all(r.error is not None and r.pred == -1 for r in dead)
        assert all(r.error is None and r.pred >= 0 for r in live)
        assert svc.metrics()["failed"] == 3

    def test_update_relocation_while_queued_uses_new_window(self):
        svc = ACAMService(N_FEATURES,
                          config=ServiceConfig(slots=4, margin_tau=-1.0),
                          class_bucket=8)
        small, head_s, p_small = svc_lib.make_synthetic_tenant(
            30, num_classes=6, num_features=N_FEATURES)
        blocker, head_b, _ = svc_lib.make_synthetic_tenant(
            31, num_classes=6, num_features=N_FEATURES)
        svc.register_tenant("a", small, head=head_s)
        svc.register_tenant("blocker", blocker, head=head_b)

        big, head_big, p_big = svc_lib.make_synthetic_tenant(
            32, num_classes=12, num_features=N_FEATURES)
        feats, labels = svc_lib.sample_tenant_queries(3, p_big, 4, noise=0.3)
        for i in range(4):
            svc.submit(ClassifyRequest("a", feats[i]))
        # relocates "a" (bucket 8 -> 16, blocker occupies the next bucket)
        svc.update_tenant("a", big, head=head_big)
        assert svc.registry.get("a").offset != 0 or \
            svc.registry.get("a").c_bucket == 16
        responses = []
        while svc.scheduler.qsize:
            responses.extend(svc.step())
        # served against the NEW 12-class placement, not the stale window
        assert [r.pred for r in responses] == [int(y) for y in labels]
        assert all(r.error is None for r in responses)

    def test_bad_head_rejects_without_registry_mutation(self):
        svc, _, _ = _make_service()
        bank, _, _ = svc_lib.make_synthetic_tenant(
            40, num_classes=4, num_features=N_FEATURES)
        bad_head = (np.zeros((N_FEATURES * 2, 4), np.float32),
                    np.zeros((4,), np.float32))
        with pytest.raises(RegistryError):
            svc.register_tenant("new", bank, head=bad_head)
        assert "new" not in svc.registry  # nothing half-installed
        good_head = (np.zeros((N_FEATURES, 4), np.float32),
                     np.zeros((4,), np.float32))
        svc.register_tenant("new", bank, head=good_head)  # retry works
        feats = np.zeros(N_FEATURES, np.float32)
        svc.submit(ClassifyRequest("new", feats))
        assert all(r.error is None for r in svc.step())


class TestRegistryHotOps:
    def test_register_update_evict_keep_device_shapes(self):
        reg = TemplateBankRegistry(N_FEATURES, k_max=2, class_bucket=8,
                                   initial_classes=64)
        bank0, _, _ = svc_lib.make_synthetic_tenant(
            1, num_classes=N_CLASSES, num_features=N_FEATURES)
        e0 = reg.register("a", bank0)
        shape0 = reg.device_bank().templates.shape
        thr0 = reg.thresholds_table().shape

        bank1, _, _ = svc_lib.make_synthetic_tenant(
            2, num_classes=4, k=2, num_features=N_FEATURES)
        reg.register("b", bank1)
        reg.update("a", bank0)
        reg.evict("b")
        assert reg.device_bank().templates.shape == shape0
        assert reg.thresholds_table().shape == thr0
        # freed range is reused (no capacity growth on re-register)
        e2 = reg.register("c", bank1)
        assert reg.device_bank().templates.shape == shape0
        assert e2.offset != e0.offset or "a" not in reg

    def test_device_bank_cached_per_generation(self):
        reg = TemplateBankRegistry(N_FEATURES)
        bank, _, _ = svc_lib.make_synthetic_tenant(
            3, num_classes=N_CLASSES, num_features=N_FEATURES)
        reg.register("a", bank)
        b1 = reg.device_bank()
        assert reg.device_bank() is b1  # cache hit: no re-upload per tick
        reg.update("a", bank)
        assert reg.device_bank() is not b1  # mutation invalidates

    def test_window_and_contents(self):
        reg = TemplateBankRegistry(N_FEATURES, class_bucket=8)
        bank, _, _ = svc_lib.make_synthetic_tenant(
            4, num_classes=N_CLASSES, num_features=N_FEATURES)
        entry = reg.register("a", bank)
        lo, hi = entry.window
        assert hi - lo == N_CLASSES
        sb = reg.device_bank()
        np.testing.assert_array_equal(
            np.asarray(sb.templates[lo:hi, :entry.k]),
            np.asarray(bank.templates))
        np.testing.assert_array_equal(
            np.asarray(sb.valid[lo:hi, :entry.k]), np.asarray(bank.valid))

    def test_registry_errors(self):
        reg = TemplateBankRegistry(N_FEATURES, k_max=1)
        bank, _, _ = svc_lib.make_synthetic_tenant(
            5, num_classes=N_CLASSES, num_features=N_FEATURES)
        reg.register("a", bank)
        with pytest.raises(RegistryError):
            reg.register("a", bank)  # duplicate
        with pytest.raises(RegistryError):
            reg.get("ghost")
        bank_k2, _, _ = svc_lib.make_synthetic_tenant(
            6, num_classes=N_CLASSES, k=2, num_features=N_FEATURES)
        with pytest.raises(RegistryError):
            reg.register("b", bank_k2)  # k exceeds k_max
        bank_n, _, _ = svc_lib.make_synthetic_tenant(
            7, num_classes=N_CLASSES, num_features=N_FEATURES * 2)
        with pytest.raises(RegistryError):
            reg.register("c", bank_n)  # wrong feature dim

    def test_update_relocates_and_invalidates_old_range(self):
        reg = TemplateBankRegistry(N_FEATURES, class_bucket=8,
                                   initial_classes=64)
        small, _, _ = svc_lib.make_synthetic_tenant(
            20, num_classes=6, num_features=N_FEATURES)
        big, _, _ = svc_lib.make_synthetic_tenant(
            21, num_classes=12, num_features=N_FEATURES)
        reg.register("a", small)
        # a neighbour occupies the adjacent bucket so "a" cannot grow in
        # place and must relocate
        reg.register("b", small)
        e_old = reg.get("a")
        reg.update("a", big)
        e_new = reg.get("a")
        assert e_new.num_classes == 12 and e_new.c_bucket == 16
        assert e_new.offset != e_old.offset
        # the vacated range holds no stale valid rows
        sb = reg.device_bank()
        old_rows = np.asarray(
            sb.valid[e_old.offset:e_old.offset + e_old.c_bucket])
        assert not old_rows.any()
        np.testing.assert_array_equal(
            np.asarray(sb.templates[e_new.offset:e_new.offset + 12,
                                    :e_new.k]),
            np.asarray(big.templates))
        assert reg.stats()["programmed_rows"] == 12 + 6  # "a" big + "b"

    def test_capacity_growth_by_doubling(self):
        reg = TemplateBankRegistry(N_FEATURES, class_bucket=16,
                                   initial_classes=32)
        for t in range(4):  # 4 x 16-row buckets > 32 rows -> one grow
            bank, _, _ = svc_lib.make_synthetic_tenant(
                10 + t, num_classes=10, num_features=N_FEATURES)
            reg.register(f"t{t}", bank)
        assert reg.capacity_classes == 64
        assert len(reg) == 4


class TestMarginsKernelParity:
    @pytest.mark.parametrize("b,c,k,n", [(3, 5, 2, 784), (37, 10, 2, 300),
                                         (257, 10, 1, 784)])
    def test_fused_margins_matches_oracle(self, b, c, k, n):
        import jax

        from repro.core.templates import TemplateBank

        key = jax.random.PRNGKey(b + c)
        tmpl = (jax.random.uniform(key, (c, k, n)) > 0.5).astype(jnp.float32)
        valid = jnp.ones((c, k), bool)
        if k > 1:
            valid = valid.at[0, k - 1].set(False)
        thr = jax.random.normal(jax.random.fold_in(key, 1), (n,)) * 0.1
        bank = TemplateBank(tmpl, jnp.zeros_like(tmpl), jnp.ones_like(tmpl),
                            valid, thr)
        feats = jax.random.normal(jax.random.fold_in(key, 2), (b, n))
        rng = np.random.RandomState(b)
        lo = rng.randint(0, c, size=b).astype(np.int32)
        hi = np.minimum(lo + rng.randint(1, c, size=b), c).astype(np.int32)
        lo[0], hi[0] = 0, 0  # an empty (padding) window

        pred_k, pc_k, m_k = matching.classify_features_margin(
            feats, bank, jnp.asarray(lo), jnp.asarray(hi), backend="kernel")
        _, pc_r = matching.classify_features(feats, bank,
                                             backend="reference")
        pred_r, m_r = matching.window_margin(pc_r, jnp.asarray(lo),
                                             jnp.asarray(hi), cap=float(n))
        np.testing.assert_array_equal(np.asarray(pred_k), np.asarray(pred_r))
        np.testing.assert_allclose(np.asarray(pc_k), np.asarray(pc_r),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(m_k), np.asarray(m_r),
                                   rtol=1e-5, atol=1e-5)
        assert float(m_k[0]) == 0.0  # empty window -> margin 0

    def test_single_class_window_margin_clamped(self):
        import jax

        from repro.core.templates import TemplateBank

        c, k, n = 4, 1, 128
        key = jax.random.PRNGKey(0)
        tmpl = (jax.random.uniform(key, (c, k, n)) > 0.5).astype(jnp.float32)
        bank = TemplateBank(tmpl, jnp.zeros_like(tmpl), jnp.ones_like(tmpl),
                            jnp.ones((c, k), bool), jnp.zeros((n,)))
        feats = jax.random.normal(key, (4, n))
        lo = jnp.array([1, 0, 0, 0], jnp.int32)
        hi = jnp.array([2, 4, 4, 4], jnp.int32)  # row 0: single-class window
        _, _, margin = matching.classify_features_margin(
            feats, bank, lo, hi, backend="kernel")
        assert float(margin[0]) == pytest.approx(float(n))  # clamped to cap
        assert np.all(np.isfinite(np.asarray(margin)))


class TestSchedulerOccupancy:
    def test_fill_stats_observable(self):
        svc, _, protos = _make_service(slots=8)
        tid = "tenant-0"
        feats, _ = svc_lib.sample_tenant_queries(1, protos[tid], 11)
        for i in range(11):  # 11 requests over 8 slots -> fills 8 + 3
            svc.submit(ClassifyRequest(tid, feats[i]))
        while svc.scheduler.qsize:
            svc.step()
        s = svc.scheduler.stats
        assert s.ticks == 2
        assert s.min_fill == 3 and s.max_fill == 8
        assert s.occupancy == pytest.approx(11 / 16)
