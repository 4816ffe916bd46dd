"""Unit tests for the paper's core modules (Eq. 1-12, §II, §V-D)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_fallback import given, settings, st

from repro.core import acam, distill, energy, matching, prune, quant, templates


# ---------------------------------------------------------------------------
# distillation (Eq. 1-4)
# ---------------------------------------------------------------------------

class TestDistill:
    def test_kd_loss_zero_for_identical_logits(self):
        z = jax.random.normal(jax.random.PRNGKey(0), (8, 10))
        assert float(distill.kd_loss(z, z, 4.0)) == pytest.approx(0.0, abs=1e-5)

    def test_kd_loss_nonnegative(self):
        k1, k2 = jax.random.split(jax.random.PRNGKey(1))
        zs = jax.random.normal(k1, (16, 10)) * 3
        zt = jax.random.normal(k2, (16, 10)) * 3
        assert float(distill.kd_loss(zs, zt, 2.0)) >= 0.0

    @given(st.floats(1.0, 10.0))
    @settings(max_examples=10, deadline=None)
    def test_t_squared_scaling_keeps_gradient_magnitude(self, t):
        """Eq. 2's T^2 factor: gradients stay O(1) across temperatures."""
        zs = jnp.array([[1.0, -1.0, 0.5, 2.0]])
        zt = jnp.array([[2.0, 0.0, -1.0, 1.0]])
        g = jax.grad(lambda z: distill.kd_loss(z, zt, t))(zs)
        assert 1e-3 < float(jnp.max(jnp.abs(g))) < 10.0

    def test_composite_loss_endpoints(self):
        """Eq. 1: alpha=0 -> pure CE; alpha=1 -> pure KD."""
        k1, k2 = jax.random.split(jax.random.PRNGKey(2))
        zs = jax.random.normal(k1, (4, 10))
        zt = jax.random.normal(k2, (4, 10))
        y = jnp.arange(4)
        l0 = distill.distillation_loss(zs, zt, y, alpha=0.0, temperature=3.0)
        assert float(l0) == pytest.approx(float(distill.cross_entropy(zs, y)), rel=1e-6)
        l1 = distill.distillation_loss(zs, zt, y, alpha=1.0, temperature=3.0)
        assert float(l1) == pytest.approx(float(distill.kd_loss(zs, zt, 3.0)), rel=1e-6)

    def test_curriculum_orders_easy_to_hard(self):
        """Eq. 4: the teacher-confident sample must sort first."""
        zt = jnp.array([[10.0, -10.0], [0.1, 0.0], [-10.0, 10.0]])
        y = jnp.array([0, 0, 0])  # sample 0 easy, 2 hardest
        order = distill.curriculum_order(zt, y)
        assert list(np.asarray(order)) == [0, 1, 2]

    def test_pacing_schedule_monotone(self):
        sched = distill.CurriculumSchedule(0.3, 5)
        avail = [sched.available(e, 1000) for e in range(7)]
        assert avail[0] == 300 and avail[-1] == 1000
        assert all(a <= b for a, b in zip(avail, avail[1:]))


# ---------------------------------------------------------------------------
# pruning (Eq. 5-7)
# ---------------------------------------------------------------------------

class TestPrune:
    def test_schedule_endpoints(self):
        assert float(prune.polynomial_sparsity(0, 100)) == pytest.approx(0.5)
        assert float(prune.polynomial_sparsity(100, 100)) == pytest.approx(0.8)

    @given(st.integers(1, 99))
    @settings(max_examples=20, deadline=None)
    def test_schedule_monotone_in_bounds(self, t):
        s = float(prune.polynomial_sparsity(t, 100))
        s_next = float(prune.polynomial_sparsity(t + 1, 100))
        # the schedule is float32: at t = n_t it returns float32(0.8)
        assert 0.5 <= s <= s_next <= float(np.float32(0.8))

    def test_prune_achieves_sparsity(self):
        w = {"a": jax.random.normal(jax.random.PRNGKey(0), (64, 64))}
        pruned, masks = prune.prune_tree(w, 0.8)
        assert prune.sparsity_of(pruned) == pytest.approx(0.8, abs=0.01)

    def test_biases_untouched(self):
        tree = {"w": jax.random.normal(jax.random.PRNGKey(0), (32, 32)),
                "b": jnp.ones((32,))}
        pruned, _ = prune.prune_tree(tree, 0.9)
        assert bool(jnp.all(pruned["b"] == 1.0))

    def test_masks_persistent_under_gradients(self):
        w = {"a": jax.random.normal(jax.random.PRNGKey(0), (32, 32))}
        pruned, masks = prune.prune_tree(w, 0.7)
        g = {"a": jnp.ones((32, 32))}
        g = prune.mask_gradients(g, masks)
        stepped = jax.tree_util.tree_map(lambda p, gg: p - 0.1 * gg, pruned, g)
        stepped = prune.apply_masks(stepped, masks)
        assert prune.sparsity_of(stepped) >= 0.69

    def test_global_vs_per_tensor_ranking(self):
        tree = {"small": jnp.full((16, 16), 0.01),
                "big": jnp.full((16, 16), 1.0)}
        pruned_g, _ = prune.prune_tree(tree, 0.5, global_ranking=True)
        # global ranking kills the uniformly-small tensor first
        assert float(jnp.sum(pruned_g["small"] != 0)) == 0.0
        assert float(jnp.sum(pruned_g["big"] != 0)) == 256.0

    @given(st.integers(2, 40), st.integers(2, 40))
    @settings(max_examples=15, deadline=None)
    def test_sparse_roundtrip(self, h, w_):
        w = jax.random.normal(jax.random.PRNGKey(h * w_), (h, w_))
        pruned, _ = prune.prune_tree({"w": w}, 0.6)
        s = prune.to_sparse(pruned["w"])
        assert bool(jnp.allclose(prune.from_sparse(s), pruned["w"]))


# ---------------------------------------------------------------------------
# quantisation (§II-C)
# ---------------------------------------------------------------------------

class TestQuant:
    @given(st.integers(0, 1000))
    @settings(max_examples=20, deadline=None)
    def test_int8_error_bound(self, seed):
        w = jax.random.normal(jax.random.PRNGKey(seed), (32, 32))
        q = quant.fake_quant_int8(w)
        scale = float(jnp.max(jnp.abs(w))) / 127.0
        assert float(jnp.max(jnp.abs(q - w))) <= scale * 0.5 + 1e-7

    def test_ste_gradient_is_identity(self):
        w = jax.random.normal(jax.random.PRNGKey(0), (8, 8))
        g = jax.grad(lambda x: jnp.sum(quant.fake_quant_int8(x) * 2.0))(w)
        assert bool(jnp.allclose(g, 2.0))

    def test_mean_below_median_for_relu_sparse(self):
        """Fig. 1's premise: sparse ReLU features push the mean below the
        median-of-nonzeros... and below the median when >50% are zero."""
        x = jax.nn.relu(jax.random.normal(jax.random.PRNGKey(0), (4000, 16)) - 0.8)
        mean_t = quant.feature_thresholds(x, "mean")
        med_t = quant.feature_thresholds(x, "median")
        assert bool(jnp.all(mean_t >= med_t))  # median is 0, mean positive
        # and the mean keeps low-magnitude activations discriminative:
        binz = quant.binarize(x, mean_t)
        assert 0.0 < float(binz.mean()) < 0.5

    def test_binarize_output_binary(self):
        x = jax.random.normal(jax.random.PRNGKey(1), (64, 32))
        b, thr = quant.binarize_with_stats(x, "mean")
        assert set(np.unique(np.asarray(b))) <= {0.0, 1.0}


# ---------------------------------------------------------------------------
# templates + matching (Eq. 8-12, §II-D)
# ---------------------------------------------------------------------------

def _clustered_features(key, n_per=40, classes=4, dim=32, spread=0.3):
    centers = jax.random.normal(key, (classes, dim)) * 2.0
    feats, labels = [], []
    for c in range(classes):
        k = jax.random.fold_in(key, c)
        feats.append(centers[c] + spread * jax.random.normal(k, (n_per, dim)))
        labels += [c] * n_per
    return jnp.concatenate(feats), jnp.asarray(labels)


class TestTemplates:
    def test_kmeans_partitions(self):
        x, _ = _clustered_features(jax.random.PRNGKey(0), classes=3)
        cents, assign = templates.kmeans(x, 3)
        assert cents.shape == (3, 32)
        assert len(set(np.asarray(assign).tolist())) == 3

    @given(st.integers(2, 4))
    @settings(max_examples=8, deadline=None)
    def test_silhouette_range(self, k):
        x, _ = _clustered_features(jax.random.PRNGKey(k), n_per=20, classes=k)
        _, assign = templates.kmeans(x, k)
        s = float(templates.silhouette_score(x, assign, k))
        assert -1.0 <= s <= 1.0

    def test_template_bank_shapes_valid(self):
        x, y = _clustered_features(jax.random.PRNGKey(2))
        bank = templates.generate_templates(x, y, 4, k=2)
        assert bank.templates.shape == (4, 2, 32)
        assert bool(jnp.all(bank.valid))
        assert bool(jnp.all(bank.upper >= bank.lower))
        vals = np.unique(np.asarray(bank.templates))
        assert set(vals.tolist()) <= {0.0, 1.0}

    def test_matching_classifies_clustered_data(self):
        x, y = _clustered_features(jax.random.PRNGKey(3))
        bank = templates.generate_templates(x, y, 4, k=1)
        q = quant.binarize(x, bank.thresholds)
        pred_fc, _ = matching.classify(q, bank, method="feature_count")
        pred_s, _ = matching.classify(q, bank, method="similarity")
        assert float(jnp.mean(pred_fc == y)) > 0.9
        assert float(jnp.mean(pred_s == y)) > 0.9

    def test_binary_convergence_of_fc_and_similarity(self):
        """Paper §V-B: in the fully-binary regime both matching models give
        identical decisions."""
        x, y = _clustered_features(jax.random.PRNGKey(4), classes=5)
        bank = templates.generate_templates(x, y, 5, k=1, binary_windows=True)
        # windows collapsed to the point template => same ranking
        bank = bank._replace(lower=bank.templates, upper=bank.templates)
        q = quant.binarize(x, bank.thresholds)
        pred_fc, _ = matching.classify(q, bank, method="feature_count")
        pred_s, _ = matching.classify(q, bank, method="similarity")
        assert bool(jnp.all(pred_fc == pred_s))

    def test_multi_template_max_pool(self):
        scores = jnp.asarray([[[1.0, 5.0], [3.0, 2.0]]])  # (B=1, C=2, K=2)
        pred, per_class = matching.classify_scores(scores)
        assert per_class.tolist() == [[5.0, 3.0]]
        assert int(pred[0]) == 0

    def test_select_k_by_silhouette(self):
        x, y = _clustered_features(jax.random.PRNGKey(5), n_per=30)
        best, scores = templates.select_k_by_silhouette(x, y, 4, (1, 2))
        assert best in (1, 2) and set(scores) == {1, 2}


# ---------------------------------------------------------------------------
# ACAM device models (§III)
# ---------------------------------------------------------------------------

class TestACAMDevice:
    def _bank(self, key):
        x, y = _clustered_features(key)
        bank = templates.generate_templates(x, y, 4, k=1)
        q = quant.binarize(x, bank.thresholds)
        return bank, q, y

    @pytest.mark.parametrize("cell", ["6T4R", "3T1R"])
    def test_sense_matches_ideal_ranking(self, cell):
        bank, q, y = self._bank(jax.random.PRNGKey(0))
        cfg = acam.ACAMConfig(cell=cell)
        arr = acam.program(bank.templates.reshape(4, 32),
                           bank.templates.reshape(4, 32),
                           bank.valid.reshape(4), cfg)
        winner = acam.wta(acam.sense(arr, q))
        acc = float(jnp.mean(winner == y))
        assert acc > 0.9

    def test_matchline_voltage_saturates(self):
        cfg = acam.ACAMConfig()
        arr = acam.program(jnp.zeros((2, 64)), jnp.ones((2, 64)),
                           jnp.ones(2, bool), cfg)
        v = acam.matchline_voltage(arr, jnp.full((1, 64), 0.5))
        assert float(v.max()) <= cfg.vdd + 1e-9

    def test_programming_noise_changes_windows(self):
        cfg = acam.ACAMConfig(sigma_program=0.3)
        lo, hi = jnp.full((4, 16), 0.4), jnp.full((4, 16), 0.6)
        arr = acam.program(lo, hi, jnp.ones(4, bool), cfg,
                           key=jax.random.PRNGKey(0))
        assert not bool(jnp.allclose(arr.lower, lo))
        assert bool(jnp.all(arr.upper >= arr.lower))

    def test_soft_sense_differentiable_and_close_to_hard(self):
        bank, q, _ = self._bank(jax.random.PRNGKey(1))
        cfg = acam.ACAMConfig(cell="3T1R", beta=50.0)
        arr = acam.program(bank.lower.reshape(4, 32), bank.upper.reshape(4, 32),
                           bank.valid.reshape(4), cfg)
        hard = acam.sense(arr, q[:16])
        soft = acam.soft_sense(arr, q[:16])
        assert bool(jnp.all(jnp.argmax(hard, -1) == jnp.argmax(soft, -1)))
        g = jax.grad(lambda lo: acam.soft_sense(
            arr._replace(lower=lo), q[:16]).sum())(arr.lower)
        assert float(jnp.max(jnp.abs(g))) > 0.0

    def test_calibration_improves_separation(self):
        bank, q, y = self._bank(jax.random.PRNGKey(2))
        cfg = acam.ACAMConfig(cell="3T1R", sigma_program=0.5)
        arr = acam.program(bank.lower.reshape(4, 32), bank.upper.reshape(4, 32),
                           bank.valid.reshape(4), cfg, key=jax.random.PRNGKey(3))
        acc0 = float(jnp.mean(acam.wta(acam.sense(arr, q)) == y))
        cal = acam.calibrate_windows(arr, q, y.astype(jnp.int32), steps=60)
        acc1 = float(jnp.mean(acam.wta(acam.sense(cal, q)) == y))
        assert acc1 >= acc0

    def test_search_energy_matches_eq14(self):
        cfg = acam.ACAMConfig()
        arr = acam.program(jnp.zeros((10, 784)), jnp.ones((10, 784)),
                           jnp.ones(10, bool), cfg)
        assert float(acam.search_energy(arr)) == pytest.approx(1.4504e-9, rel=1e-3)


# ---------------------------------------------------------------------------
# energy model (§V-D)
# ---------------------------------------------------------------------------

class TestEnergy:
    def test_paper_numbers(self):
        """§V-D regression: the printed constants, in paper_faithful mode."""
        n = energy.paper_numbers()
        assert n["backend_nj"] == pytest.approx(1.45, abs=0.01)  # Eq. 14
        assert n["frontend_nj"] == pytest.approx(96.07, abs=0.05)
        assert n["total_nj"] == pytest.approx(97.52, abs=0.05)
        assert n["teacher_uj"] == pytest.approx(78.06, abs=0.05)
        # the paper prints ~792x; the exact arithmetic lands at ~800x
        assert n["reduction_x"] == pytest.approx(792, rel=0.02)

    def test_effective_ops_arithmetic(self):
        """effective = MACs * (1 - sparsity) - softmax head ops, and both
        the front-end and teacher charge the same 20.23 fJ/op figure."""
        rep = energy.hybrid_report(paper_faithful=True)
        per_op = energy.per_op_energy(bits=8, paper_faithful=True)
        assert per_op == pytest.approx(20.23e-15, rel=1e-3)
        effective = round(23_785_120 * 0.2) - 7_850
        assert rep.frontend_j == pytest.approx(effective * per_op, rel=1e-9)
        assert rep.teacher_j == pytest.approx(3_858_551_808 * per_op,
                                              rel=1e-9)

    def test_physical_vs_paper_units(self):
        """The recorded unit slip: the paper applied Horowitz pJ as fJ."""
        assert energy.PAPER_UNIT_SLIP == pytest.approx(1e-3)
        # exactly 1000x per op, for both op widths
        for bits in (8, 32):
            assert energy.per_op_energy(bits=bits, paper_faithful=False) \
                == pytest.approx(
                    1000 * energy.per_op_energy(bits=bits,
                                                paper_faithful=True))
        rep_paper = energy.hybrid_report(paper_faithful=True)
        rep_phys = energy.hybrid_report(paper_faithful=False)
        assert rep_phys.frontend_j == pytest.approx(
            rep_paper.frontend_j * 1000, rel=1e-6)
        assert rep_phys.teacher_j == pytest.approx(
            rep_paper.teacher_j * 1000, rel=1e-6)
        # Eq. 14 is physically consistent as printed: no slip on the ACAM
        assert rep_phys.backend_j == rep_paper.backend_j
        # the headline reduction is nearly unit-independent (the fixed 1.45nJ
        # ACAM term weighs less against the 1000x larger physical front-end)
        assert rep_phys.reduction == pytest.approx(rep_paper.reduction, rel=0.05)
