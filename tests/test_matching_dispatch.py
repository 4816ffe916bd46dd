"""Kernel/reference parity for the matching dispatch layer (PR 1).

Property-style sweeps (chex variants, à la the SNIPPETS.md pattern) asserting
the Pallas kernels (interpret mode on this CPU container) match the pure-jnp
references on non-block-multiple shapes — exercising the padded-column
corrections, `valid` masking, and the fused binarize->match->WTA epilogue —
plus coverage for the backend dispatch API and the block autotuner cache.
"""
import chex
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import matching, quant
from repro.core.templates import TemplateBank
from repro.kernels import tuning
from repro.kernels.acam_match import ops as match_ops
from repro.kernels.acam_similarity import ops as sim_ops
from repro.match import MAX_FUSED_ROWS


def _bank(key, c, k, n, *, invalidate_some=True) -> TemplateBank:
    tmpl = (jax.random.uniform(key, (c, k, n)) > 0.5).astype(jnp.float32)
    lo = (jax.random.uniform(jax.random.fold_in(key, 1), (c, k, n)) > 0.6
          ).astype(jnp.float32)
    hi = jnp.maximum(lo, (jax.random.uniform(jax.random.fold_in(key, 2),
                                             (c, k, n)) > 0.4
                          ).astype(jnp.float32))
    valid = jnp.ones((c, k), bool)
    if invalidate_some and k > 1:
        valid = valid.at[0, k - 1].set(False).at[c - 1, 0].set(False)
    thr = jax.random.normal(jax.random.fold_in(key, 3), (n,)) * 0.1
    return TemplateBank(tmpl, lo, hi, valid, thr)


# the paper's deployment geometry (N=784 forces padded feature columns:
# neither 784 nor the ragged batches are block multiples)
PARITY_SHAPES = [(1, 5, 2, 784), (3, 5, 2, 784), (257, 5, 2, 784),
                 (9, 10, 1, 300), (33, 10, 3, 784)]


class TestFeatureCountParity:
    @pytest.mark.parametrize("b,c,k,n", PARITY_SHAPES)
    def test_scores_exact(self, b, c, k, n):
        key = jax.random.PRNGKey(b * n + c)
        bank = _bank(key, c, k, n)
        q = (jax.random.uniform(jax.random.fold_in(key, 4), (b, n)) > 0.5
             ).astype(jnp.float32)
        got = matching.feature_count_scores(q, bank.templates, bank.valid,
                                            backend="kernel")
        want = matching.feature_count_scores_ref(q, bank.templates, bank.valid)
        # bipolar-matmul identity is integer-exact: bit-for-bit equality,
        # including the -inf rows from `valid` masking
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_bool_queries(self):
        # bool arrays must binarise through a float 0.5 threshold, not a
        # bool-dtype one (True), which would zero every query bit
        key = jax.random.PRNGKey(2)
        bank = _bank(key, 5, 2, 784, invalidate_some=False)
        q = jax.random.uniform(jax.random.fold_in(key, 4), (9, 784)) > 0.5
        got = matching.feature_count_scores(q.astype(bool),
                                            bank.templates.astype(bool),
                                            backend="kernel")
        want = matching.feature_count_scores_ref(q.astype(jnp.float32),
                                                 bank.templates)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_no_valid_mask(self):
        key = jax.random.PRNGKey(0)
        bank = _bank(key, 4, 2, 96, invalidate_some=False)
        q = (jax.random.uniform(key, (17, 96)) > 0.5).astype(jnp.float32)
        got = matching.feature_count_scores(q, bank.templates,
                                            backend="kernel")
        want = matching.feature_count_scores_ref(q, bank.templates)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


class TestSimilarityParity:
    @pytest.mark.parametrize("b,c,k,n", PARITY_SHAPES)
    def test_scores_close(self, b, c, k, n):
        key = jax.random.PRNGKey(b + c * n)
        bank = _bank(key, c, k, n)
        q = jax.random.uniform(jax.random.fold_in(key, 4), (b, n))
        got = matching.similarity_scores(q, bank.lower, bank.upper,
                                         bank.valid, alpha=0.7,
                                         backend="kernel")
        want = matching.similarity_scores_ref(q, bank.lower, bank.upper,
                                              bank.valid, alpha=0.7)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)


class TestFusedClassify(chex.TestCase):
    # chex.TestCase is absltest-based: sweep methods in-test rather than via
    # pytest.mark.parametrize (which doesn't compose with variants)
    @chex.variants(with_jit=True, without_jit=True)
    def test_classify_features_parity(self):
        key = jax.random.PRNGKey(11)
        bank = _bank(key, 10, 2, 784)
        feats = jax.random.normal(jax.random.fold_in(key, 5), (37, 784))

        for method in ("feature_count", "similarity"):
            fn = self.variant(
                lambda f, m=method: matching.classify_features(
                    f, bank, method=m, backend="kernel"))
            pred_k, pc_k = fn(feats)
            pred_r, pc_r = matching.classify_features(
                feats, bank, method=method, backend="reference")
            np.testing.assert_array_equal(np.asarray(pred_k),
                                          np.asarray(pred_r))
            np.testing.assert_allclose(np.asarray(pc_k), np.asarray(pc_r),
                                       rtol=1e-5, atol=1e-6)


class TestClassifyBinaryQueries:
    @pytest.mark.parametrize("b", [1, 3, 257])
    @pytest.mark.parametrize("method", ["feature_count", "similarity"])
    def test_classify_binary_queries(self, b, method):
        key = jax.random.PRNGKey(b)
        bank = _bank(key, 10, 2, 784)
        feats = jax.random.normal(jax.random.fold_in(key, 5), (b, 784))
        q = quant.binarize(feats, bank.thresholds)
        pred_k, pc_k = matching.classify(q, bank, method=method,
                                         backend="kernel")
        pred_r, pc_r = matching.classify(q, bank, method=method,
                                         backend="reference")
        np.testing.assert_array_equal(np.asarray(pred_k), np.asarray(pred_r))
        np.testing.assert_allclose(np.asarray(pc_k), np.asarray(pc_r),
                                   rtol=1e-5, atol=1e-6)


class TestFusedOpsDirect:
    def test_fused_ops_direct(self):
        """Both methods' single-tenant serve-kernel faces == reference."""
        key = jax.random.PRNGKey(3)
        c, k, n = 6, 3, 300
        bank = _bank(key, c, k, n)
        feats = jax.random.normal(jax.random.fold_in(key, 9), (29, n))
        q = quant.binarize(feats, bank.thresholds)

        pred_f, pc_f, _ = match_ops.classify_fused_margins(
            feats, bank.thresholds, bank.templates, bank.valid,
            max_rows=MAX_FUSED_ROWS)
        pred_r, pc_r = matching.classify(q, bank, method="feature_count",
                                         backend="reference")
        np.testing.assert_array_equal(np.asarray(pred_f), np.asarray(pred_r))
        np.testing.assert_allclose(np.asarray(pc_f), np.asarray(pc_r), atol=0)

        pred_s, pc_s, _ = sim_ops.classify_fused_margins(
            feats, bank.thresholds, bank.lower, bank.upper, bank.valid,
            alpha=1.0, max_rows=MAX_FUSED_ROWS)
        pred_r, pc_r = matching.classify(q, bank, method="similarity",
                                         backend="reference")
        np.testing.assert_array_equal(np.asarray(pred_s), np.asarray(pred_r))
        np.testing.assert_allclose(np.asarray(pc_s), np.asarray(pc_r),
                                   rtol=1e-5, atol=1e-6)


class TestBackendDispatch:
    def test_set_get_roundtrip(self):
        old = matching.get_backend()
        try:
            for b in ("kernel", "reference", "auto"):
                matching.set_backend(b)
                assert matching.get_backend() == b
            with pytest.raises(ValueError):
                matching.set_backend("cuda")
        finally:
            matching.set_backend(old)

    def test_auto_tiny_uses_reference_semantics(self):
        # below TINY_ELEMENTS auto == reference; above, auto == kernel;
        # either way results agree, which is what deployments observe.
        key = jax.random.PRNGKey(1)
        bank = _bank(key, 4, 1, 32, invalidate_some=False)
        q = (jax.random.uniform(key, (2, 32)) > 0.5).astype(jnp.float32)
        assert 2 * 4 * 1 * 32 < matching.TINY_ELEMENTS
        got = matching.feature_count_scores(q, bank.templates, backend="auto")
        want = matching.feature_count_scores_ref(q, bank.templates)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_invalid_backend_kw(self):
        key = jax.random.PRNGKey(1)
        bank = _bank(key, 4, 1, 32, invalidate_some=False)
        q = jnp.zeros((2, 32))
        with pytest.raises(ValueError):
            matching.feature_count_scores(q, bank.templates, backend="gpuuu")


class TestTuning:
    def test_cache_roundtrip(self, tmp_path, monkeypatch):
        monkeypatch.setenv("REPRO_TUNING_CACHE", str(tmp_path / "blocks.json"))
        tuning.clear_cache_for_tests()
        try:
            shape = (64, 10, 784)
            assert tuning.get_block("acam_match", shape, jnp.float32) == \
                tuning.default_block("acam_match")

            calls = []

            def run(block):
                calls.append(block)
                return jnp.zeros((4, 4))

            best = tuning.autotune("acam_match", shape, jnp.float32, run,
                                   cands=[(128, 128, 256), (128, 128, 512)],
                                   iters=1)
            assert best in calls
            tuning.clear_cache_for_tests()
            assert tuning.get_block("acam_match", shape, jnp.float32) == best
            # other shapes still fall back to the default
            assert tuning.get_block("acam_match", (8, 8, 8), jnp.float32) == \
                tuning.default_block("acam_match")
        finally:
            tuning.clear_cache_for_tests()

    def test_candidates_aligned(self):
        for kernel in ("acam_match", "acam_similarity"):
            cands = tuning.candidates(kernel)
            assert cands, kernel
            for bm, bn, bk in cands:
                assert bn % 128 == 0 and bk % 128 == 0
                assert bm % 8 == 0 or bm < 8

    def test_failing_candidates_skipped(self):
        def run(block):
            if block[0] == 128:
                raise RuntimeError("VMEM OOM")
            return jnp.zeros(())

        best = tuning.autotune("acam_match", (1, 1, 1), jnp.float32, run,
                               cands=[(128, 128, 256), (256, 128, 256)],
                               iters=1, save=False)
        assert best == (256, 128, 256)

    def test_no_candidate_runs_raises(self):
        def run(block):
            raise RuntimeError("VMEM OOM")

        with pytest.raises(RuntimeError, match="none of 2 candidate blocks"):
            tuning.autotune("acam_match", (1, 1, 1), jnp.float32, run,
                            cands=[(128, 128, 256), (256, 128, 256)],
                            iters=1, save=False)
