"""Per-kernel allclose sweeps against the pure-jnp oracles (interpret mode).

Every Pallas kernel is swept over shapes (aligned + ragged, forcing the
padding paths) and dtypes, asserting against its ref.py oracle.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_fallback import given, settings, st

from repro.kernels import layout
from repro.kernels.acam_match import ops as match_ops
from repro.kernels.acam_match.ref import acam_match_ref
from repro.kernels.acam_similarity import ops as sim_ops
from repro.kernels.acam_similarity.acam_similarity import eq11
from repro.kernels.acam_similarity.ref import acam_similarity_ref
from repro.kernels.kd_loss import ops as kd_ops
from repro.kernels.kd_loss.ref import kd_loss_ref
from repro.match import MAX_FUSED_ROWS
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.flash_attention.ref import attention_ref


class TestAcamMatch:
    @pytest.mark.parametrize("b,m,n", [
        (8, 10, 784),      # the paper's deployment shape
        (128, 128, 512),   # exactly one tile
        (37, 30, 300),     # ragged: every dim padded
        (1, 1, 1),         # degenerate
        (200, 257, 1000),  # multi-tile ragged
    ])
    def test_shapes(self, b, m, n):
        key = jax.random.PRNGKey(b * m + n)
        f = jax.random.normal(key, (b, n))
        thr = jax.random.normal(jax.random.fold_in(key, 1), (n,)) * 0.1
        t = (jax.random.uniform(jax.random.fold_in(key, 2), (m, n)) > 0.5
             ).astype(jnp.float32)
        got = match_ops.match_scores(f, thr, t)
        np.testing.assert_allclose(got, acam_match_ref(f, thr, t), atol=0)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_dtypes(self, dtype):
        key = jax.random.PRNGKey(0)
        f = jax.random.normal(key, (16, 256)).astype(dtype)
        thr = jnp.zeros((256,), dtype)
        t = (jax.random.uniform(jax.random.fold_in(key, 1), (12, 256)) > 0.5
             ).astype(dtype)
        got = match_ops.match_scores(f, thr, t)
        want = acam_match_ref(f.astype(jnp.float32), thr.astype(jnp.float32),
                              t.astype(jnp.float32))
        np.testing.assert_allclose(got, want, atol=0)

    def test_classify_matches_core(self):
        from repro.core import matching, quant, templates as T
        key = jax.random.PRNGKey(7)
        feats = jax.random.normal(key, (64, 96))
        labels = jnp.arange(64) % 4
        bank = T.generate_templates(feats, labels, 4, k=2)
        pred_kernel, _, _ = match_ops.classify_fused_margins(
            feats, bank.thresholds, bank.templates, bank.valid,
            max_rows=MAX_FUSED_ROWS)
        q = quant.binarize(feats, bank.thresholds)
        pred_core, _ = matching.classify(q, bank, method="feature_count")
        assert bool(jnp.all(pred_kernel == pred_core))


class TestAcamSimilarity:
    @pytest.mark.parametrize("b,m,n,alpha", [
        (8, 128, 128, 1.0),
        (17, 9, 300, 2.0),
        (8, 10, 784, 0.5),
        (3, 2, 50, 1.0),
    ])
    def test_shapes(self, b, m, n, alpha):
        key = jax.random.PRNGKey(b + m + n)
        q = jax.random.uniform(key, (b, n))
        lo = jax.random.uniform(jax.random.fold_in(key, 1), (m, n)) * 0.5
        hi = lo + jax.random.uniform(jax.random.fold_in(key, 2), (m, n)) * 0.5
        got = sim_ops.similarity_scores(q, lo, hi, alpha=alpha)
        want = acam_similarity_ref(q, lo, hi, alpha=alpha)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)

    def test_scores_bounded(self):
        key = jax.random.PRNGKey(3)
        q = jax.random.uniform(key, (32, 100))
        lo = jnp.zeros((5, 100))
        hi = jnp.ones((5, 100))
        s = sim_ops.similarity_scores(q, lo, hi)
        assert bool(jnp.all((s >= 0) & (s <= 1)))
        np.testing.assert_allclose(s, 1.0)  # everything inside the window

    @pytest.mark.parametrize("n", [50, 784])
    def test_eq11_perfect_match_is_one(self, n):
        hits = jnp.asarray([float(n), 0.0])
        s = eq11(hits, jnp.zeros(2), n, 1.0)
        assert s.tolist() == [1.0, 0.0]


class TestLayout:
    @pytest.mark.parametrize("row", [
        [0.5, 1.0, 1.0, 0.2],           # tie: lowest index wins
        [1.0, 1.0, 1.0, 1.0],
        [-np.inf, -np.inf, -np.inf],    # nothing valid: index 0
        [-np.inf, 3.0, -np.inf, 3.0],
    ])
    def test_first_argmax_matches_jnp_argmax(self, row):
        x = jnp.asarray([row], jnp.float32)
        assert int(layout.first_argmax(x)[0]) == int(jnp.argmax(x[0]))
        assert layout.first_argmax(x).dtype == jnp.int32


class TestKDLoss:
    @pytest.mark.parametrize("b,v", [
        (13, 5000), (8, 152064 // 16), (256, 2048), (3, 17), (64, 504),
    ])
    def test_shapes(self, b, v):
        key = jax.random.PRNGKey(b + v)
        zs = jax.random.normal(key, (b, v)) * 3
        zt = jax.random.normal(jax.random.fold_in(key, 1), (b, v)) * 3
        y = jax.random.randint(jax.random.fold_in(key, 2), (b,), 0, v)
        got = kd_ops.distillation_loss(zs, zt, y)
        want = float(jnp.mean(kd_loss_ref(zs, zt, y)))
        assert float(got) == pytest.approx(want, rel=1e-4, abs=1e-5)

    @given(st.floats(1.0, 8.0), st.floats(0.0, 1.0))
    @settings(max_examples=10, deadline=None)
    def test_hyperparams(self, t, alpha):
        key = jax.random.PRNGKey(int(t * 10 + alpha * 100))
        zs = jax.random.normal(key, (6, 400)) * 2
        zt = jax.random.normal(jax.random.fold_in(key, 1), (6, 400)) * 2
        y = jnp.arange(6) * 7
        got = kd_ops.distillation_loss(zs, zt, y, temperature=t, alpha=alpha)
        want = float(jnp.mean(kd_loss_ref(zs, zt, y, temperature=t, alpha=alpha)))
        assert float(got) == pytest.approx(want, rel=1e-3, abs=1e-4)

    def test_matches_core_distill(self):
        from repro.core import distill
        key = jax.random.PRNGKey(0)
        zs = jax.random.normal(key, (32, 100))
        zt = jax.random.normal(jax.random.fold_in(key, 1), (32, 100))
        y = jnp.arange(32) % 100
        got = kd_ops.distillation_loss(zs, zt, y, temperature=4.0, alpha=0.5)
        want = distill.distillation_loss(zs, zt, y, alpha=0.5, temperature=4.0)
        assert float(got) == pytest.approx(float(want), rel=1e-4)


class TestFlashAttention:
    @pytest.mark.parametrize("b,s,h,kv,d,causal", [
        (2, 200, 8, 2, 64, True),
        (1, 128, 4, 4, 128, True),
        (2, 333, 6, 2, 64, False),   # ragged + bidirectional (encoder)
        (1, 512, 2, 1, 32, True),
    ])
    def test_against_ref(self, b, s, h, kv, d, causal):
        key = jax.random.PRNGKey(s + h)
        q = jax.random.normal(key, (b, s, h, d), jnp.float32)
        k = jax.random.normal(jax.random.fold_in(key, 1), (b, s, kv, d))
        v = jax.random.normal(jax.random.fold_in(key, 2), (b, s, kv, d))
        got = fa_ops.attention(q, k, v, causal=causal, block=(128, 128))
        g = h // kv
        kx, vx = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
        q3 = q.transpose(0, 2, 1, 3).reshape(b * h, s, d)
        k3 = kx.transpose(0, 2, 1, 3).reshape(b * h, s, d)
        v3 = vx.transpose(0, 2, 1, 3).reshape(b * h, s, d)
        want = attention_ref(q3, k3, v3, causal=causal).reshape(
            b, h, s, d).transpose(0, 2, 1, 3)
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)

    def test_matches_model_fallback(self):
        """Kernel == the model's chunked XLA fallback (same semantics)."""
        from repro.models.layers import chunked_attention
        key = jax.random.PRNGKey(1)
        q = jax.random.normal(key, (2, 160, 4, 32))
        k = jax.random.normal(jax.random.fold_in(key, 1), (2, 160, 2, 32))
        v = jax.random.normal(jax.random.fold_in(key, 2), (2, 160, 2, 32))
        got = fa_ops.attention(q, k, v, causal=True, block=(64, 64))
        want = chunked_attention(q, k, v, causal=True, q_chunk=64)
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)
