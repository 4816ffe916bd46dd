"""The serving mega-kernel, the one classify kernel of each method.

Four contracts:

  * `MatchEngine.classify_serve` on the kernel backend — the ONE-pallas_call
    gather -> binarize -> match -> per-class max -> WTA -> windowed margin ->
    escalation-mask path — is bit-identical to the jnp reference oracle,
    for both methods, at resident AND class-chunked bank sizes;
  * the tick really is a single dispatch: the traced jaxpr contains exactly
    one pallas_call, the method's serve kernel, with no jnp epilogue, below
    and above MAX_FUSED_ROWS — and so does the single-tenant face behind
    `classify_features_margin`;
  * both methods' non-serve classify calls run that face and match the
    reference at resident and class-chunked sizes ((1100, 2) and (300, 8)
    both exceed the fused budget);
  * "auto" backend routing uses per-method tiny cutoffs
    (`repro.match.tiny_cutoff`) from the measured reference/kernel
    crossovers, and the autotuner cache separates interpreted from compiled
    timings (v2 ``+interp`` keys).

Similarity parity notes: every operand is a dyadic rational (n/4, n/8) and
every query is exactly representable, so the kernel's per-chunk f32
accumulation and the oracle's full-axis sums are both exact — bit-equality
is well-defined, not luck.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import match
from repro.core.templates import TemplateBank
from repro.kernels import layout, tuning

N = 64
SLOT_TABLE = 8


def _dyadic(rng, shape, lo=-8, hi=9, denom=4.0):
    return jnp.asarray((rng.integers(lo, hi, size=shape) / denom
                        ).astype(np.float32))


def _serve_inputs(seed, b, c, k, n=N):
    rng = np.random.default_rng(seed)
    feats = _dyadic(rng, (b, n))
    thr_table = _dyadic(rng, (SLOT_TABLE, n), -4, 5)
    slot = jnp.asarray(rng.integers(0, SLOT_TABLE, size=b).astype(np.int32))
    lo_w = _dyadic(rng, (c, k, n), -8, 1)
    hi_w = lo_w + _dyadic(rng, (c, k, n), 0, 9)
    valid = jnp.asarray(rng.random((c, k)) > 0.2)
    bank = TemplateBank(templates=(lo_w > 0).astype(jnp.float32),
                        lower=lo_w, upper=hi_w, valid=valid,
                        thresholds=jnp.zeros((n,), jnp.float32))
    lo = jnp.asarray(rng.integers(0, max(c - 4, 1), size=b).astype(np.int32))
    hi = jnp.minimum(lo + rng.integers(1, c + 1, size=b), c).astype(jnp.int32)
    hi = hi.at[0].set(lo[0])  # empty window: pred 0 / margin 0 / no escalate
    return feats, thr_table, slot, bank, lo, hi


def _eng(method, backend):
    return match.engine_from_config(match.EngineConfig(
        method=method, backend=backend))


# (c, k) resident in the fused budget, and two chunked shapes past it
RESIDENT = (12, 4)
CHUNKED = [(1100, 2), (300, 8)]


class TestMegaKernelParity:
    @pytest.mark.parametrize("method", ["feature_count", "similarity"])
    @pytest.mark.parametrize("c,k", [RESIDENT] + CHUNKED)
    def test_bit_identical_to_oracle(self, method, c, k):
        feats, thr_table, slot, bank, lo, hi = _serve_inputs(c + k, 16, c, k)
        ref = _eng(method, "reference")
        # per-row taus straddle each oracle margin (above on even rows,
        # below on odd): the escalation set is non-trivial by construction
        margins = ref.classify_serve(feats, thr_table, slot, bank, lo, hi)[2]
        sign = jnp.where(jnp.arange(16) % 2 == 0, 0.5, -0.5)
        tau = (margins + sign).astype(jnp.float32)

        r = ref.classify_serve(feats, thr_table, slot, bank, lo, hi, tau)
        k_ = _eng(method, "kernel").classify_serve(
            feats, thr_table, slot, bank, lo, hi, tau)
        for name, i in zip(("pred", "per_class", "margin", "escalate"),
                           range(4)):
            np.testing.assert_array_equal(
                np.asarray(r[i]), np.asarray(k_[i]), err_msg=f"mega {name}")
        esc = np.asarray(r[3])
        assert esc.any() and not esc.all()  # mask exercised both ways
        assert int(r[0][0]) == 0 and float(r[2][0]) == 0.0  # empty window
        assert bool(esc[0]) == (0.0 < float(tau[0]))

    @pytest.mark.parametrize("method", ["feature_count", "similarity"])
    @pytest.mark.parametrize("c,k", [RESIDENT, CHUNKED[0]])
    def test_tick_is_one_pallas_call(self, method, c, k):
        feats, thr_table, slot, bank, lo, hi = _serve_inputs(7, 8, c, k)
        tau = jnp.zeros((8,), jnp.float32)
        eng = _eng(method, "kernel")
        serve = {"feature_count": "acam_match_serve",
                 "similarity": "acam_similarity_serve"}[method]
        ticks = {
            "classify_serve": (eng.classify_serve,
                               (feats, thr_table, slot, bank, lo, hi, tau)),
            "classify_features_margin": (eng.classify_features_margin,
                                         (feats, bank, lo, hi)),
        }
        for entry, (fn, args) in ticks.items():
            jaxpr = str(jax.make_jaxpr(lambda *a: fn(*a))(*args))
            assert jaxpr.count("pallas_call") == 1, entry
            assert f"name={serve}" in jaxpr, entry

    def test_default_tau_never_escalates(self):
        feats, thr_table, slot, bank, lo, hi = _serve_inputs(3, 8, *RESIDENT)
        out = _eng("feature_count", "kernel").classify_serve(
            feats, thr_table, slot, bank, lo, hi)
        assert not np.asarray(out[3]).any()


class TestSingleTenantFaceCoverage:
    """Both methods' non-serve classify calls: the serve kernel's
    single-tenant face, resident and class-chunked."""

    @pytest.mark.parametrize("method", ["feature_count", "similarity"])
    @pytest.mark.parametrize("c,k", [RESIDENT] + CHUNKED)
    def test_margin_parity_resident_and_chunked(self, method, c, k):
        assert (k * layout.padded_classes(c) > match.MAX_FUSED_ROWS) == \
            ((c, k) in CHUNKED)
        feats, _, _, bank, lo, hi = _serve_inputs(c, 16, c, k)
        ker = _eng(method, "kernel")
        ref = _eng(method, "reference")
        got = ker.classify_features_margin(feats, bank, lo, hi)
        want = ref.classify_features_margin(feats, bank, lo, hi)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))

    @pytest.mark.parametrize("method", ["feature_count", "similarity"])
    @pytest.mark.parametrize("c,k", [RESIDENT] + CHUNKED)
    def test_classify_features_single_dispatch_parity(self, method, c, k):
        feats, _, _, bank, _, _ = _serve_inputs(c + 1, 8, c, k)
        ker = _eng(method, "kernel")
        got = ker.classify_features(feats, bank)
        want = _eng(method, "reference").classify_features(feats, bank)
        np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
        np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
        jaxpr = str(jax.make_jaxpr(
            lambda f: ker.classify_features(f, bank))(feats))
        assert jaxpr.count("pallas_call") == 1


class TestTinyCutoffRouting:
    def test_per_method_cutoffs(self):
        assert match.tiny_cutoff("feature_count") == match.TINY_ELEMENTS
        assert match.tiny_cutoff("similarity") == \
            match.TINY_ELEMENTS_SIMILARITY
        assert match.TINY_ELEMENTS_SIMILARITY > match.TINY_ELEMENTS

    def test_auto_routes_tiny_shapes_to_reference(self):
        # an element count between the two cutoffs: the VPU-bound similarity
        # kernel still loses to jnp there, the MXU match kernel already wins
        mid = (match.TINY_ELEMENTS + match.TINY_ELEMENTS_SIMILARITY) // 2
        fc = match.engine_from_config(match.EngineConfig(
            method="feature_count", backend="auto"))
        sim = match.engine_from_config(match.EngineConfig(
            method="similarity", backend="auto"))
        assert isinstance(fc.backend(match.TINY_ELEMENTS - 1),
                          match.ReferenceBackend)
        assert isinstance(fc.backend(mid), match.KernelBackend)
        assert isinstance(sim.backend(mid), match.ReferenceBackend)
        assert isinstance(sim.backend(match.TINY_ELEMENTS_SIMILARITY),
                          match.KernelBackend)


class TestTuningCacheKeys:
    def test_interp_token_separates_cpu_populations(self):
        key = tuning.entry_key("acam_match", (8, 8, 8), jnp.float32)
        if tuning.interpret_mode():
            assert "+interp" in key
        else:
            assert "+interp" not in key
        # explicit device strings are taken verbatim (offline tuning for
        # another platform never inherits this process's interpret state)
        assert "+interp" not in tuning.entry_key("acam_match", (8, 8, 8),
                                                 jnp.float32, device="tpu")

    def test_v1_caches_discarded(self, tmp_path, monkeypatch):
        import json
        path = tmp_path / "blocks.json"
        path.write_text(json.dumps({
            "version": 1,
            "entries": {"acam_match|cpu|b8_m8_n8|float32":
                        {"block": [256, 256, 1024], "us": 1.0}}}))
        monkeypatch.setenv("REPRO_TUNING_CACHE", str(path))
        tuning.clear_cache_for_tests()
        try:
            assert tuning.get_block("acam_match", (8, 8, 8), jnp.float32) \
                == tuning.default_block("acam_match")
        finally:
            tuning.clear_cache_for_tests()

