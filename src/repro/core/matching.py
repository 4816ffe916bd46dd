"""ACAM pattern matching (paper §II-D-2, Eq. 8-12) — deprecated shims.

The matching implementation lives in **`repro.match`** (engine layer): a
`MatchEngine` built from a hashable `EngineConfig`, a backend registry
(`reference` jnp oracles / `kernel` Pallas serve kernels /
`device` RRAM-CMOS physics from `repro.core.acam`), and mesh-sharded
execution over the data-parallel axes when `repro.distributed.context`
holds a mesh. New code should use it directly:

    from repro import match
    eng = match.engine_for(method="feature_count", backend="kernel")
    pred, per_class = eng.classify_features(features, bank)

This module keeps the historical entry points as thin delegating shims so
existing imports, notebooks and the parity test-suite keep working:

  feature_count_scores / similarity_scores / classify / classify_features /
  classify_features_margin / classify_scores / winner_take_all /
  window_margin, the `*_ref` oracles, and the TINY_ELEMENTS /
  MAX_FUSED_ROWS dispatch constants (all resolved lazily from
  `repro.match` — this shim must not import the engine at module level,
  because `repro.match` itself imports `repro.core`).

Backend selection
-----------------
`set_backend("auto" | "kernel" | "reference" | "device")` now sets the
*process default* in `repro.match` (same as `REPRO_MATCHING_BACKEND`), and
`use_backend(...)` scopes it to a `with` block. The old trace-time footgun
is gone: jitted callers (`repro.core.hybrid._fused_forward`, the serving
scheduler tick) receive the backend as a **static jit argument** resolved
eagerly at call time, so changing the backend between calls produces a new
trace instead of silently replaying the old one. Per-call pinning via the
``backend=`` keyword still works and still wins over the default.
"""
from __future__ import annotations

from typing import TYPE_CHECKING

import jax

if TYPE_CHECKING:
    from repro.core.templates import TemplateBank

Array = jax.Array

NEG = -jax.numpy.inf

#: names resolved lazily from repro.match on first attribute access
#: (PEP 562) — matching <-> match would otherwise be an import cycle.
_REEXPORTS = {
    "TINY_ELEMENTS", "MAX_FUSED_ROWS", "classify_scores", "winner_take_all",
    "window_margin", "feature_count_scores_ref", "similarity_scores_ref",
    "use_backend",
}

__all__ = sorted(_REEXPORTS | {
    "set_backend", "get_backend", "feature_count_scores",
    "similarity_scores", "classify", "classify_features",
    "classify_features_margin",
})


def __getattr__(name: str):
    if name in _REEXPORTS:
        import repro.match as match_lib

        value = getattr(match_lib, name)
        globals()[name] = value  # cache: subsequent access is direct
        return value
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def set_backend(name: str) -> None:
    """Set the process default backend (shim over
    `repro.match.set_default_backend`); "auto" | any registered backend."""
    from repro.match import set_default_backend

    set_default_backend(name)


def get_backend() -> str:
    """The process default backend name (shim)."""
    from repro.match import default_backend

    return default_backend()


def feature_count_scores(queries: Array, templates: Array,
                         valid: Array | None = None, *,
                         backend: str | None = None) -> Array:
    """Eq. 8 for a bank of templates (shim over `MatchEngine`).

    queries:   (B, N) binary {0,1}
    templates: (C, K, N) binary {0,1}
    returns:   (B, C, K) match counts; invalid templates get -inf.
    """
    from repro.match import engine_for

    return engine_for(backend=backend).feature_count_scores(
        queries, templates, valid)


def similarity_scores(
    queries: Array,
    lower: Array,
    upper: Array,
    valid: Array | None = None,
    *,
    alpha: float = 1.0,
    backend: str | None = None,
) -> Array:
    """Eq. 9-11 for a bank of window templates (shim over `MatchEngine`).

    queries:      (B, N)
    lower/upper:  (C, K, N)
    returns:      (B, C, K) similarity scores.
    """
    from repro.match import engine_for

    return engine_for(method="similarity", alpha=alpha,
                      backend=backend).similarity_scores(
        queries, lower, upper, valid)


def classify(
    queries: Array,
    bank: "TemplateBank",
    *,
    method: str = "feature_count",
    alpha: float = 1.0,
    backend: str | None = None,
) -> tuple[Array, Array]:
    """End-to-end Eq. 8/11 + Eq. 12 over *binary* queries (engine shim)."""
    from repro.match import engine_for

    return engine_for(method=method, alpha=alpha,
                      backend=backend).classify(queries, bank)


def classify_features(
    features: Array,
    bank: "TemplateBank",
    *,
    method: str = "feature_count",
    alpha: float = 1.0,
    backend: str | None = None,
) -> tuple[Array, Array]:
    """Raw front-end features -> binarize -> match -> WTA (engine shim).

    On the kernel backend this is a single fused pallas_call when the bank
    fits the fused layout (see `repro.match.KernelBackend`).
    """
    from repro.match import engine_for

    return engine_for(method=method, alpha=alpha,
                      backend=backend).classify_features(features, bank)


def classify_features_margin(
    features: Array,
    bank: "TemplateBank",
    class_lo: Array | None = None,
    class_hi: Array | None = None,
    *,
    method: str = "feature_count",
    alpha: float = 1.0,
    backend: str | None = None,
) -> tuple[Array, Array, Array]:
    """`classify_features` + per-request confidence margin (engine shim).

    Returns (pred (B,) int32 global class index, per_class (B, C),
    margin (B,) f32 clamped to the backend's score range: N for
    feature_count, 1 for similarity and the device backend). Empty windows
    (slot padding) yield pred 0, margin 0.
    """
    from repro.match import engine_for

    return engine_for(method=method, alpha=alpha,
                      backend=backend).classify_features_margin(
        features, bank, class_lo, class_hi)
