"""Engine configuration: one hashable value object per matching setup.

`EngineConfig` replaces the old mutable module global in
`repro.core.matching` (`_backend`) as the way a caller selects matching
behaviour. Because it is a NamedTuple of hashables it can be

  * a `functools.lru_cache` key (`repro.match.engine_for` memoises one
    `MatchEngine` per distinct config), and
  * a **static jit argument** — jitted callers that close over a config
    (e.g. `repro.core.hybrid._fused_forward`, the serving scheduler's tick)
    get a *separate trace per config*, so changing the backend can never be
    silently baked into a stale executable.

Fields map onto the knobs the old dispatch layer spread across module
globals, keywords and environment variables:

  method   "feature_count" (Eq. 8) or "similarity" (Eq. 9-11)
  alpha    Eq. 11 distance weight (similarity method only)
  backend  "auto" | "reference" | "kernel" | "device" (or any name added
           via `repro.match.register_backend`); "auto" picks reference for
           tiny shapes and kernel otherwise
  block    optional (bm, bn, bk) Pallas block override; None = autotuner
  margin   `MatchEngine.__call__` returns (pred, per_class, margin) instead
           of (pred, per_class) — the serving cascade's signal
  device   `repro.core.acam.ACAMConfig` for the device-physics backend
           (cell flavour, sigma_program, ...); None = ACAMConfig() defaults
  seed     PRNG seed for `sigma_program > 0` programming noise
  device_noise
           how `sigma_program > 0` write noise maps onto bank shards
           (device backend only):
             "global"     ONE physical array draws one noise field — the
                          backend declines bank sharding, since per-shard
                          sub-arrays keyed alike would realise a different
                          layout than the replicated array.
             "per_shard"  real tiled deployments program one array PER
                          shard: array s (class rows [s*C/S, (s+1)*C/S))
                          draws its noise from fold_in(PRNGKey(seed), s).
                          Lifts the sharding refusal; an unsharded call is
                          the S = 1 tiling (fold_in(seed, 0)).
"""
from __future__ import annotations

from typing import NamedTuple

from repro.core.acam import ACAMConfig

METHODS = ("feature_count", "similarity")

DEVICE_NOISE_MODES = ("global", "per_shard")


class EngineConfig(NamedTuple):
    method: str = "feature_count"
    alpha: float = 1.0
    backend: str = "auto"
    block: tuple[int, int, int] | None = None
    margin: bool = False
    device: ACAMConfig | None = None
    seed: int = 0
    device_noise: str = "global"


def validate(config: EngineConfig, backend_names: tuple[str, ...]) -> None:
    """Raise ValueError for unknown methods/backends (same errors the old
    `repro.core.matching` dispatch raised, so callers/tests are unchanged)."""
    if config.method not in METHODS:
        raise ValueError(f"unknown matching method {config.method}")
    if config.backend != "auto" and config.backend not in backend_names:
        raise ValueError(
            f"unknown matching backend {config.backend!r}; use "
            f"{('auto',) + backend_names}")
    if config.block is not None and len(tuple(config.block)) != 3:
        raise ValueError(f"block must be (bm, bn, bk), got {config.block!r}")
    if config.device_noise not in DEVICE_NOISE_MODES:
        raise ValueError(f"unknown device_noise {config.device_noise!r}; "
                         f"use {DEVICE_NOISE_MODES}")
    hash(config)  # fail fast: configs must stay usable as static jit args
