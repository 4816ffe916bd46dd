"""repro.match — the pluggable, mesh-aware template-matching engine.

Layering (plan x backends x callers):

    callers   repro.core.hybrid (ACAMHead / HybridClassifier)
              repro.serve.{registry, scheduler, acam_service}  (super-bank)
              repro.launch.serve --workload acam --backend/--bank-shards
              benchmarks/{kernel_bench, serving_bench}, examples/*
                 |
    engine    MatchEngine(EngineConfig)   one hashable config; jit-static
                 |
    plan      PartitionPlan(config, mesh, static shapes): batch over the dp
              axes, bank class rows over the model axis, or both (2D); the
              cross-shard (max, argmax) reduce keeps decisions/margins
              bit-identical to replicated execution
                 |
    backends  reference (jnp oracles) | kernel (single-dispatch Pallas at
              any bank size + the serving mega-kernel + autotuner) | device
              (repro.core.acam RRAM-CMOS physics)

See `repro.match.engine`, `repro.match.plan` and `repro.match.backends`
for the contracts.
"""
from repro.match.backends import (MAX_FUSED_ROWS, TINY_ELEMENTS,
                                  TINY_ELEMENTS_SIMILARITY, DeviceBackend,
                                  KernelBackend, MatchBackend,
                                  ReferenceBackend, backend_for,
                                  backend_names, classify_scores,
                                  feature_count_scores_ref, register_backend,
                                  shard_window_top2, similarity_scores_ref,
                                  tiny_cutoff, window_margin, winner_take_all)
from repro.match.config import EngineConfig
from repro.match.engine import (MatchEngine, bank_specs, batch_specs,
                                default_backend, dp_axes_in_mesh, engine_for,
                                engine_from_config, set_default_backend,
                                use_backend)
from repro.match.plan import (REPLICATED, TREE_REDUCE_MIN_SHARDS,
                              PartitionPlan, Placement, bank_shards_in_mesh,
                              placement_for, plan_for, reduce_strategy)

__all__ = [
    "MAX_FUSED_ROWS", "TINY_ELEMENTS", "TINY_ELEMENTS_SIMILARITY",
    "DeviceBackend", "KernelBackend", "MatchBackend", "ReferenceBackend",
    "backend_for", "backend_names", "classify_scores",
    "feature_count_scores_ref", "register_backend", "shard_window_top2",
    "similarity_scores_ref", "tiny_cutoff", "window_margin",
    "winner_take_all", "EngineConfig", "MatchEngine", "bank_specs",
    "batch_specs", "default_backend", "dp_axes_in_mesh", "engine_for",
    "engine_from_config", "set_default_backend", "use_backend", "REPLICATED",
    "TREE_REDUCE_MIN_SHARDS", "PartitionPlan", "Placement",
    "bank_shards_in_mesh", "placement_for", "plan_for", "reduce_strategy",
]
