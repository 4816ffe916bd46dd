"""Kernel micro-benchmarks: ref-vs-kernel comparison harness.

Times the compiled jnp reference paths against the Pallas kernel paths at
deployment shapes (B in {1, 256, 4096}; M=10 templates, N=784 features) and
emits ``BENCH_kernels.json`` so the perf trajectory is tracked PR over PR.

On this CPU container the Pallas kernels execute in interpret mode (lowered
to XLA through the pallas interpreter — a correctness path, not a TPU
number), so CPU "speedup" mostly measures interpreter overhead; the JSON
records ``backend``/``interpret`` so TPU runs are distinguishable. The jnp
reference wall-times remain real regression signals for the XLA fallbacks.

BENCH_kernels.json schema::

    {"backend": "cpu" | "tpu",
     "interpret": bool,            # kernels ran via the pallas interpreter
     "entries": [
       {"kernel": "acam_match",    # | acam_similarity
                                   # | acam_device_classify (RRAM physics)
                                   # | acam_match_serve /
                                   #   acam_similarity_serve (the serving
                                   #   mega-kernel; ref_us = the jnp
                                   #   reference's classify_serve)
                                   # | acam_similarity_face_chunked
                                   #   (big-bank single-dispatch similarity;
                                   #   ref_us = jnp oracle)
                                   # | acam_match_face_sharded
                                   #   (bank rows sharded over the model
                                   #   axis; ref_us = replicated engine,
                                   #   kernel_us = sharded engine, extra
                                   #   "bank_sharding" + "reduce" fields —
                                   #   the cross-shard reduce strategy the
                                   #   plan selected; rows appear only
                                   #   under REPRO_FORCE_MESH)
        "b": 256, "m": 10, "n": 784,
        "ref_us": 123.4,           # jnp reference, us/call
        "kernel_us": 456.7,        # timed engine backend (pallas kernels,
                                   # or the device-physics model), us/call
        "speedup": 0.27,           # ref_us / kernel_us
        "ref_cell_matches_per_us": ...,    # b*m*n / us
        "kernel_cell_matches_per_us": ...}]}

The raw ``acam_match``/``acam_similarity`` rows time the raw-scores Pallas
kernels directly against their jnp oracles (kernel micro-benchmarks); the
``*_classify*`` and ``*_serve`` rows go through `repro.match.MatchEngine` —
the exact path production callers execute.

``--tune`` grid-searches kernel block sizes first (repro.kernels.tuning —
the winners persist to the v2 JSON cache keyed by
``kernel|platform[+interp]|shape|dtype``, so interpreted and compiled
timings never cross-contaminate); ``--smoke`` restricts to B in {1, 256}
for CI.

`run()` keeps the harness contract used by benchmarks/run.py: a list of
``{"name", "us_per_call", "derived"}`` rows.
"""
from __future__ import annotations

import argparse
import json
import os
import time

from repro.distributed import forcemesh  # imports no jax

# REPRO_FORCE_MESH phase 1 (forced host devices) must land in XLA_FLAGS
# before jax initialises its CPU backend — i.e. before the import below
forcemesh.apply_xla_flags()

import jax
import jax.numpy as jnp

BENCH_SHAPES = (1, 256, 4096)  # batch sizes; the paper bank is M=10, N=784
SMOKE_SHAPES = (1, 256)
M, N = 10, 784


def _time(fn, *args, iters=20, reps=3) -> float:
    """us/call: best of `reps` timed loops (min suppresses the scheduler
    noise of this shared CPU container, the standard repeat-min protocol)."""
    out = fn(*args)  # single warmup call; reuse its result
    (out[0] if isinstance(out, tuple) else out).block_until_ready()
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
            (out[0] if isinstance(out, tuple) else out).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best / iters * 1e6  # us


def _compare_entry(kernel: str, b: int, m: int, n: int, ref_us: float,
                   kernel_us: float) -> dict:
    cells = b * m * n
    return {
        "kernel": kernel, "b": b, "m": m, "n": n,
        "ref_us": round(ref_us, 2), "kernel_us": round(kernel_us, 2),
        "speedup": round(ref_us / kernel_us, 4),
        "ref_cell_matches_per_us": round(cells / ref_us, 1),
        "kernel_cell_matches_per_us": round(cells / kernel_us, 1),
    }


def compare_kernels(batches=BENCH_SHAPES, *, iters=10) -> list[dict]:
    """Ref-vs-kernel timing entries for both raw-scores ACAM kernels + the
    device-physics classify."""
    from repro.core import templates as T
    from repro.kernels.acam_match import ops as match_ops
    from repro.kernels.acam_match.ref import acam_match_ref
    from repro.kernels.acam_similarity import ops as sim_ops
    from repro.kernels.acam_similarity.ref import acam_similarity_ref

    key = jax.random.PRNGKey(0)
    thr = jnp.zeros((N,))
    tmpl = (jax.random.uniform(key, (M, N)) > 0.5).astype(jnp.float32)
    lo = jnp.zeros((M, N))
    hi = (jax.random.uniform(jax.random.fold_in(key, 1), (M, N)) > 0.3
          ).astype(jnp.float32)
    bank = T.TemplateBank(
        templates=tmpl[:, None, :], lower=lo[:, None, :], upper=hi[:, None, :],
        valid=jnp.ones((M, 1), bool), thresholds=thr)

    entries = []
    for b in batches:
        f = jax.random.normal(jax.random.fold_in(key, b), (b, N))
        it = max(3, iters // 4) if b >= 4096 else iters

        # kernel paths timed under jit, as deployed (hybrid._fused_forward
        # traces the dispatch into one graph; block lookup is trace-time)
        ref_us = _time(jax.jit(acam_match_ref), f, thr, tmpl, iters=it)
        ker_us = _time(jax.jit(lambda x: match_ops.match_scores(x, thr, tmpl)),
                       f, iters=it)
        entries.append(_compare_entry("acam_match", b, M, N, ref_us, ker_us))

        ref_us = _time(jax.jit(acam_similarity_ref), f, lo, hi, iters=it)
        ker_us = _time(jax.jit(lambda x: sim_ops.similarity_scores(x, lo, hi)),
                       f, iters=it)
        entries.append(_compare_entry("acam_similarity", b, M, N, ref_us,
                                      ker_us))

        # end-to-end classify through the engine layer (what production
        # callers execute): reference vs the RRAM-device-physics backend
        from repro import match

        eng_ref = match.engine_for(backend="reference")
        eng_dev = match.engine_for(backend="device")

        ref_us = _time(jax.jit(lambda feats: eng_ref.classify_features(
            feats, bank)), f, iters=it)
        dev_us = _time(jax.jit(lambda feats: eng_dev.classify_features(
            feats, bank)), f, iters=it)
        entries.append(_compare_entry("acam_device_classify", b, M, N,
                                      ref_us, dev_us))
    return entries


def serve_entries(batches=BENCH_SHAPES, *, iters: int = 10) -> list[dict]:
    """Serve-kernel rows for the multi-tenant serve path.

    Times `MatchEngine.classify_serve` (the scheduler tick's dispatch) on
    the kernel backend (ONE pallas_call) against the reference backend's
    jnp composition. Plus the big-bank chunked-similarity row against its
    jnp oracle."""
    from repro import match
    from repro.core import templates as T

    key = jax.random.PRNGKey(2)
    n_slots = 8
    tmpl = (jax.random.uniform(key, (M, 1, N)) > 0.5).astype(jnp.float32)
    bank = T.TemplateBank(
        templates=tmpl, lower=jnp.zeros_like(tmpl),
        upper=(jax.random.uniform(jax.random.fold_in(key, 1), (M, 1, N))
               > 0.3).astype(jnp.float32),
        valid=jnp.ones((M, 1), bool), thresholds=jnp.zeros((N,)))
    thr_table = jax.random.normal(jax.random.fold_in(key, 2),
                                  (n_slots, N)) * 0.1

    entries = []
    for b in batches:
        f = jax.random.normal(jax.random.fold_in(key, b), (b, N))
        slot = jnp.asarray(
            jax.random.randint(jax.random.fold_in(key, b + 1), (b,), 0,
                               n_slots), jnp.int32)
        tau = jnp.full((b,), 2.0, jnp.float32)
        it = max(3, iters // 4) if b >= 4096 else iters
        for method, name in (("feature_count", "acam_match_serve"),
                             ("similarity", "acam_similarity_serve")):
            ker = match.engine_from_config(match.EngineConfig(
                method=method, backend="kernel"))
            ref = match.engine_from_config(match.EngineConfig(
                method=method, backend="reference"))
            ref_us = _time(jax.jit(lambda x, s, t, e=ref: e.classify_serve(
                x, thr_table, s, bank, tau=t)), f, slot, tau, iters=it)
            ker_us = _time(jax.jit(lambda x, s, t, e=ker: e.classify_serve(
                x, thr_table, s, bank, tau=t)), f, slot, tau, iters=it)
            entries.append(_compare_entry(name, b, M, N, ref_us, ker_us))

    # big-bank chunked similarity: C=1100, K=2 exceeds the fused budget
    c_big, k_big = 1100, 2
    big = (jax.random.uniform(jax.random.fold_in(key, 9),
                              (c_big, k_big, N)) > 0.5).astype(jnp.float32)
    big_bank = T.TemplateBank(
        templates=big, lower=jnp.zeros_like(big), upper=jnp.ones_like(big),
        valid=jnp.ones((c_big, k_big), bool), thresholds=jnp.zeros((N,)))
    eng_ref = match.engine_from_config(match.EngineConfig(
        method="similarity", backend="reference"))
    eng_ker = match.engine_from_config(match.EngineConfig(
        method="similarity", backend="kernel"))
    for b in batches[:2]:  # the big bank at B=4096 is a minutes-long cell
        f = jax.random.normal(jax.random.fold_in(key, 20 + b), (b, N))
        ref_us = _time(jax.jit(lambda x: eng_ref.classify_features_margin(
            x, big_bank)), f, iters=max(3, iters // 2))
        ker_us = _time(jax.jit(lambda x: eng_ker.classify_features_margin(
            x, big_bank)), f, iters=max(3, iters // 2))
        entries.append(_compare_entry("acam_similarity_face_chunked", b,
                                      c_big * k_big, N, ref_us, ker_us))
    return entries


def sharded_classify_entries(batches=BENCH_SHAPES, *, classes: int = 512,
                             iters: int = 10) -> list[dict]:
    """Replicated-vs-bank-sharded classify rows (the model-axis story).

    Times `MatchEngine.classify_features` over a ``classes``-row bank with
    the forced ``REPRO_FORCE_MESH`` mesh installed (super-bank class rows
    sharded over "model", batch over "data") against the same engine
    replicated. Emits nothing when no forced mesh is available. On CPU both
    sides run Pallas-interpret, so these rows track the *dispatch
    structure* cost; the replicated-vs-sharded crossover is a TPU number.
    """
    from repro import match
    from repro.core import templates as T
    from repro.distributed import context

    spec = forcemesh.env_spec()
    if spec is None:
        return []
    try:
        mesh = forcemesh.install(spec)
    except RuntimeError as e:
        print(f"skipping sharded-classify rows: {e}")
        return []
    # record what the engine will actually do, not the mesh shape: a model
    # axis that doesn't divide `classes` runs bank-replicated
    plan, _ = match.plan_for(batch=batches[0], num_classes=classes)
    shards = plan.bank_shards
    if shards == 1:
        print(f"skipping sharded-classify rows: {classes} classes do not "
              f"shard over the {dict(mesh.shape)} mesh")
        context.clear()
        return []

    key = jax.random.PRNGKey(1)
    tmpl = (jax.random.uniform(key, (classes, 1, N)) > 0.5
            ).astype(jnp.float32)
    bank = T.TemplateBank(
        templates=tmpl, lower=jnp.zeros_like(tmpl),
        upper=jnp.ones_like(tmpl), valid=jnp.ones((classes, 1), bool),
        thresholds=jnp.zeros((N,)))
    eng = match.engine_for(backend="kernel")

    entries = []
    for b in batches:
        f = jax.random.normal(jax.random.fold_in(key, b), (b, N))
        it = max(3, iters // 4) if b >= 4096 else iters
        context.set_mesh_axes("data", "model", mesh)
        sharded_us = _time(jax.jit(
            lambda x: eng.classify_features(x, bank)), f, iters=it)
        context.clear()
        rep_us = _time(jax.jit(
            lambda x: eng.classify_features(x, bank)), f, iters=it)
        e = _compare_entry("acam_match_face_sharded", b, classes, N,
                           rep_us, sharded_us)
        e["bank_sharding"] = shards
        e["reduce"] = plan.reduce  # cross-shard strategy the plan selected
        entries.append(e)
    context.clear()
    return entries


def write_bench_json(entries: list[dict],
                     path: str = "BENCH_kernels.json") -> None:
    from repro.kernels import tuning

    payload = {
        "backend": tuning.backend(),
        # same predicate the ops wrappers use to enable interpret mode, so
        # the flag always reflects how the kernels actually executed
        "interpret": tuning.interpret_mode(),
        "entries": entries,
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)


def run() -> list[dict]:
    fast = os.environ.get("REPRO_BENCH_FAST", "0") == "1"
    rows = []
    key = jax.random.PRNGKey(0)

    shapes = SMOKE_SHAPES if fast else BENCH_SHAPES
    entries = compare_kernels(shapes)
    entries += serve_entries(shapes)
    entries += sharded_classify_entries(shapes)  # no-op without forced mesh
    write_bench_json(entries)
    for e in entries:
        rows.append({
            "name": f"{e['kernel']}_b{e['b']}",
            "us_per_call": e["kernel_us"],
            "derived": (f"ref={e['ref_us']:.0f}us,speedup={e['speedup']:.2f},"
                        f"{e['kernel_cell_matches_per_us']:.0f} cell-matches/us"),
        })

    from repro.kernels.kd_loss.ref import kd_loss_ref
    zs = jax.random.normal(key, (64, 32000))
    zt = jax.random.normal(key, (64, 32000))
    y = jnp.zeros((64,), jnp.int32)
    us = _time(jax.jit(lambda a, b, c: jnp.mean(kd_loss_ref(a, b, c))), zs, zt, y)
    rows.append({"name": "kd_loss_ref_64x32k", "us_per_call": us,
                 "derived": f"{64*32000*4/us/1e3:.1f} MB/ms logits"})

    from repro.models.layers import chunked_attention
    qq = jax.random.normal(key, (1, 1024, 8, 64), jnp.bfloat16)
    kk = jax.random.normal(key, (1, 1024, 2, 64), jnp.bfloat16)
    vv = jax.random.normal(key, (1, 1024, 2, 64), jnp.bfloat16)
    us = _time(jax.jit(lambda a, b, c: chunked_attention(a, b, c, causal=True)),
               qq, kk, vv)
    flops = 2 * 2 * 1024 * 1024 * 8 * 64
    rows.append({"name": "chunked_attention_1k", "us_per_call": us,
                 "derived": f"{flops/us/1e3:.1f} MFLOP/ms"})
    return rows


def main() -> None:
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--tune", action="store_true",
                    help="autotune kernel blocks before benchmarking")
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke: B in {1, 256} only")
    args = ap.parse_args()

    if args.smoke:
        os.environ["REPRO_BENCH_FAST"] = "1"
    if args.tune:
        from repro.kernels import tuning
        for k, blk in tuning.autotune_acam(
                shapes=[(b, M, N) for b in
                        (SMOKE_SHAPES if args.smoke else BENCH_SHAPES)]).items():
            print(f"tuned {k} -> {blk}")

    for r in run():
        print(r)
    print("wrote BENCH_kernels.json")


if __name__ == "__main__":
    main()
