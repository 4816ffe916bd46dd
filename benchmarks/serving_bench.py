"""Serving-tier benchmark: the multi-tenant ACAM service under load.

Sweeps tenant count x scheduler micro-batch size and measures the service
end to end — admission, cross-tenant micro-batching (one fused classify
dispatch per tick), the confidence cascade, and paper §V-D energy
attribution — emitting ``BENCH_serving.json`` so the serving trajectory is
tracked PR over PR alongside ``BENCH_kernels.json``.

On this CPU container the fused kernels run in Pallas interpret mode, so
requests/s is a correctness-path number, not a TPU number; the JSON records
``backend``/``interpret`` to keep runs distinguishable. Escalation rate and
nJ/request are backend-independent.

BENCH_serving.json schema::

    {"backend": "cpu" | "tpu",
     "interpret": bool,
     "entries": [
       {"tenants": 8, "slots": 256, "requests": 1024,
        "classes": 10,                # classes per synthetic tenant
        "matching_backend": "default",  # or the pinned engine backend
                                        # ("device" = RRAM-physics row)
        "bank_sharding": 1,           # super-bank class-row shards (model
                                      # axis size; 1 = replicated bank)
        "requests_per_s": ...,        # completed / service busy time
        "latency_p50_ms": ..., "latency_p99_ms": ...,
        "escalation_rate": ...,       # cascade escalations / requests
        "nj_per_request": ...,        # E_backend (+ E_frontend if escalated)
        "occupancy": ...,             # mean batch fill fraction
        "classify_dispatches": ...}]}

The **bank-scaling sweep** (`bank_scaling_sweep`) grows tenants x classes
and, when ``REPRO_FORCE_MESH=DxM`` provides a forced host mesh, measures
every point replicated AND bank-sharded — the `bank_sharding` field is how
BENCH json tracks the replicated-vs-sharded crossover as the super-bank
outgrows one device. (On this CPU container both run through Pallas
interpret, so the sharded rows are a correctness-path number; the
crossover itself is a TPU measurement.)

**Resilience rows** (`benchmarks/traces.py` harness) run with the flight
recorder's JSONL event log armed and RE-DERIVE their headline numbers
from it rather than poking service internals: ``"trace": "burst"``
replays a seeded bursty Zipf trace with the overload policy armed and adds
``p99_burst_ms`` / ``p99_calm_ms`` / ``shed_rate`` / ``shed_intervals``
(shed_on..shed_off episodes reconstructed from the log, shed-tick counts
cross-checked against the registry); ``"trace": "chaos"`` kills the
service mid-trace, restores it from its durable snapshot, asserts
bit-identity against a clean build and adds ``recovery_ms`` (the log's
``restore`` event) / ``lost_in_flight`` (queue depth on the dead
incarnation's last ``tick`` line). ``--chaos`` runs only the chaos smoke
and appends its row to an existing ``BENCH_serving.json``; with
``--telemetry-dir DIR`` it leaves ``DIR/events.jsonl`` +
``DIR/metrics.prom`` behind for `python -m repro.obs.export` validation
(the CI telemetry-smoke job).

The **telemetry-overhead row** (`telemetry_overhead_bench`) serves one
identical stream twice — no sink and spans off / the JSONL sink and
every serving span on — asserts preds/margins/escalations are bit-identical
either way, and records ``telemetry_overhead_pct`` (the tests hold the
same comparison under 5%).

The **mega-kernel row** (`megakernel_bench`, name ``serving_megakernel``)
uses the same twice-served protocol to price the resident serve kernel:
``serve_fusion="compose"`` (the pre-fusion tick) vs ``"mega"`` (ONE
pallas_call per tick) at tenants=8, slots=32, bit-identity asserted,
``megakernel_speedup_pct`` + both us/request medians recorded.

The **LM semantic-cache rows** (`lm_cache_bench`) price the ACAM tier as
a router in front of the continuous-batching decode engine:
``serving_lm_decode_only`` (marker ``lm_baseline``) is the bare
`serve.Engine` over the prompt set; ``serving_lm_cache_h{0,50,90}``
(marker ``hit_rate``) serve a measured window with EXACTLY that fraction
of warm-template repeats through `repro.serve.semantic_cache`. Each row
records the amortisation-bounded mean ratios (``mean_speedup`` /
``mean_energy_ratio``, ceiling 1/(1-h) — ~10x at h=0.9) next to the
hit-path ratios (``hit_path_speedup`` / ``hit_path_energy_ratio``, the
paper's Eq. 14-vs-decode asymmetry). ``--lm-cache`` runs only this sweep
and appends/replaces its rows in an existing ``BENCH_serving.json``.

``--smoke`` restricts the sweep for CI. `run()` keeps the harness contract
used by benchmarks/run.py: a list of ``{"name", "us_per_call", "derived"}``
rows.
"""
from __future__ import annotations

import argparse
import json
import os

import numpy as np

TENANT_SWEEP = (1, 8, 64)
SLOT_SWEEP = (1, 256)
SMOKE_TENANTS = (1, 8)
SMOKE_SLOTS = (1, 64)
NUM_FEATURES = 64
NUM_CLASSES = 10


def make_spec(slots: int, *, requests: int, backend: str | None = None,
              bank_shards: int = 1, install_mesh: bool = False,
              telemetry_dir: str | None = None):
    """The bench's one `ServiceSpec`: every measurement constructs through
    the spec path (`HybridService.from_spec`), never the legacy keywords.
    Taus ride in explicit match-count units; the service converts to the
    backend's native margin units itself. ``telemetry_dir`` arms the
    flight recorder's JSONL event log (the resilience rows re-derive
    their numbers from it)."""
    from repro import match as match_lib
    from repro.match.config import EngineConfig
    from repro.serve import spec as spec_lib

    return spec_lib.ServiceSpec(
        registry=spec_lib.RegistrySpec(
            num_features=NUM_FEATURES,
            initial_classes=spec_lib.aligned_classes(bank_shards)),
        engine=EngineConfig(backend=backend or match_lib.default_backend(),
                            margin=True),
        mesh=spec_lib.MeshSpec(bank_shards=bank_shards,
                               install=install_mesh),
        scheduler=spec_lib.SchedulerSpec(slots=slots),
        cascade=spec_lib.CascadeSpec(tau=8.0, tau_units="count",
                                     max_queue=max(requests, 4096)),
        obs=spec_lib.ObsSpec(telemetry_dir=telemetry_dir),
    )


def bench_service(tenants: int, slots: int, *, requests: int | None = None,
                  seed: int = 0, backend: str | None = None,
                  classes: int = NUM_CLASSES, bank_shards: int | None = None,
                  install_mesh: bool = False) -> dict:
    """Serve a mixed-tenant burst through a fresh spec-built service.

    ``bank_shards=None`` keeps the historical behaviour of aligning to
    whatever mesh is installed when this runs (`bank_scaling_sweep`
    toggles it); an explicit value + ``install_mesh=True`` lets the spec
    own the mesh end to end.
    """
    from repro import match as match_lib
    from repro.serve import acam_service as svc_lib
    from repro.serve.control import HybridService

    requests = requests or max(4 * slots, 128)
    if bank_shards is None:
        bank_shards = match_lib.bank_shards_in_mesh()
    svc = HybridService.from_spec(make_spec(
        slots, requests=requests, backend=backend, bank_shards=bank_shards,
        install_mesh=install_mesh))
    protos = []
    for t in range(tenants):
        bank, head, p = svc_lib.make_synthetic_tenant(
            seed * 1000 + t, num_classes=classes,
            num_features=NUM_FEATURES)
        svc.register_tenant(f"t{t}", bank, head=head)
        protos.append(p)

    rng = np.random.RandomState(seed)
    tenant_of = rng.randint(0, tenants, size=requests)
    reqs = []
    for i, t in enumerate(tenant_of):
        feats, _ = svc_lib.sample_tenant_queries(seed + i, protos[t], 1,
                                                 noise=0.8)
        reqs.append(svc_lib.ClassifyRequest(f"t{t}", feats[0]))

    # warmup tick compiles the fused dispatch so requests/s measures the
    # steady state, matching how a long-lived service behaves
    svc.serve(reqs[:1])
    svc.reset_metrics()
    responses = svc.serve(reqs)
    assert len(responses) == requests
    m = svc.metrics()
    return {
        "tenants": tenants,
        "slots": slots,
        "requests": requests,
        "classes": classes,
        "matching_backend": backend or "default",
        "bank_sharding": svc.registry.bank_shards,
        "requests_per_s": m["requests_per_s"],
        "latency_p50_ms": m["latency_p50_ms"],
        "latency_p99_ms": m["latency_p99_ms"],
        "escalation_rate": m["escalation_rate"],
        "nj_per_request": m["nj_per_request"],
        "occupancy": m["occupancy"],
        "classify_dispatches": m["classify_dispatches"],
    }


def _report(e):
    print(f"tenants={e['tenants']:3d} classes={e['classes']:3d} "
          f"slots={e['slots']:4d} shards={e['bank_sharding']} "
          f"backend={e['matching_backend']:9s}: "
          f"{e['requests_per_s']:9.1f} req/s, "
          f"escalation {e['escalation_rate']:.3f}, "
          f"{e['nj_per_request']:.2f} nJ/req, "
          f"occupancy {e['occupancy']:.2f}")


def bank_scaling_sweep(*, smoke: bool = False, seed: int = 0) -> list[dict]:
    """Grow the super-bank (tenants x classes) replicated vs bank-sharded.

    The sharded points need a model mesh axis: when ``REPRO_FORCE_MESH``
    provides forced host devices the sweep installs the mesh around each
    sharded measurement (`repro.distributed.forcemesh`); without it only
    the replicated rows are emitted.
    """
    from repro.distributed import context, forcemesh

    grid = ((4, 16), (8, 32)) if smoke else ((8, 16), (32, 32), (64, 48))
    slots = min(SLOT_SWEEP[-1], 64)
    spec = forcemesh.env_spec()
    entries = []
    for tenants, classes in grid:
        requests = 2 * slots if smoke else 4 * slots
        context.clear()
        entries.append(bench_service(tenants, slots, requests=requests,
                                     seed=seed, classes=classes))
        _report(entries[-1])
        if spec is None:
            continue
        try:
            forcemesh.install(spec)
        except RuntimeError as e:
            print(f"skipping sharded rows: {e}")
            spec = None
            continue
        entries.append(bench_service(tenants, slots, requests=requests,
                                     seed=seed, classes=classes))
        _report(entries[-1])
        context.clear()
    return entries


def reshard_bench(*, seed: int = 0, tenants: int = 8, slots: int = 64,
                  to_shards: int = 2) -> dict | None:
    """Live-reshard downtime: boot a spec-built service at ``bank_shards=1``
    (mesh owned by the spec), load it, then `reconfigure` to ``to_shards``
    mid-stream and measure the drain->resume wall time. Asserts the
    post-reshard scheduler keeps ONE sharded dispatch per tick and that
    predictions are bit-identical across the transition.

    Needs a forced host mesh (``REPRO_FORCE_MESH=DxM`` with D*M divisible
    by ``to_shards``); returns None (with a note) when unavailable.
    """
    import jax

    from repro import match as match_lib
    from repro.distributed import context, forcemesh
    from repro.serve import acam_service as svc_lib
    from repro.serve.control import HybridService

    if forcemesh.env_spec() is None or len(jax.devices()) % to_shards:
        print("skipping reshard row: set REPRO_FORCE_MESH (devices must "
              f"divide {to_shards})")
        return None
    context.clear()
    requests = 4 * slots
    svc = HybridService.from_spec(make_spec(slots, requests=requests,
                                            bank_shards=1,
                                            install_mesh=True))
    protos = []
    for t in range(tenants):
        bank, head, p = svc_lib.make_synthetic_tenant(
            seed * 1000 + t, num_classes=NUM_CLASSES,
            num_features=NUM_FEATURES)
        svc.register_tenant(f"t{t}", bank, head=head)
        protos.append(p)
    rng = np.random.RandomState(seed)
    tenant_of = rng.randint(0, tenants, size=requests)
    reqs = []
    for i, t in enumerate(tenant_of):
        feats, _ = svc_lib.sample_tenant_queries(seed + i, protos[t], 1,
                                                 noise=0.8)
        reqs.append(svc_lib.ClassifyRequest(f"t{t}", feats[0]))
    before = [(r.tenant_id, r.pred, r.escalated, round(r.margin, 6))
              for r in svc.serve(reqs)]

    # mid-stream: enqueue a burst, reconfigure (drains it), resume sharded
    for req in reqs[:slots]:
        svc.submit(req)
    report = svc.reconfigure(svc.spec._replace(
        mesh=svc.spec.mesh._replace(bank_shards=to_shards)))
    assert len(report.drained) == slots, "drain lost queued work"
    assert svc.registry.bank_shards == to_shards
    assert match_lib.bank_shards_in_mesh() == to_shards

    # the tick's shapes now derive a bank-sharded plan: the scheduler's ONE
    # dispatch per tick executes 2D-sharded (batch over data, class rows
    # over model) — this is the actual sharded-dispatch assertion, since
    # classify_dispatches == ticks holds by construction
    plan, _ = match_lib.plan_for(batch=slots,
                                 num_classes=svc.registry.capacity_classes)
    assert plan.bank_shards == to_shards, plan
    svc.reset_metrics()
    after = [(r.tenant_id, r.pred, r.escalated, round(r.margin, 6))
             for r in svc.serve(reqs)]
    assert after == before, "reshard changed served results"
    m = svc.metrics()
    assert m["classify_dispatches"] == m["ticks"], m
    context.clear()
    entry = {
        "tenants": tenants, "slots": slots, "requests": requests,
        "classes": NUM_CLASSES, "matching_backend": "default",
        "bank_sharding": to_shards,
        "reshard_downtime_ms": round(report.downtime_s * 1e3, 3),
        "tenants_moved": report.tenants_moved,
        "requests_per_s": m["requests_per_s"],
        "latency_p50_ms": m["latency_p50_ms"],
        "latency_p99_ms": m["latency_p99_ms"],
        "escalation_rate": m["escalation_rate"],
        "nj_per_request": m["nj_per_request"],
        "occupancy": m["occupancy"],
        "classify_dispatches": m["classify_dispatches"],
    }
    print(f"reshard 1->{to_shards}: downtime "
          f"{entry['reshard_downtime_ms']:.1f} ms "
          f"({entry['tenants_moved']} tenants moved, bit-identical, "
          f"{m['classify_dispatches']} sharded dispatches)")
    return entry


def telemetry_overhead_bench(*, smoke: bool = False, seed: int = 0) -> dict:
    """The flight recorder's tax: serve the IDENTICAL request stream twice
    — no JSONL sink and the serving spans off, then the full recorder
    (event log on, every serving span on) — and record the per-request
    overhead.
    Doubles as the purity check: preds/margins/escalations must be
    bit-identical either way (telemetry observes, never steers)."""
    import tempfile

    from repro.serve import acam_service as svc_lib
    from repro.serve import spec as spec_lib
    from repro.serve.control import HybridService

    tenants, slots = 8, 64
    requests = 256 if smoke else 1024

    def build(obs):
        svc = HybridService.from_spec(make_spec(
            slots, requests=requests)._replace(obs=obs))
        protos = []
        for t in range(tenants):
            bank, head, p = svc_lib.make_synthetic_tenant(
                seed * 1000 + t, num_classes=NUM_CLASSES,
                num_features=NUM_FEATURES)
            svc.register_tenant(f"t{t}", bank, head=head)
            protos.append(p)
        rng = np.random.RandomState(seed)
        reqs = []
        for i, t in enumerate(rng.randint(0, tenants, size=requests)):
            feats, _ = svc_lib.sample_tenant_queries(seed + i, protos[t], 1,
                                                     noise=0.8)
            reqs.append(svc_lib.ClassifyRequest(f"t{t}", feats[0]))
        # full-stream warmup: compiles EVERY bucketed batch shape the
        # measured passes will hit (a 1-request warmup leaves the first
        # run paying all the compiles and poisons the comparison)
        svc.serve(reqs)
        return svc, reqs

    def measure(svc, reqs):
        svc.reset_metrics()
        sig = [(r.tenant_id, r.pred, r.escalated, round(r.margin, 6))
               for r in svc.serve(reqs)]
        return svc.metrics(), sig

    # INTERLEAVED passes (base, telemetry, base, ...) so clock drift
    # across the run hits both arms equally, then the MEDIAN us/request
    # per arm: a one-sided hiccup (GC pause, slow JSONL flush) lands in
    # one pass of one arm and the median rejects it, where a min would
    # bias low and a mean would smear it in
    base_svc, base_reqs = build(spec_lib.ObsSpec())
    with tempfile.TemporaryDirectory() as td:
        tel_svc, tel_reqs = build(spec_lib.ObsSpec(telemetry_dir=td,
                                                   profile_annotations=True))
        base_us_all, tel_us_all = [], []
        base_sig = tel_sig = tel_m = None
        for _ in range(9):
            m, base_sig = measure(base_svc, base_reqs)
            base_us_all.append(1e6 / m["requests_per_s"])
            m, tel_sig = measure(tel_svc, tel_reqs)
            tel_us_all.append(1e6 / m["requests_per_s"])
            if tel_m is None or \
                    m["requests_per_s"] > tel_m["requests_per_s"]:
                tel_m = m
    assert tel_sig == base_sig, \
        "telemetry changed served results (must be pure observation)"
    base_us = float(np.median(base_us_all))
    tel_us = float(np.median(tel_us_all))
    entry = {
        "tenants": tenants, "slots": slots, "requests": requests,
        "classes": NUM_CLASSES, "matching_backend": "default",
        "bank_sharding": 1,
        "telemetry_overhead_pct": round(100.0 * (tel_us - base_us)
                                        / base_us, 2),
        "base_us_per_request": round(base_us, 3),
        "telemetry_us_per_request": round(tel_us, 3),
        "requests_per_s": tel_m["requests_per_s"],
        "latency_p50_ms": tel_m["latency_p50_ms"],
        "latency_p99_ms": tel_m["latency_p99_ms"],
        "escalation_rate": tel_m["escalation_rate"],
        "nj_per_request": tel_m["nj_per_request"],
        "occupancy": tel_m["occupancy"],
        "classify_dispatches": tel_m["classify_dispatches"],
    }
    print(f"telemetry overhead: {entry['telemetry_overhead_pct']:+.2f}% "
          f"({base_us:.1f} -> {tel_us:.1f} us/request, bit-identical "
          "results)")
    return entry


def megakernel_bench(*, smoke: bool = False, seed: int = 0) -> dict:
    """The resident serve mega-kernel's win over the composed tick.

    Serves the IDENTICAL request stream through two spec-built services
    that differ only in ``EngineConfig.serve_fusion`` — "compose" (the
    pre-megakernel jnp gather/shift + fused margins kernel + jnp tau
    compare) vs "mega" (gather, binarize, match, windowed margin and the
    escalation mask in ONE resident pallas_call) — at tenants=8, slots=32.
    Interleaved passes + per-arm median us/request, same protocol as
    `telemetry_overhead_bench`; preds/margins/escalations must be
    bit-identical (the fusion is a pure execution change)."""
    from repro.serve import acam_service as svc_lib
    from repro.serve.control import HybridService

    tenants, slots = 8, 32
    requests = 256 if smoke else 1024

    def build(serve_fusion):
        spec = make_spec(slots, requests=requests)
        spec = spec._replace(engine=spec.engine._replace(
            serve_fusion=serve_fusion))
        svc = HybridService.from_spec(spec)
        protos = []
        for t in range(tenants):
            bank, head, p = svc_lib.make_synthetic_tenant(
                seed * 1000 + t, num_classes=NUM_CLASSES,
                num_features=NUM_FEATURES)
            svc.register_tenant(f"t{t}", bank, head=head)
            protos.append(p)
        rng = np.random.RandomState(seed)
        reqs = []
        for i, t in enumerate(rng.randint(0, tenants, size=requests)):
            feats, _ = svc_lib.sample_tenant_queries(seed + i, protos[t], 1,
                                                     noise=0.8)
            reqs.append(svc_lib.ClassifyRequest(f"t{t}", feats[0]))
        svc.serve(reqs)  # full-stream warmup: compile every batch shape
        return svc, reqs

    def measure(svc, reqs):
        svc.reset_metrics()
        sig = [(r.tenant_id, r.pred, r.escalated, round(r.margin, 6))
               for r in svc.serve(reqs)]
        return svc.metrics(), sig

    comp_svc, comp_reqs = build("compose")
    mega_svc, mega_reqs = build("mega")
    comp_us_all, mega_us_all = [], []
    comp_sig = mega_sig = mega_m = None
    for _ in range(9):
        m, comp_sig = measure(comp_svc, comp_reqs)
        comp_us_all.append(1e6 / m["requests_per_s"])
        m, mega_sig = measure(mega_svc, mega_reqs)
        mega_us_all.append(1e6 / m["requests_per_s"])
        if mega_m is None or m["requests_per_s"] > mega_m["requests_per_s"]:
            mega_m = m
    assert mega_sig == comp_sig, \
        "mega-kernel changed served results (must be a pure fusion)"
    comp_us = float(np.median(comp_us_all))
    mega_us = float(np.median(mega_us_all))
    entry = {
        "tenants": tenants, "slots": slots, "requests": requests,
        "classes": NUM_CLASSES, "matching_backend": "default",
        "bank_sharding": 1,
        "megakernel_speedup_pct": round(100.0 * (comp_us - mega_us)
                                        / comp_us, 2),
        "compose_us_per_request": round(comp_us, 3),
        "mega_us_per_request": round(mega_us, 3),
        "requests_per_s": mega_m["requests_per_s"],
        "latency_p50_ms": mega_m["latency_p50_ms"],
        "latency_p99_ms": mega_m["latency_p99_ms"],
        "escalation_rate": mega_m["escalation_rate"],
        "nj_per_request": mega_m["nj_per_request"],
        "occupancy": mega_m["occupancy"],
        "classify_dispatches": mega_m["classify_dispatches"],
    }
    print(f"serve mega-kernel: {entry['megakernel_speedup_pct']:+.2f}% "
          f"({comp_us:.1f} -> {mega_us:.1f} us/request, bit-identical "
          "results)")
    return entry


def _traces():
    """Import benchmarks/traces.py under both invocation styles (package
    via benchmarks.run, script dir on sys.path via `python
    benchmarks/serving_bench.py`)."""
    try:
        from benchmarks import traces
    except ImportError:
        import traces
    return traces


def burst_bench(*, smoke: bool = False, seed: int = 0) -> dict:
    """p99-under-burst + shed rate: replay a seeded bursty Zipf trace
    against a service whose overload policy is armed (``shed_queue``), so
    burst phases push the queue past the threshold and ticks degrade to
    ACAM-only answers. The row tracks burst-phase p99 separately from calm
    p99 and records how much of the traffic was shed — the shed numbers
    are RE-DERIVED from the flight recorder's event log (tick lines +
    shed_on/shed_off flips) and cross-checked against the registry."""
    import tempfile

    from repro.obs import read_events
    from repro.serve.control import HybridService

    traces = _traces()
    slots = 32
    cfg = traces.TraceConfig(
        seed=seed, tenants=8, classes=NUM_CLASSES,
        num_features=NUM_FEATURES, requests=256 if smoke else 1024,
        burst=128, calm=8, phase_ticks=3)
    with tempfile.TemporaryDirectory() as td:
        spec = make_spec(slots, requests=cfg.requests, telemetry_dir=td)
        spec = spec._replace(
            cascade=spec.cascade._replace(shed_queue=2 * slots))
        svc = HybridService.from_spec(spec)
        pool = traces.TenantPool(cfg)
        pool.register_all(svc)
        svc.serve([pool.request(0, seed + 1)])  # compile warmup
        svc.reset_metrics()
        svc, stats = traces.replay(svc, traces.make_trace(cfg), pool)
        m = svc.metrics()
        # the black box is the source of truth for the shed story: shed
        # ticks are tick lines that dispatched in shed mode, shed requests
        # sum over the same lines, episodes come from the flip events
        events = read_events(svc.obs.events.path)
        tick_lines = [e for e in events if e["kind"] == "tick"]
        shed_ticks = sum(1 for e in tick_lines
                         if e["shed_mode"] and e["fill"])
        shed_requests = sum(e["shed"] for e in tick_lines)
        shed_intervals = sum(1 for e in events if e["kind"] == "shed_on")
    assert shed_ticks == m["load_shed_ticks"], \
        (shed_ticks, m["load_shed_ticks"])
    assert shed_requests == m["shed"], (shed_requests, m["shed"])
    entry = {
        "tenants": cfg.tenants, "slots": slots, "requests": cfg.requests,
        "classes": cfg.classes, "matching_backend": "default",
        "bank_sharding": svc.registry.bank_shards,
        "trace": "burst",
        "p99_burst_ms": stats["p99_burst_ms"],
        "p99_calm_ms": stats["p99_calm_ms"],
        "shed_rate": round(shed_requests / max(m["completed"], 1), 4),
        "load_shed_ticks": shed_ticks,
        "shed_intervals": shed_intervals,
        "requests_per_s": m["requests_per_s"],
        "latency_p50_ms": m["latency_p50_ms"],
        "latency_p99_ms": m["latency_p99_ms"],
        "escalation_rate": m["escalation_rate"],
        "nj_per_request": m["nj_per_request"],
        "occupancy": m["occupancy"],
        "classify_dispatches": m["classify_dispatches"],
    }
    print(f"burst trace: p99 burst {entry['p99_burst_ms']} ms vs calm "
          f"{entry['p99_calm_ms']} ms, shed rate {entry['shed_rate']:.3f} "
          f"({entry['load_shed_ticks']} shed ticks over "
          f"{entry['shed_intervals']} episodes, from the event log)")
    return entry


def chaos_bench(*, smoke: bool = False, seed: int = 0,
                telemetry_dir: str | None = None) -> dict:
    """Kill-and-restore recovery time: replay a trace with a mid-stream
    kill injected (the service object is dropped — in-flight queue lost,
    durable snapshot survives) and measure snapshot-restore-to-serving
    wall time. Asserts the restored service is bit-identical to a clean
    build on a fixed probe set. Under ``REPRO_FORCE_MESH`` the service
    runs bank-sharded (spec-owned mesh), so the restore also exercises the
    mesh-reinstall path.

    The row's resilience numbers come out of the flight recorder's JSONL
    event log — the snapshot rides the ``ObsSpec`` so the restored
    incarnation reopens the SAME ``events.jsonl`` in append mode:
    ``recovery_ms`` is the ``restore`` event's duration, ``lost_in_flight``
    the queue depth on the dead incarnation's last ``tick`` line, both
    cross-checked against the replay harness. ``telemetry_dir`` keeps the
    log (plus a rendered ``metrics.prom``) on disk for the CI
    telemetry-smoke job's `python -m repro.obs.export` pass."""
    import tempfile

    import jax

    from repro.checkpoint.checkpointer import Checkpointer
    from repro.distributed import context, forcemesh
    from repro.obs import read_events, write_prometheus
    from repro.serve.control import HybridService

    traces = _traces()
    sharded = forcemesh.env_spec() is not None \
        and len(jax.devices()) % 2 == 0
    if sharded:
        context.clear()
    slots = 32
    # phase_ticks=1 keeps a standing queue, so the kill catches (and the
    # lost_in_flight row reports) genuinely in-flight work
    cfg = traces.TraceConfig(
        seed=seed, tenants=8, classes=NUM_CLASSES,
        num_features=NUM_FEATURES, requests=192 if smoke else 768,
        burst=64, calm=8, phase_ticks=1)
    with tempfile.TemporaryDirectory() as td:
        tel_dir = telemetry_dir or os.path.join(td, "telemetry")
        spec = make_spec(slots, requests=cfg.requests,
                         bank_shards=2 if sharded else 1,
                         install_mesh=sharded, telemetry_dir=tel_dir)
        ckpt = Checkpointer(os.path.join(td, "ckpt"), keep=3)
        svc = HybridService.from_spec(spec)
        pool = traces.TenantPool(cfg)
        pool.register_all(svc)
        svc.serve([pool.request(0, seed + 1)])  # compile warmup
        svc.reset_metrics()
        chaos = traces.ChaosPlan(ckpt=ckpt, snapshot_every=2,
                                 kill_at_tick=3)
        svc, stats = traces.replay(svc, traces.make_trace(cfg), pool,
                                   chaos=chaos)
        assert stats["killed"] and stats["recovery_ms"] is not None
        m = svc.metrics()

        # restored-vs-clean bit-identity probe: the restored incarnation
        # must serve exactly what a never-killed service would (the clean
        # build runs telemetry-sinks-off — also proving the kill/restore
        # story is identical with and without the recorder's sinks)
        probe = [pool.request(t % cfg.tenants, 999_000 + t)
                 for t in range(64)]
        sig = [(r.tenant_id, r.pred, r.escalated, round(r.margin, 6))
               for r in svc.serve(probe)]
        clean = HybridService.from_spec(spec._replace(
            obs=spec.obs._replace(telemetry_dir=None)))
        pool.register_all(clean)
        clean_sig = [(r.tenant_id, r.pred, r.escalated, round(r.margin, 6))
                     for r in clean.serve(probe)]
        assert sig == clean_sig, "restored service diverged from clean build"

        # re-derive the resilience numbers from the black box (validating
        # every line on the way): one restore event, snapshots before the
        # kill, and the dead incarnation's final tick line still readable
        events = read_events(svc.obs.events.path)
        kills = [i for i, e in enumerate(events) if e["kind"] == "restore"]
        assert len(kills) == 1, f"expected one restore event, got {kills}"
        assert any(e["kind"] == "snapshot" for e in events[:kills[0]]), \
            "no durable snapshot event before the kill"
        pre_ticks = [e for e in events[:kills[0]] if e["kind"] == "tick"]
        lost = pre_ticks[-1]["queue_depth"]
        assert lost == stats["lost_in_flight"], \
            (lost, stats["lost_in_flight"])
        recovery_ms = events[kills[0]]["duration_ms"]
        if telemetry_dir:
            write_prometheus(svc.obs.registry,
                             os.path.join(tel_dir, "metrics.prom"))
    if sharded:
        context.clear()
    entry = {
        "tenants": cfg.tenants, "slots": slots, "requests": cfg.requests,
        "classes": cfg.classes, "matching_backend": "default",
        "bank_sharding": 2 if sharded else 1,
        "trace": "chaos",
        "recovery_ms": recovery_ms,
        "lost_in_flight": lost,
        "requests_per_s": m["requests_per_s"],
        "latency_p50_ms": m["latency_p50_ms"],
        "latency_p99_ms": m["latency_p99_ms"],
        "escalation_rate": m["escalation_rate"],
        "nj_per_request": m["nj_per_request"],
        "occupancy": m["occupancy"],
        "classify_dispatches": m["classify_dispatches"],
    }
    print(f"chaos trace: killed mid-stream, restored bit-identical in "
          f"{entry['recovery_ms']:.1f} ms "
          f"({entry['lost_in_flight']} in-flight lost, "
          f"bank_shards={entry['bank_sharding']}; numbers from the "
          "event log)")
    return entry


def autopilot_bench(*, smoke: bool = True, seed: int = 0,
                    telemetry_dir: str | None = None) -> dict | None:
    """The self-driving fleet row: a bursty Zipf churn trace served with
    the `repro.fleet` autopilot on, under a forced host mesh.

    Tenants arrive via a `FleetManifest` apply; the trace's churn ops are
    expressed as further manifest applies (evict the coldest tenant, then
    the manifest that brings it back). The telemetry policy escalates
    ``bank_shards`` under row pressure through the DOUBLE-BUFFERED rolling
    reshard — shadow bank built between ticks, flipped at a tick boundary,
    no drain — and the row prices that flip (``flip_downtime_ms``, from
    the event log's ``buffer_flip`` lines) against the drained
    ``reconfigure`` alternative measured in-situ on an identical service
    (``drained_downtime_ms``). Asserts: at least one policy-initiated
    flip; flip downtime strictly below the drained downtime; served
    results bit-identical to a pinned-spec run of the same trace (policy
    transitions are pure execution changes); and every logged
    ``policy_decision`` replays to the same action from its frozen view
    alone. Needs ``REPRO_FORCE_MESH`` (even device count); returns None
    with a note when unavailable."""
    import tempfile

    import jax

    from repro.distributed import context, forcemesh
    from repro.fleet import (Autopilot, FleetManifest, PolicySpec,
                             RegistryView, TenantSpec, explain,
                             should_compact)
    from repro.obs import read_events, write_prometheus
    from repro.serve.control import HybridService

    if forcemesh.env_spec() is None or len(jax.devices()) % 2:
        print("skipping autopilot row: set REPRO_FORCE_MESH (even device "
              "count)")
        return None
    traces = _traces()
    slots = 16
    # 6 tenants x 64 classes = 384 registered rows: 0.75 of the doubled
    # 512-row capacity, exactly the policy's row-pressure threshold
    cfg = traces.TraceConfig(
        seed=seed, tenants=6, classes=64, num_features=NUM_FEATURES,
        requests=160 if smoke else 640, burst=48, calm=8, phase_ticks=2,
        churn_every=3)
    manifest = FleetManifest(tenants=tuple(
        TenantSpec(tenant_id=f"t{t}", seed=cfg.seed * 1000 + t,
                   num_classes=cfg.classes)
        for t in range(cfg.tenants)))
    coldest = int(np.argmin(traces.zipf_weights(cfg)))
    without_cold = FleetManifest(tenants=tuple(
        t for t in manifest.tenants if t.tenant_id != f"t{coldest}"))
    pool = traces.TenantPool(cfg)
    trace = traces.make_trace(cfg)

    def serve_trace(svc, pilot):
        sig = []
        for op in trace:
            kind = op[0]
            if kind == "submit":
                svc.submit(pool.request(op[1], op[2]))
            elif kind == "evict":
                svc.apply_manifest(without_cold)
            elif kind == "register":
                svc.apply_manifest(manifest)
            elif kind == "tick":
                sig.extend((r.tenant_id, r.pred, r.escalated,
                            round(r.margin, 6)) for r in svc.step())
                if pilot is not None:
                    pilot.observe_tick()
                    sig.extend((r.tenant_id, r.pred, r.escalated,
                                round(r.margin, 6))
                               for r in pilot.take_drained())
        return sig

    context.clear()
    with tempfile.TemporaryDirectory() as td:
        tel_dir = telemetry_dir or os.path.join(td, "telemetry")
        spec = make_spec(slots, requests=cfg.requests, bank_shards=1,
                         install_mesh=True, telemetry_dir=tel_dir)
        svc = HybridService.from_spec(spec)
        svc.apply_manifest(manifest)
        svc.serve([pool.request(0, seed + 1)])  # compile warmup
        svc.reset_metrics()
        pilot = Autopilot(svc, policy=PolicySpec(interval=4, hysteresis=2,
                                                 cooldown=8))
        sig = serve_trace(svc, pilot)
        m = svc.metrics()

        # the black box is the source of truth: flips, decisions and
        # manifest applies all come off the JSONL event log, and every
        # decision must replay from its own frozen view
        events = read_events(svc.obs.events.path)
        flips = [e for e in events if e["kind"] == "buffer_flip"]
        decisions = [e for e in events if e["kind"] == "policy_decision"]
        applies = sum(1 for e in events if e["kind"] == "manifest_apply")
        assert flips, "autopilot never executed a double-buffered reshard"
        assert applies >= 2, "churn never went through the manifest path"
        for e in decisions:
            view = RegistryView.from_dict(e["view"])
            act = explain(view, pilot.policy)[0]
            if act == "hold" and should_compact(view, pilot.policy):
                act = "compact"
            assert act == e["action"], (act, e["action"])
        if telemetry_dir:
            write_prometheus(svc.obs.registry,
                             os.path.join(tel_dir, "metrics.prom"))

        # pinned-spec control arm: same trace, same manifest churn, no
        # autopilot — the policy's transitions must not change results
        context.clear()
        pinned = HybridService.from_spec(spec._replace(
            obs=spec.obs._replace(telemetry_dir=None)))
        pinned.apply_manifest(manifest)
        pinned.serve([pool.request(0, seed + 1)])
        pinned.reset_metrics()
        pin_sig = serve_trace(pinned, None)
        assert sig == pin_sig, "autopilot changed served results"

        # the drained alternative, priced in-situ: identical service,
        # full queue, quiesce-and-reshard 1->2
        context.clear()
        drained_svc = HybridService.from_spec(spec._replace(
            obs=spec.obs._replace(telemetry_dir=None)))
        drained_svc.apply_manifest(manifest)
        warm = [pool.request(t % cfg.tenants, 777_000 + t)
                for t in range(4 * slots)]
        drained_svc.serve(warm)  # warm every bucketed shape
        for r in warm[:slots]:
            drained_svc.submit(r)
        report = drained_svc.reconfigure(drained_svc.spec._replace(
            mesh=drained_svc.spec.mesh._replace(bank_shards=2)))
        assert len(report.drained) == slots
        drained_ms = round(report.downtime_s * 1e3, 3)
        context.clear()

    flip_ms = max(e["flip_ms"] for e in flips)
    assert flip_ms < drained_ms, \
        f"flip {flip_ms} ms not below drained {drained_ms} ms"
    entry = {
        "tenants": cfg.tenants, "slots": slots, "requests": cfg.requests,
        "classes": cfg.classes, "matching_backend": "default",
        "bank_sharding": svc.registry.bank_shards,
        "trace": "autopilot",
        "flip_downtime_ms": flip_ms,
        "drained_downtime_ms": drained_ms,
        "policy_flips": len(flips),
        "policy_decisions": len(decisions),
        "manifest_applies": applies,
        "requests_per_s": m["requests_per_s"],
        "latency_p50_ms": m["latency_p50_ms"],
        "latency_p99_ms": m["latency_p99_ms"],
        "escalation_rate": m["escalation_rate"],
        "nj_per_request": m["nj_per_request"],
        "occupancy": m["occupancy"],
        "classify_dispatches": m["classify_dispatches"],
    }
    print(f"autopilot trace: {len(flips)} rolling reshards to "
          f"bank_shards={entry['bank_sharding']}, flip "
          f"{flip_ms:.2f} ms vs drained {drained_ms:.1f} ms "
          f"({len(decisions)} policy decisions, {applies} manifest "
          "applies, bit-identical to the pinned run)")
    return entry


def lm_cache_bench(*, smoke: bool = False, seed: int = 0) -> list[dict]:
    """The ACAM semantic cache in front of LM decode, swept over hit rate.

    One ``serving_lm_decode_only`` baseline row (the bare continuous-
    batching `Engine` over the identical prompt set) plus one
    ``serving_lm_cache_h{0,50,90}`` row per target hit rate: the bank is
    warmed with a fixed prompt pool, then a measured window of R requests
    containing EXACTLY round(R*h) Zipf-weighted repeats (hits) and
    R - round(R*h) fresh prompts (decode misses) is served through
    `repro.serve.semantic_cache.SemanticCacheService`.

    Honesty note on the means: with per-miss decode cost D and per-hit
    cost o << D, mean-vs-decode-only improvements are amortisation-bounded
    by 1/(1-h) — ~10x at h=0.9 no matter how cheap the hit path is. The
    rows therefore record BOTH the mean ratios (``mean_speedup``,
    ``mean_energy_ratio``, ceiling 1/(1-h)) and the hit-path ratios
    (``hit_path_speedup``, ``hit_path_energy_ratio`` — the paper's
    E_backend-vs-frontend asymmetry, Eq. 14 nJ against per-token decode
    energy, orders of magnitude). Both engines run the SMOKE arch in
    interpret mode, which deflates the decode side of every latency
    ratio by orders of magnitude — treat ``hit_path_speedup`` as a hard
    lower bound; the energy ratios are modelled and arch-scaled, so
    they transfer."""
    import time as time_mod

    import jax

    from repro import configs
    from repro.models import lm as lm_mod
    from repro.serve import spec as spec_lib
    from repro.serve.engine import Engine, Request
    from repro.serve.semantic_cache import (PromptRequest,
                                            SemanticCacheService)

    arch = "tinyllama-1.1b"
    requests = 20 if smoke else 60
    pool_size, plen, max_new, slots = 8, 12, 8, 16
    cfg = configs.get(arch, smoke=True)
    params = lm_mod.init_params(jax.random.PRNGKey(seed), cfg)
    rng = np.random.RandomState(seed)
    pool = [rng.randint(0, cfg.vocab, size=plen).astype(np.int32)
            for _ in range(pool_size)]
    fresh_pool = [rng.randint(0, cfg.vocab, size=plen).astype(np.int32)
                  for _ in range(requests)]
    zipf = 1.0 / np.arange(1, pool_size + 1) ** 1.2
    zipf /= zipf.sum()

    def measured_trace(h: float) -> tuple[list[np.ndarray], int]:
        n_hit = int(round(requests * h))
        prompts = [pool[i] for i in rng.choice(pool_size, size=n_hit,
                                               p=zipf)]
        prompts += fresh_pool[:requests - n_hit]
        order = np.random.RandomState(seed + 1).permutation(requests)
        return [prompts[i] for i in order], n_hit

    # decode-only baseline: the bare engine over a representative window.
    # The warmup pass MUST be full-size: with > batch_size queued
    # requests, continuous batching joins prefill at padded lengths, and
    # those shapes compile the first time they appear — a group-of-4
    # warmup alone leaves ~seconds of compilation inside the timed pass.
    base_eng = Engine(cfg, params, batch_size=4, max_len=64, seed=seed)
    base_prompts, _ = measured_trace(0.0)
    reqs = [Request(prompt=p, max_new_tokens=max_new)
            for p in base_prompts]
    base_eng.generate(reqs)  # compile warmup, join shapes included
    t0 = time_mod.perf_counter()
    base_eng.generate([Request(prompt=p, max_new_tokens=max_new)
                       for p in base_prompts])
    base_us = (time_mod.perf_counter() - t0) * 1e6 / requests
    from repro.core.energy import lm_decode_energy

    base_nj = lm_decode_energy(cfg.active_param_count(),
                               plen + max_new) * 1e9
    entries = [{
        "tenants": 1, "slots": 4, "requests": requests, "classes": 0,
        "matching_backend": "default", "bank_sharding": 1,
        "arch": cfg.name, "lm_baseline": True,
        "us_per_request": round(base_us, 1),
        "decode_energy_nj": round(base_nj, 3),
        "requests_per_s": round(1e6 / base_us, 2),
        "latency_p50_ms": round(base_us / 1e3, 3),
        "latency_p99_ms": round(base_us / 1e3, 3),
        "escalation_rate": 1.0, "nj_per_request": round(base_nj, 3),
        "occupancy": 0.0, "classify_dispatches": 0,
    }]
    print(f"lm decode-only baseline: {base_us:.0f} us/request, "
          f"{base_nj:.1f} nJ/request modelled")

    eng = Engine(cfg, params, batch_size=4, max_len=64, seed=seed)
    for h in (0.0, 0.5, 0.9):
        spec = spec_lib.ServiceSpec(
            registry=spec_lib.RegistrySpec(num_features=NUM_FEATURES),
            scheduler=spec_lib.SchedulerSpec(slots=slots),
            cascade=spec_lib.CascadeSpec(backend="lm", tau=8.0,
                                         tau_units="count",
                                         max_queue=4096),
            router=spec_lib.RouterSpec(
                max_templates=pool_size + requests + slots,
                response_capacity=4096),
            mesh=spec_lib.MeshSpec(install=False))
        svc = SemanticCacheService.from_spec(spec, engine=eng)
        svc.add_tenant("edge-0")
        # warm: admit the pool one-by-one, then one slots-wide all-miss
        # burst so the worst-case escalation join shapes are compiled
        # before the measured window (same trap as the baseline above)
        for p in pool:
            svc.serve_prompts([PromptRequest("edge-0", p,
                                             max_new_tokens=max_new)])
        warm = [rng.randint(0, cfg.vocab, size=plen).astype(np.int32)
                for _ in range(slots)]
        svc.serve_prompts(PromptRequest("edge-0", p,
                                        max_new_tokens=max_new)
                          for p in warm)
        svc.reset_metrics()
        prompts, n_hit = measured_trace(h)
        out = []
        t0 = time_mod.perf_counter()
        for i in range(0, requests, slots):
            out.extend(svc.serve_prompts(
                PromptRequest("edge-0", p, max_new_tokens=max_new)
                for p in prompts[i:i + slots]))
        us = (time_mod.perf_counter() - t0) * 1e6 / requests
        hits = [r for r in out if r.cache_hit]
        assert len(hits) == n_hit, (len(hits), n_hit)  # exact hit rate
        m = svc.metrics()
        # hit-path cost, isolated: an all-hit probe burst AFTER the
        # window (in-window hit latencies are tick latencies — they
        # include the co-scheduled misses' decode time, which is the
        # amortisation story, not the hit-path story)
        probe = [pool[i % pool_size] for i in range(slots)]
        t0 = time_mod.perf_counter()
        probed = svc.serve_prompts(
            PromptRequest("edge-0", p, max_new_tokens=max_new)
            for p in probe)
        hit_us = (time_mod.perf_counter() - t0) * 1e6 / slots
        assert all(r.cache_hit for r in probed), "probe burst must hit"
        hit_nj = float(np.median([r.energy_j for r in probed])) * 1e9
        entry = {
            "tenants": 1, "slots": slots, "requests": requests,
            "classes": 0, "matching_backend": "default",
            "bank_sharding": 1, "arch": cfg.name,
            "hit_rate": h,
            "mean_speedup": round(base_us / us, 2),
            "mean_energy_ratio": round(base_nj / m["nj_per_request"], 2)
            if m["nj_per_request"] else None,
            "hit_path_speedup": round(base_us / hit_us, 1),
            "hit_path_energy_ratio": round(base_nj / hit_nj, 1),
            "hit_path_us": round(hit_us, 1),
            "hit_path_nj": round(hit_nj, 4),
            "decode_us_per_request": round(base_us, 1),
            "decode_energy_nj": round(base_nj, 3),
            "requests_per_s": m["requests_per_s"],
            "latency_p50_ms": m["latency_p50_ms"],
            "latency_p99_ms": m["latency_p99_ms"],
            "escalation_rate": m["escalation_rate"],
            "nj_per_request": m["nj_per_request"],
            "occupancy": m["occupancy"],
            "classify_dispatches": m["classify_dispatches"],
        }
        assert m["classify_dispatches"] == m["ticks"], m  # ONE per tick
        entries.append(entry)
        print(f"lm cache h={h:.1f}: {us:.0f} us/request "
              f"(mean x{entry['mean_speedup']}, "
              f"bound {1 / (1 - h):.0f}x), "
              f"{m['nj_per_request']:.1f} nJ/request; hit path "
              f"x{entry['hit_path_speedup']} latency, "
              f"x{entry['hit_path_energy_ratio']} energy")
    return entries


def sweep(*, smoke: bool = False, seed: int = 0) -> list[dict]:
    tenant_grid = SMOKE_TENANTS if smoke else TENANT_SWEEP
    slot_grid = SMOKE_SLOTS if smoke else SLOT_SWEEP

    entries = []
    for tenants in tenant_grid:
        for slots in slot_grid:
            requests = (2 * max(slots, 32) if smoke
                        else max(4 * slots, 128))
            entries.append(bench_service(tenants, slots, requests=requests,
                                         seed=seed))
            _report(entries[-1])
    # one device-physics row: the same service stack through the RRAM-CMOS
    # behavioural models (repro.match "device" backend), tracking how much
    # hardware-faithful simulation costs relative to the kernel path
    tenants, slots = tenant_grid[-1], max(slot_grid)
    entries.append(bench_service(tenants, slots,
                                 requests=2 * max(slots, 32) if smoke
                                 else max(4 * slots, 128),
                                 seed=seed, backend="device"))
    _report(entries[-1])
    # bank-scaling rows: replicated vs sharded super-bank (the crossover)
    entries.extend(bank_scaling_sweep(smoke=smoke, seed=seed))
    # live-reshard row: spec-built service, 1 -> 2 shards mid-stream
    reshard = reshard_bench(seed=seed)
    if reshard is not None:
        entries.append(reshard)
    # resilience rows: p99-under-burst + shed rate, and kill/restore
    # recovery time (benchmarks/traces.py chaos harness), both re-derived
    # from the flight recorder's event log
    entries.append(burst_bench(smoke=smoke, seed=seed))
    entries.append(chaos_bench(smoke=smoke, seed=seed))
    # self-driving fleet row: autopilot over a churn trace, flip-vs-drained
    pilot_row = autopilot_bench(smoke=smoke, seed=seed)
    if pilot_row is not None:
        entries.append(pilot_row)
    # telemetry tax: sinks-off vs full recorder on one identical stream
    entries.append(telemetry_overhead_bench(smoke=smoke, seed=seed))
    # serve fusion win: composed tick vs the resident mega-kernel
    entries.append(megakernel_bench(smoke=smoke, seed=seed))
    # ACAM-as-semantic-cache in front of LM decode: hit-rate sweep +
    # decode-only baseline (hit-path AND amortisation-bounded mean ratios)
    entries.extend(lm_cache_bench(smoke=smoke, seed=seed))
    return entries


def write_bench_json(entries: list[dict],
                     path: str = "BENCH_serving.json") -> None:
    from repro.kernels import tuning

    payload = {
        "backend": tuning.backend(),
        "interpret": tuning.interpret_mode(),
        "entries": entries,
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)


def run() -> list[dict]:
    """benchmarks/run.py harness contract."""
    from repro.distributed import forcemesh

    # phase 1 of REPRO_FORCE_MESH must precede jax backend init; this
    # module leaves jax untouched until bench_service, so this is in time
    forcemesh.apply_xla_flags()
    fast = os.environ.get("REPRO_BENCH_FAST", "0") == "1"
    entries = sweep(smoke=fast)
    write_bench_json(entries)
    return [{"name": _row_name(e), "us_per_call":
             round(1e6 / e["requests_per_s"], 2)
             if e["requests_per_s"] else 0.0,
             "derived": _row_derived(e)} for e in entries]


def _row_name(e: dict) -> str:
    if e.get("lm_baseline"):
        return "serving_lm_decode_only"
    if "hit_rate" in e:
        return f"serving_lm_cache_h{int(round(e['hit_rate'] * 100))}"
    if "megakernel_speedup_pct" in e:
        return "serving_megakernel"
    if "telemetry_overhead_pct" in e:
        return "serving_telemetry_overhead"
    if "flip_downtime_ms" in e:
        return "serving_autopilot"
    if "reshard_downtime_ms" in e:
        return f"serving_reshard_1to{e['bank_sharding']}"
    if e.get("trace") == "chaos":
        return "serving_chaos_recovery"
    if e.get("trace") == "burst":
        return f"serving_burst_t{e['tenants']}_s{e['slots']}"
    return (f"serving_t{e['tenants']}_c{e['classes']}_s{e['slots']}"
            + ("" if e["bank_sharding"] == 1
               else f"_shard{e['bank_sharding']}")
            + ("" if e["matching_backend"] == "default"
               else f"_{e['matching_backend']}"))


def _row_derived(e: dict) -> str:
    if e.get("lm_baseline"):
        return (f"{e['us_per_request']}us/req,"
                f"{e['decode_energy_nj']}nJ/req,decode-only")
    if "hit_rate" in e:
        return (f"h={e['hit_rate']},mean_x{e['mean_speedup']},"
                + (f"hitpath_x{e['hit_path_speedup']}us/"
                   f"x{e['hit_path_energy_ratio']}nJ"
                   if e["hit_path_speedup"] else "no-hits")
                + f",{e['nj_per_request']:.1f}nJ/req")
    if "megakernel_speedup_pct" in e:
        return (f"speedup={e['megakernel_speedup_pct']}%,"
                f"compose={e['compose_us_per_request']}us,"
                f"mega={e['mega_us_per_request']}us")
    if "telemetry_overhead_pct" in e:
        return (f"overhead={e['telemetry_overhead_pct']}%,"
                f"base={e['base_us_per_request']}us,"
                f"tel={e['telemetry_us_per_request']}us")
    if "flip_downtime_ms" in e:
        return (f"flip={e['flip_downtime_ms']}ms,"
                f"drained={e['drained_downtime_ms']}ms,"
                f"flips={e['policy_flips']},"
                f"shards={e['bank_sharding']}")
    if "reshard_downtime_ms" in e:
        return (f"downtime={e['reshard_downtime_ms']}ms,"
                f"moved={e['tenants_moved']},"
                f"{e['requests_per_s']:.0f}req/s")
    if e.get("trace") == "chaos":
        return (f"recovery={e['recovery_ms']}ms,"
                f"lost={e['lost_in_flight']},"
                f"{e['requests_per_s']:.0f}req/s")
    if e.get("trace") == "burst":
        return (f"p99_burst={e['p99_burst_ms']}ms,"
                f"shed={e['shed_rate']:.3f},"
                f"{e['requests_per_s']:.0f}req/s")
    return (f"{e['requests_per_s']:.0f}req/s,"
            f"esc={e['escalation_rate']:.3f},"
            f"{e['nj_per_request']:.2f}nJ/req")


def main() -> None:
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke: small tenant/slot grid")
    ap.add_argument("--reshard", action="store_true",
                    help="run ONLY the live-reshard smoke: boot the "
                         "spec-built service at bank_shards=1 under "
                         "REPRO_FORCE_MESH, reconfigure to 2 mid-stream, "
                         "assert bit-identity + one sharded dispatch per "
                         "tick, report drain->resume downtime")
    ap.add_argument("--chaos", action="store_true",
                    help="run ONLY the chaos smoke: replay a bursty trace, "
                         "kill the service mid-stream, restore from its "
                         "snapshot, assert bit-identity vs a clean build, "
                         "and append the recovery-time row to "
                         "BENCH_serving.json")
    ap.add_argument("--megakernel", action="store_true",
                    help="run ONLY the serve mega-kernel A/B: interleaved "
                         "serve_fusion=mega vs =compose passes over the "
                         "same request stream (bit-identical signatures "
                         "asserted), then append/replace the "
                         "serving_megakernel row in BENCH_serving.json")
    ap.add_argument("--lm-cache", action="store_true",
                    help="run ONLY the ACAM-semantic-cache-vs-LM-decode "
                         "sweep: decode-only baseline plus exact hit "
                         "rates {0, 0.5, 0.9}, then append/replace the "
                         "serving_lm_* rows in BENCH_serving.json")
    ap.add_argument("--autopilot", action="store_true",
                    help="run ONLY the self-driving fleet smoke: bursty "
                         "Zipf churn trace with the repro.fleet autopilot "
                         "on under REPRO_FORCE_MESH — asserts at least one "
                         "policy-initiated double-buffered reshard, "
                         "bit-identity vs a pinned-spec run, and flip "
                         "downtime strictly below the drained reshard — "
                         "then append the serving_autopilot row to "
                         "BENCH_serving.json")
    ap.add_argument("--telemetry-dir", default=None, metavar="DIR",
                    help="with --chaos or --autopilot: keep the flight "
                         "recorder's events.jsonl + metrics.prom in DIR so "
                         "the CI smoke jobs can validate them with "
                         "`python -m repro.obs.export`")
    args = ap.parse_args()
    if args.reshard or args.chaos or args.autopilot:
        from repro.distributed import forcemesh

        forcemesh.apply_xla_flags()
    if args.reshard:
        entry = reshard_bench()
        if entry is None:
            raise SystemExit("--reshard needs REPRO_FORCE_MESH=DxM")
        return
    if args.chaos:
        entry = chaos_bench(smoke=True, telemetry_dir=args.telemetry_dir)
        assert entry["recovery_ms"] is not None, "service never recovered"
        path = "BENCH_serving.json"
        if os.path.exists(path):
            with open(path) as f:
                payload = json.load(f)
            payload["entries"] = [e for e in payload["entries"]
                                  if e.get("trace") != "chaos"] + [entry]
            with open(path, "w") as f:
                json.dump(payload, f, indent=1)
        else:
            write_bench_json([entry], path)
        print("appended chaos recovery row to BENCH_serving.json")
        return
    if args.autopilot:
        entry = autopilot_bench(smoke=True,
                                telemetry_dir=args.telemetry_dir)
        if entry is None:
            raise SystemExit("--autopilot needs REPRO_FORCE_MESH=DxM")
        path = "BENCH_serving.json"
        if os.path.exists(path):
            with open(path) as f:
                payload = json.load(f)
            payload["entries"] = [e for e in payload["entries"]
                                  if "flip_downtime_ms" not in e] + [entry]
            with open(path, "w") as f:
                json.dump(payload, f, indent=1)
        else:
            write_bench_json([entry], path)
        print("appended serving_autopilot row to BENCH_serving.json")
        return
    if args.lm_cache:
        rows = lm_cache_bench(smoke=args.smoke)
        path = "BENCH_serving.json"
        if os.path.exists(path):
            with open(path) as f:
                payload = json.load(f)
            payload["entries"] = [
                e for e in payload["entries"]
                if "hit_rate" not in e and not e.get("lm_baseline")] + rows
            with open(path, "w") as f:
                json.dump(payload, f, indent=1)
        else:
            write_bench_json(rows, path)
        print("appended lm semantic-cache rows to BENCH_serving.json")
        return
    if args.megakernel:
        entry = megakernel_bench(smoke=args.smoke)
        path = "BENCH_serving.json"
        if os.path.exists(path):
            with open(path) as f:
                payload = json.load(f)
            payload["entries"] = [
                e for e in payload["entries"]
                if "megakernel_speedup_pct" not in e] + [entry]
            with open(path, "w") as f:
                json.dump(payload, f, indent=1)
        else:
            write_bench_json([entry], path)
        print("appended serve mega-kernel row to BENCH_serving.json")
        return
    if args.smoke:
        os.environ["REPRO_BENCH_FAST"] = "1"
    for r in run():
        print(r)
    print("wrote BENCH_serving.json")


if __name__ == "__main__":
    main()
