"""Serving launcher: LM decode engine OR the multi-tenant ACAM service.

Two workloads behind one CLI:

  lm    — batched generation over any zoo architecture
          (`repro.serve.engine.Engine`). CPU smoke scale by default; on a
          real pod the same engine runs under `make_production_mesh()` with
          the `tp`/`fsdp_tp` shardings whose lowering the decode_32k /
          long_500k dry-run cells prove.

  acam  — the multi-tenant hybrid-classifier service, constructed through
          the ONE front door: a declarative `repro.serve.spec.ServiceSpec`
          (built from the CLI flags, or loaded verbatim via
          ``--spec service.json``) handed to
          `repro.serve.control.HybridService.from_spec`, which owns the
          whole boot sequence — mesh install -> registry -> scheduler ->
          cascade — so there is no constructor ordering to get wrong.

  lm-cached — the two composed (`repro.serve.semantic_cache`): the ACAM
          tier fronts the decode engine as a template router; repeats of
          an admitted prompt answer from the response store at Eq. 14
          ACAM energy, cold prompts escalate to decode and are admitted
          back as templates.

  PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
      --requests 8 --max-new 16 --temperature 0.8
  PYTHONPATH=src python -m repro.launch.serve --workload lm-cached \
      --requests 32 --unique 8 --temperature 0.7
  PYTHONPATH=src python -m repro.launch.serve --workload acam \
      --tenants 8 --requests 256 --slots 64
  PYTHONPATH=src python -m repro.launch.serve --workload acam \
      --backend device   # serve through the RRAM-CMOS physics models
  PYTHONPATH=src python -m repro.launch.serve --workload acam \
      --spec service.json --print-spec   # declarative boot from a file
  REPRO_FORCE_MESH=2x2 PYTHONPATH=src python -m repro.launch.serve \
      --workload acam --bank-shards 2   # 2D-sharded: batch over "data",
                                        # super-bank class rows over "model"
  PYTHONPATH=src python -m repro.launch.serve --workload acam \
      --snapshot-dir /tmp/acam-ckpt     # durable state: snapshot on exit
  PYTHONPATH=src python -m repro.launch.serve --workload acam \
      --snapshot-dir /tmp/acam-ckpt --restore   # restart bit-identical
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np


def build_acam_spec(args):
    """The launcher's flag surface -> one `ServiceSpec` (or load the spec
    verbatim from ``--spec file.json`` — flags are then ignored)."""
    from repro import match as match_lib
    from repro.match.config import EngineConfig
    from repro.serve import spec as spec_lib

    if args.spec:
        return spec_lib.ServiceSpec.from_file(args.spec)
    return spec_lib.ServiceSpec(
        registry=spec_lib.RegistrySpec(
            num_features=args.features,
            initial_classes=spec_lib.aligned_classes(args.bank_shards)),
        engine=EngineConfig(backend=args.backend
                            or match_lib.default_backend(), margin=True,
                            device_noise=args.device_noise),
        mesh=spec_lib.MeshSpec(bank_shards=args.bank_shards),
        scheduler=spec_lib.SchedulerSpec(slots=args.slots),
        cascade=spec_lib.CascadeSpec(tau=args.margin_tau,
                                     tau_units="count",
                                     deadline_ms=args.deadline_ms,
                                     shed_queue=args.shed_queue),
        obs=spec_lib.ObsSpec(telemetry_dir=args.telemetry_dir,
                             profile_annotations=args.profile_annotations),
    )


def run_lm(args) -> dict:
    from repro import configs
    from repro.models import lm
    from repro.serve.engine import Engine, Request

    cfg = configs.get(args.arch, smoke=args.smoke)
    params = lm.init_params(jax.random.PRNGKey(args.seed), cfg)
    eng = Engine(cfg, params, batch_size=args.batch_size,
                 max_len=args.max_len, temperature=args.temperature)
    rng = np.random.RandomState(args.seed)
    reqs = [Request(prompt=rng.randint(0, cfg.vocab, size=rng.randint(4, 32)),
                    max_new_tokens=args.max_new)
            for _ in range(args.requests)]
    t0 = time.time()
    eng.generate(reqs)
    dt = time.time() - t0
    total = sum(len(r.out) for r in reqs)
    print(f"{cfg.name}: {len(reqs)} requests, {total} tokens, "
          f"{dt:.2f}s ({total / dt:.1f} tok/s)")
    return {"tokens": total, "seconds": dt}


def run_acam(args) -> dict:
    from repro.serve import acam_service as svc_lib
    from repro.serve.control import HybridService

    # ONE declarative spec drives the whole stack; from_spec installs the
    # (data, model=bank_shards) mesh itself, then builds registry ->
    # scheduler -> cascade in order. margin_tau rides in the spec with
    # explicit units ("count"); the service converts to the backend's
    # native margin units (matchline fractions for "device") itself.
    spec = build_acam_spec(args)
    if args.print_spec:
        print(spec.to_json())
    if args.restore:
        from repro.checkpoint.checkpointer import Checkpointer

        if not args.snapshot_dir:
            raise SystemExit("--restore needs --snapshot-dir")
        svc, report = HybridService.restore(Checkpointer(args.snapshot_dir))
        print(f"restored step {report.step}: {report.tenants} tenants, "
              f"{report.restore_s * 1e3:.1f} ms"
              + (" (resharded)" if report.resharded else ""))
        spec = svc.spec
    else:
        svc = HybridService.from_spec(spec)
    n_features = spec.registry.num_features
    if spec.mesh.bank_shards > 1:
        print(f"installed serving mesh model={spec.mesh.bank_shards} "
              f"({len(jax.devices())} devices)")

    protos = {}
    if args.manifest:
        # declarative tenant population: ONE FleetManifest JSON file,
        # diffed against the (empty) manifest in force — the same
        # apply_manifest the autopilot uses for churn
        from repro.fleet import FleetManifest

        rep = svc.apply_manifest(FleetManifest.from_file(args.manifest))
        print(f"manifest applied: +{len(rep.added)} added, "
              f"-{len(rep.evicted)} evicted, {len(rep.updated)} updated, "
              f"{len(rep.retuned)} retuned "
              f"({len(svc.registry)} tenants live)")
        for t in rep.manifest.tenants:
            if t.seed is not None:  # checkpoint tenants have no protos
                protos[t.tenant_id] = svc_lib.make_synthetic_tenant(
                    t.seed, num_classes=t.num_classes, k=t.k,
                    num_features=n_features)[2]
    else:
        for t in range(args.tenants):
            bank, head, p = svc_lib.make_synthetic_tenant(
                args.seed * 1000 + t, num_classes=args.classes,
                num_features=n_features)
            tid = f"tenant-{t}"
            if tid not in svc.registry:  # a restored service adopted them
                svc.register_tenant(tid, bank, head=head)
            protos[tid] = p

    # mixed-tenant request stream (round-robin interleave, then shuffled —
    # every micro-batch holds several tenants)
    rng = np.random.RandomState(args.seed)
    reqs, truth = [], []
    tids = sorted(protos)
    per_tenant = -(-args.requests // max(len(tids), 1))
    for t, tid in enumerate(tids):
        feats, labels = svc_lib.sample_tenant_queries(
            args.seed + 7 * t, protos[tid], per_tenant, noise=args.noise)
        for i in range(per_tenant):
            reqs.append(svc_lib.ClassifyRequest(tid, feats[i]))
            truth.append(int(labels[i]))
    order = rng.permutation(len(reqs))[:args.requests]
    reqs = [reqs[i] for i in order]
    truth = [truth[i] for i in order]

    if args.autopilot:
        # closed-loop serving: bursty submission, one observe_tick per
        # step — the policy controller may reshard (double-buffered
        # flip), swap backends, widen slots or compact mid-stream
        from repro.fleet import Autopilot, PolicySpec

        pilot = Autopilot(svc, policy=PolicySpec())
        burst = spec.scheduler.slots
        responses, i = [], 0
        while i < len(reqs) or svc.scheduler.qsize:
            for r in reqs[i:i + burst]:
                svc.submit(r)
            i += burst
            responses.extend(svc.step())
            pilot.observe_tick()
            responses.extend(pilot.take_drained())
        if pilot.actions:
            acts = ", ".join(f"t{a['tick']}:{a['action']}"
                             for a in pilot.actions)
            print(f"autopilot: {len(pilot.actions)} actions ({acts}); "
                  f"now bank_shards={svc.spec.mesh.bank_shards}, "
                  f"slots={svc.spec.scheduler.slots}, "
                  f"backend={svc.spec.engine.backend}")
        else:
            print("autopilot: no action (no threshold crossed)")
        spec = svc.spec
    else:
        responses = svc.serve(reqs)
    m = svc.metrics()
    if args.snapshot_dir:
        from repro.checkpoint.checkpointer import Checkpointer

        step = svc.snapshot(Checkpointer(args.snapshot_dir))
        print(f"service snapshot -> {args.snapshot_dir} step {step} "
              f"(restart with --restore)")
    acc = float(np.mean([r.pred == y for r, y in zip(responses, truth)]))
    print(f"acam service: {m['completed']} requests over "
          f"{len(svc.registry)} tenants, "
          f"{m['classify_dispatches']} fused dispatches "
          f"(occupancy {m['occupancy']:.2f}), accuracy {acc:.4f}")
    print(f"  escalation rate {m['escalation_rate']:.3f} "
          f"({m['escalated']} escalated, "
          f"{m['escalation_dispatches']} head dispatches), "
          f"{m['nj_per_request']:.2f} nJ/request, "
          f"{m['requests_per_s']:.1f} req/s, "
          f"p50 {m['latency_p50_ms']:.1f} ms / p99 {m['latency_p99_ms']:.1f} ms")
    fleet = svc.obs.ledger.fleet()
    print(f"  energy ledger: {fleet['total_nj']:.1f} nJ fleet total, "
          f"backend share {fleet['backend_share']:.3f} "
          f"(E_backend {fleet['backend_nj']:.1f} nJ / "
          f"E_frontend {fleet['frontend_nj']:.1f} nJ)")
    if spec.obs.telemetry_dir:
        import os

        from repro.obs import write_prometheus

        prom = os.path.join(spec.obs.telemetry_dir, "metrics.prom")
        write_prometheus(svc.obs.registry, prom)
        print(f"  telemetry: {svc.obs.events.path} (event log), "
              f"{prom} (Prometheus scrape)")
    return {"accuracy": acc, **m}


def run_lm_cached(args) -> dict:
    """The two engines composed: ACAM semantic cache fronting LM decode.

    A Zipf-repeat prompt trace is routed through
    `repro.serve.semantic_cache.SemanticCacheService` — repeats of an
    admitted prompt answer from the response store at Eq. 14 ACAM energy,
    cold prompts escalate to ONE `Engine.generate` call per tick and are
    admitted back as templates."""
    from repro import configs
    from repro.models import lm
    from repro.serve import spec as spec_lib
    from repro.serve.engine import Engine
    from repro.serve.semantic_cache import (PromptRequest,
                                            SemanticCacheService,
                                            synthetic_prompt_trace)

    cfg = configs.get(args.arch, smoke=args.smoke)
    params = lm.init_params(jax.random.PRNGKey(args.seed), cfg)
    eng = Engine(cfg, params, batch_size=args.batch_size,
                 max_len=args.max_len, temperature=args.temperature,
                 seed=args.seed)
    spec = spec_lib.ServiceSpec(
        registry=spec_lib.RegistrySpec(num_features=args.features),
        scheduler=spec_lib.SchedulerSpec(slots=args.slots),
        cascade=spec_lib.CascadeSpec(backend="lm", tau=args.margin_tau,
                                     tau_units="count"),
        router=spec_lib.RouterSpec(max_templates=max(args.unique, 1)),
        mesh=spec_lib.MeshSpec(install=False))
    if args.print_spec:
        print(spec.to_json())
    svc = SemanticCacheService.from_spec(spec, engine=eng)
    svc.add_tenant("edge-0")

    trace = synthetic_prompt_trace(args.seed, vocab=cfg.vocab,
                                   n_unique=args.unique,
                                   n_requests=args.requests)
    # arrivals come in bursts, not all at once: a template admitted on a
    # miss can only serve hits on LATER ticks, so a single slots-wide
    # tick over the whole trace would (correctly) never hit
    burst = max(1, min(args.slots, args.unique))
    t0 = time.time()
    out = []
    for i in range(0, len(trace), burst):
        out.extend(svc.serve_prompts(
            PromptRequest("edge-0", p, max_new_tokens=args.max_new)
            for p in trace[i:i + burst]))
    dt = time.time() - t0
    m = svc.metrics()
    hits = sum(r.cache_hit for r in out)
    served = sum(r.error is None for r in out)
    fleet = svc.obs.ledger.fleet()
    print(f"{cfg.name} behind ACAM semantic cache: {served} requests "
          f"({args.unique} unique), {hits} cache hits "
          f"({hits / max(served, 1):.2f} hit rate), "
          f"{m['classify_dispatches']} fused match dispatches over "
          f"{m['ticks']} ticks, {dt:.2f}s")
    print(f"  energy: {m['nj_per_request']:.1f} nJ/request mean "
          f"(ACAM share {fleet['backend_share']:.4f}; decode misses carry "
          f"{fleet['frontend_nj']:.1f} nJ of the "
          f"{fleet['total_nj']:.1f} nJ total)")
    return {"hits": hits, "served": served, "seconds": dt, **m}


def main(argv=None) -> dict:
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", choices=("lm", "acam", "lm-cached"),
                    default="lm")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    # lm
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--batch-size", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--temperature", type=float, default=0.0)
    # lm-cached
    ap.add_argument("--unique", type=int, default=8,
                    help="lm-cached: distinct prompts in the Zipf trace "
                         "(the rest are cache-hitting repeats)")
    # acam
    ap.add_argument("--spec", default=None, metavar="FILE.json",
                    help="boot the acam service from a declarative "
                         "ServiceSpec JSON file (other acam flags ignored)")
    ap.add_argument("--print-spec", action="store_true",
                    help="print the resolved ServiceSpec JSON before boot")
    ap.add_argument("--manifest", default=None, metavar="FILE.json",
                    help="populate tenants from a declarative FleetManifest "
                         "JSON file (diffed + applied as live transitions) "
                         "instead of the synthetic --tenants loop")
    ap.add_argument("--autopilot", action="store_true",
                    help="drive serving through the repro.fleet Autopilot: "
                         "the telemetry policy may reshard (double-buffered "
                         "flip), swap backends, widen slots or compact the "
                         "registry mid-stream")
    ap.add_argument("--tenants", type=int, default=8)
    ap.add_argument("--slots", type=int, default=64)
    ap.add_argument("--classes", type=int, default=10,
                    help="classes per synthetic tenant")
    ap.add_argument("--features", type=int, default=64,
                    help="feature dim of the synthetic tenants")
    ap.add_argument("--margin-tau", type=float, default=8.0,
                    help="cascade accept threshold (match-count units)")
    ap.add_argument("--noise", type=float, default=0.8,
                    help="query noise (drives the escalation rate)")
    ap.add_argument("--backend", default=None,
                    choices=("auto", "kernel", "reference", "device"),
                    help="repro.match engine backend for the ACAM service "
                         "(device = RRAM-CMOS physics models; margin-tau "
                         "is auto-rescaled to matchline-fraction units); "
                         "default: process REPRO_MATCHING_BACKEND / auto")
    ap.add_argument("--bank-shards", type=int, default=1,
                    help="shard the template super-bank's class rows over "
                         "a model mesh axis of this size (must divide the "
                         "device count; on CPU set REPRO_FORCE_MESH or "
                         "XLA_FLAGS host-device count first)")
    ap.add_argument("--snapshot-dir", default=None, metavar="DIR",
                    help="durable service state: snapshot the service "
                         "(registry, placements, taus, heads, spec) into "
                         "DIR after serving, via the atomic-rename "
                         "checkpointer")
    ap.add_argument("--restore", action="store_true",
                    help="boot by restoring the latest snapshot from "
                         "--snapshot-dir instead of building fresh "
                         "(bit-identical serving, zero re-registrations)")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request queue deadline: requests older than "
                         "this at tick time are expired with an error")
    ap.add_argument("--shed-queue", type=int, default=None,
                    help="queue depth at which the service enters load-shed "
                         "mode (ACAM stage alone, no CNN escalation)")
    ap.add_argument("--telemetry-dir", default=None, metavar="DIR",
                    help="flight-recorder sinks: append a JSONL event log "
                         "(DIR/events.jsonl, one line per serving tick + "
                         "every lifecycle event) and write a Prometheus "
                         "scrape file (DIR/metrics.prom) after serving")
    ap.add_argument("--profile-annotations", action="store_true",
                    help="turn on every serving span: a jax.profiler "
                         "TraceAnnotation at each layer boundary of the "
                         "served path (step, tick, pack, dispatch, launch, "
                         "fetch, escalate, finalize), visible in device "
                         "traces")
    ap.add_argument("--device-noise", default="global",
                    choices=("global", "per_shard"),
                    help="sigma_program noise semantics for the device "
                         "backend: per_shard programs one physical array "
                         "per bank shard (fold_in(seed, shard))")
    args = ap.parse_args(argv)
    if args.requests is None:
        args.requests = {"lm": 8, "acam": 256, "lm-cached": 32}[args.workload]
    runner = {"lm": run_lm, "acam": run_acam,
              "lm-cached": run_lm_cached}[args.workload]
    return runner(args)


if __name__ == "__main__":
    main()
