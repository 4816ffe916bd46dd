"""Quickstart: the full hybrid edge classifier, end to end.

Trains the paper's tinyML student CNN (Fig. 5) on the synthetic CIFAR-10
substitute, distils templates, programs the ACAM back-end, and reports the
accuracy/energy trade-off of §V. Runs on CPU in a few minutes.

    PYTHONPATH=src python examples/quickstart.py [--fast]
"""
import argparse
import functools

import jax
import jax.numpy as jnp

from repro import match
from repro.core import acam, energy, hybrid
from repro.data import synthetic
from repro.models import cnn
from repro.train import cnn_trainer as T


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--n-per-class", type=int, default=None)
    ap.add_argument("--mc", type=int, default=0, metavar="N",
                    help="Monte-Carlo sweep: N independent sigma_program "
                         "draws (engine.sweep_program_noise) -> confidence "
                         "interval on noisy-hardware accuracy")
    args = ap.parse_args()
    n = args.n_per_class or (120 if args.fast else 400)
    epochs = 2 if args.fast else 4

    print("== data: synthetic CIFAR-10 (greyscale, normalised; paper §IV-A)")
    tr = synthetic.load("train", n_per_class=n, seed=0)
    te = synthetic.load("test", n_per_class=max(n // 4, 50), seed=0)
    gtr = synthetic.normalize(synthetic.to_grayscale(tr.images))
    gte = synthetic.normalize(synthetic.to_grayscale(te.images))

    print("== front-end: student CNN (conv 32-128-256-16 -> 784 features)")
    cfg = T.TrainConfig(epochs=epochs, batch_size=128)
    params, _ = T.train_student(gtr, tr.labels, cfg=cfg)
    logits_fn = functools.partial(cnn.student_logits, train=False)
    acc_soft = T.evaluate(logits_fn, params, gte, te.labels)
    print(f"   softmax-head accuracy: {acc_soft:.4f}")

    print("== back-end: binary templates -> TXL-ACAM pattern matching")
    feature_fn = lambda p, x: cnn.student_features(p, x)[0]
    head = hybrid.fit_acam_head(feature_fn, params, gtr, tr.labels, 10, k=1)
    clf = hybrid.HybridClassifier(params, jax.jit(feature_fn), head)
    acc_acam = clf.accuracy(gte, te.labels)
    print(f"   ACAM feature-count accuracy: {acc_acam:.4f} "
          f"(drop {acc_soft - acc_acam:+.4f} vs softmax — paper saw -11%)")

    print("== device physics: the same head through the RRAM-CMOS models")
    feats_te = jax.jit(feature_fn)(params, gte)

    def device_acc(sigma):
        eng = match.engine_for(
            backend="device",
            device=acam.ACAMConfig(sigma_program=sigma), seed=7)
        pred, _ = eng.classify_features(feats_te, head.bank)
        return float(jnp.mean(pred == te.labels))

    acc_dev = device_acc(0.0)
    acc_noisy = device_acc(0.10)
    print(f"   ideal array (sigma=0)      : {acc_dev:.4f} "
          f"(matches the window model exactly)")
    print(f"   noisy RRAM (sigma=0.10)    : {acc_noisy:.4f} "
          f"(programming variability, §III)")

    if args.mc > 0:
        # one programmed array is a single sample of the write-noise
        # process; the vmapped sweep turns it into a confidence interval
        for sigma in (0.05, 0.10, 0.20):
            eng = match.engine_for(
                backend="device",
                device=acam.ACAMConfig(sigma_program=sigma), seed=7)
            preds, _ = eng.sweep_program_noise(feats_te, head.bank, args.mc)
            accs = jnp.mean(preds == te.labels[None, :], axis=1)
            print(f"   MC x{args.mc} sigma={sigma:.2f}      : "
                  f"{float(jnp.mean(accs)):.4f} +/- "
                  f"{float(jnp.std(accs)):.4f} "
                  f"(min {float(jnp.min(accs)):.4f}, "
                  f"max {float(jnp.max(accs)):.4f})")
        # tiled deployments program one physical array PER bank shard
        # (device_noise="per_shard": array s keyed fold_in(seed, s)) — a
        # distinct noise layout from the single-array draw above
        eng = match.engine_for(
            backend="device", device=acam.ACAMConfig(sigma_program=0.10),
            seed=7, device_noise="per_shard")
        preds, _ = eng.sweep_program_noise(feats_te, head.bank, args.mc,
                                           bank_shards=2)
        accs = jnp.mean(preds == te.labels[None, :], axis=1)
        print(f"   MC x{args.mc} sigma=0.10 x2arr: "
              f"{float(jnp.mean(accs)):.4f} +/- {float(jnp.std(accs)):.4f} "
              f"(per-shard programming keys, 2 arrays)")

    print("== serving: the same head behind the declarative front door")
    # ONE ServiceSpec stands up the whole serving stack (registry ->
    # scheduler -> cascade); the spec is JSON-round-trippable, so this
    # exact configuration can ship as a file (launch/serve --spec).
    import numpy as np

    from repro.match.config import EngineConfig
    from repro.serve import acam_service as svc_lib
    from repro.serve import spec as spec_lib
    from repro.serve.control import HybridService

    spec = spec_lib.ServiceSpec(
        registry=spec_lib.RegistrySpec(num_features=head.bank.num_features),
        engine=EngineConfig(backend=match.default_backend(), margin=True),
        mesh=spec_lib.MeshSpec(bank_shards=1, install=False),
        scheduler=spec_lib.SchedulerSpec(slots=64),
        cascade=spec_lib.CascadeSpec(tau=8.0, tau_units="count"),
    )
    assert spec_lib.ServiceSpec.from_json(spec.to_json()) == spec
    svc = HybridService.from_spec(spec)
    dense = params["head"]
    svc.register_tenant("wearable-0", head.bank,
                        head=(np.asarray(dense["w"]),
                              np.asarray(dense["b"])))
    responses = svc.serve([
        svc_lib.ClassifyRequest("wearable-0", f) for f in np.asarray(feats_te)])
    m = svc.metrics()
    acc_svc = float(np.mean([r.pred == y
                             for r, y in zip(responses, te.labels)]))
    print(f"   cascade accuracy {acc_svc:.4f} over {m['completed']} requests "
          f"({m['classify_dispatches']} fused dispatches, escalation rate "
          f"{m['escalation_rate']:.3f}, {m['nj_per_request']:.2f} nJ/req)")

    print("== telemetry: the flight recorder behind metrics()")
    # every number above was a view over `svc.obs` (repro.obs): the
    # latency quantiles are exact-from-buckets reads of ONE histogram
    # (the shed check reads the identical value), the energy ledger is
    # bit-exact with the per-response sum, and request conservation
    # (started == finished + in-flight) holds at every quiescent point.
    fleet = svc.obs.ledger.fleet()
    spans = svc.obs.conservation()
    assert fleet["total_nj"] == sum(r.energy_j for r in responses) * 1e9
    assert spans["started"] == spans["finished"] + spans["in_flight"]
    print(f"   energy ledger: {fleet['total_nj']:.1f} nJ over "
          f"{fleet['requests']} requests (backend share "
          f"{fleet['backend_share']:.3f}; bit-exact with per-response sum)")
    print(f"   latency p50/p99: {m['latency_p50_ms']:.3f}/"
          f"{m['latency_p99_ms']:.3f} ms (exact from histogram buckets)")
    print(f"   requests: {spans['started']} started == {spans['finished']} "
          f"finished + {spans['in_flight']} in-flight "
          f"(dispositions {spans['by_disposition']})")

    print("== energy (paper §V-D arithmetic)")
    nums = energy.paper_numbers()
    print(f"   back-end  : {nums['backend_nj']:.2f} nJ / inference (Eq. 14)")
    print(f"   front-end : {nums['frontend_nj']:.2f} nJ / inference")
    print(f"   teacher   : {nums['teacher_uj']:.2f} uJ / inference")
    print(f"   reduction : {nums['reduction_x']:.0f}x")
    print(f"   this head : {head.energy_per_inference()*1e9:.2f} nJ")


if __name__ == "__main__":
    main()
