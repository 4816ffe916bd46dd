#!/usr/bin/env python3
"""Drive the hybrid classifier's main path once on a TPU and check it.

    python3 chip_smoke.py               # one chip: phases a, b, c
    python3 chip_smoke.py --four-chips  # four chips: bank sharding only

Everything runs in this one process (a child process could not reach the
chip this process holds), at the paper's width: the student CNN of
`repro.models.cnn` (32x32x1 input -> 784 features), an ACAM head from
`repro.core.hybrid.fit_acam_head`, and the multi-tenant service built by
`repro.serve.control.HybridService.from_spec` with the serve mega-kernels
compiled for the chip (never the Pallas interpreter).

  a. devices and versions; anything but a TPU is a failure.
  b. front-end: a few student train steps on seeded synthetic data, ACAM
     templates fitted at k = 1 and k = 2, then `HybridClassifier.predict`
     (the fused CNN -> ACAM jit) on the "kernel" and "reference" backends;
     predictions must be equal.
  c. serve: for both matching methods, a bank resident in VMEM and one past
     `MAX_FUSED_ROWS` (the class-chunked kernel), each served for a few
     hundred requests built from the trained front-end's features by a
     "kernel" service and by an identical "reference" service; pred, margin
     and escalation must be equal, request by request.

``--four-chips`` runs only the bank-sharded path: the same tenants and
requests served with ``bank_shards`` 4 (a 1x4 mesh) and 2 (2x2), each
compared with the ``bank_shards=1`` service on the same four chips, and
each service's super-bank placed on its mesh once.

Findings go to standard output. The last line is one JSON object,
``{"ok": true, "device": {...}}``, printed only when every phase passed;
otherwise the script exits non-zero and prints no such line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

NUM_FEATURES = 784  # StudentConfig(): 7 x 7 x 16
SLOTS = 64
TENANTS = 64
K = 2
#: classes per tenant: 64 tenants x 16-class buckets x k=2 is exactly
#: MAX_FUSED_ROWS (2048) rows, resident in VMEM; 40 classes (48-class
#: buckets) make 6144 rows, walked in class chunks
BANKS = {"resident": 10, "chunked": 40}
METHODS = ("feature_count", "similarity")
REQUESTS = 384  # per configuration: six full ticks of 64 slots
#: cascade threshold in match counts (the service converts it to the
#: similarity method's Eq. 11 fraction units by 1/N): chosen so that both
#: escalated and accepted requests occur, which puts the ACAM's own pred
#: (accepted) and the escalation bit (all) under comparison
TAU = {"feature_count": 2.0, "similarity": 0.02}
SEED = 0


def features(params, x):
    """The student front-end: (B, 32, 32, 1) images -> (B, 784) features."""
    from repro.models import cnn

    return cnn.student_features(params, x)[0]


def peak_bytes(dev) -> str:
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak} B"


# ---------------------------------------------------------------------------
# front-end and tenants
# ---------------------------------------------------------------------------

def front_end(*, train: bool):
    """Seeded synthetic data, the student CNN (trained for a few steps, or
    at its seeded initial weights) and its features for train and test."""
    import jax
    import numpy as np

    from repro.data import synthetic
    from repro.models import cnn
    from repro.train import cnn_trainer

    tr = synthetic.load("train", n_per_class=128, seed=SEED)
    te = synthetic.load("test", n_per_class=64, seed=SEED)
    x_tr = synthetic.normalize(synthetic.to_grayscale(tr.images))
    x_te = synthetic.normalize(synthetic.to_grayscale(te.images))
    if train:  # 1280 images / batch 128: ten steps
        params, _ = cnn_trainer.train_student(
            x_tr, tr.labels, cfg=cnn_trainer.TrainConfig(
                epochs=1, batch_size=128, seed=SEED))
    else:
        params = cnn.init_student(jax.random.PRNGKey(SEED))
    fn = jax.jit(features)
    f_tr = np.asarray(fn(params, x_tr))
    f_te = np.asarray(fn(params, x_te))
    return params, (x_tr, tr.labels, f_tr), (x_te, te.labels, f_te)


def tenant_banks(params, train, classes: int):
    """Tenant 0 is the fitted k=2 ACAM head of the front-end with its dense
    softmax head as escalation target; the other 63 hold real front-end
    feature maps as point templates (a nearest-exemplar bank), with a
    nearest-centroid head."""
    import numpy as np

    from repro.core import hybrid
    from repro.core.templates import TemplateBank

    x_tr, y_tr, f_tr = train
    head = hybrid.fit_acam_head(features, params, x_tr, y_tr, 10, k=K)
    dense = params["head"]
    thr = np.asarray(head.bank.thresholds, np.float32)
    out = [("tenant-0", head.bank,
            (np.asarray(dense["w"]), np.asarray(dense["b"])))]
    rng = np.random.RandomState(SEED)
    for t in range(1, TENANTS):
        ex = f_tr[rng.choice(len(f_tr), classes * K, replace=False)]
        ex = ex.reshape(classes, K, NUM_FEATURES)
        bits = (ex > thr).astype(np.float32)
        bank = TemplateBank(bits, bits, bits, np.ones((classes, K), bool),
                            thr)
        protos = ex.mean(axis=1)
        out.append((f"tenant-{t}", bank,
                    (protos.T.copy(), -0.5 * np.sum(protos**2, axis=1))))
    return out


def requests(f_te, seed: int):
    import numpy as np

    from repro.serve.acam_service import ClassifyRequest

    rng = np.random.RandomState(seed)
    tenant = rng.randint(0, TENANTS, size=REQUESTS)
    rows = rng.randint(0, len(f_te), size=REQUESTS)
    return [ClassifyRequest(f"tenant-{t}", f_te[r])
            for t, r in zip(tenant, rows)]


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def build_service(method: str, backend: str, bank_shards: int,
                  capacity: int):
    from repro.match.config import EngineConfig
    from repro.serve import spec as spec_lib
    from repro.serve.control import HybridService

    spec = spec_lib.ServiceSpec(
        registry=spec_lib.RegistrySpec(
            num_features=NUM_FEATURES, k_max=K, class_bucket=16,
            initial_classes=capacity, initial_tenants=TENANTS),
        engine=EngineConfig(method=method, backend=backend, margin=True),
        mesh=spec_lib.MeshSpec(bank_shards=bank_shards),
        scheduler=spec_lib.SchedulerSpec(slots=SLOTS),
        cascade=spec_lib.CascadeSpec(tau=TAU[method], tau_units="count"))
    return HybridService.from_spec(spec)


def serve(svc, tenants, reqs) -> dict:
    """Register every tenant, serve every request; -> {request id:
    (pred, margin, escalated)} in submission order."""
    for tid, bank, head in tenants:
        svc.register_tenant(tid, bank, head=head)
    ids = [svc.submit(r) for r in reqs]
    got = {r.request_id: r for r in svc.drain()}
    bad = [got[i].error for i in ids if got[i].error is not None]
    if bad:
        raise RuntimeError(f"{len(bad)} requests failed, e.g. {bad[0]}")
    return {i: (got[i].pred, got[i].margin, got[i].escalated) for i in ids}


def bank_regime(svc, bank_shards: int) -> tuple[int, int, int]:
    """(padded classes per shard, template rows per shard, class chunks)
    of the super-bank the serve kernel walks."""
    from repro.kernels import layout
    from repro.match.backends import MAX_FUSED_ROWS

    c, k = svc.registry.capacity_classes, svc.registry.k_max
    cp = layout.padded_classes(c // bank_shards)
    return cp, k * cp, cp // layout.class_chunk(cp, k, MAX_FUSED_ROWS)


def tick_hlo(svc) -> str:
    """Compiled text of the service's scheduler tick at its live shapes and
    placements (the arrays the last tick was given)."""
    import jax
    import numpy as np

    from repro.serve.scheduler import _batched_classify, _packed_tick

    sched, reg = svc.scheduler, svc.registry
    place, mesh_gen = sched._placement(sched.engine_config)
    head = sched.head_fn(place) if sched.head_fn else None
    packed = jax.device_put(np.zeros((SLOTS, NUM_FEATURES + 4), np.int32),
                            None if place is None else place.batch)
    return _packed_tick.lower(
        reg.device_bank(place), reg.thresholds_table(place), packed,
        *(head[1:] if head else ()), classify=_batched_classify,
        head=head[0] if head else None, config=sched.engine_config,
        mesh_gen=mesh_gen).compile().as_text()


def compare(name: str, got: dict, want: dict) -> bool:
    ids = list(want)
    same = {f: sum(got[i][j] == want[i][j] for i in ids)
            for j, f in enumerate(("pred", "margin", "escalate"))}
    esc = sum(want[i][2] for i in ids)
    ok = all(v == len(ids) for v in same.values())
    print(f"  {name}: equal pred {same['pred']}/{len(ids)}, margin "
          f"{same['margin']}/{len(ids)}, escalate {same['escalate']}/"
          f"{len(ids)} (escalated {esc}) -> {'ok' if ok else 'MISMATCH'}",
          flush=True)
    if not ok:
        for i in ids:
            if got[i] != want[i]:
                print(f"    first mismatch, request {i}: got {got[i]}, "
                      f"want {want[i]}", flush=True)
                break
    return ok


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_front_end(params, train, test) -> bool:
    import numpy as np

    from repro.core import hybrid

    x_tr, y_tr, _ = train
    x_te, y_te, _ = test
    ok = True
    for k in (1, 2):
        head = hybrid.fit_acam_head(features, params, x_tr, y_tr, 10, k=k)
        assert head.bank.num_features == NUM_FEATURES
        for method in METHODS:
            pred = {}
            for backend in ("kernel", "reference"):
                clf = hybrid.HybridClassifier(
                    params, features,
                    head._replace(method=method, backend=backend))
                pred[backend] = np.asarray(clf.predict(x_te))
            same = int(np.sum(pred["kernel"] == pred["reference"]))
            acc = float(np.mean(pred["kernel"] == y_te))
            ok &= same == len(y_te)
            print(f"  predict k={k} {method}: kernel == reference "
                  f"{same}/{len(y_te)}, accuracy {acc:.4f}", flush=True)
    return ok


def phase_serve(params, train, test, dev) -> bool:
    ok = True
    for bank, classes in BANKS.items():
        tenants = tenant_banks(params, train, classes)
        reqs = requests(test[2], SEED + classes)
        capacity = TENANTS * (16 if bank == "resident" else 48)
        for method in METHODS:
            out = {}
            for backend in ("kernel", "reference"):
                svc = build_service(method, backend, 1, capacity)
                out[backend] = serve(svc, tenants, reqs)
                if backend == "kernel":
                    cp, rows, nj = bank_regime(svc, 1)
                    custom = "tpu_custom_call" in tick_hlo(svc)
                    regime_ok = (nj == 1) == (bank == "resident")
                    print(f"  {method} {bank}: super-bank {cp} classes x "
                          f"k={K} = {rows} rows, {nj} class chunk(s) "
                          f"({'as intended' if regime_ok else 'WRONG'}); "
                          f"tick has tpu_custom_call: {custom}", flush=True)
                    ok &= custom and regime_ok
            ok &= compare(f"{method} {bank} kernel vs reference",
                          out["kernel"], out["reference"])
            print(f"  peak device memory: {peak_bytes(dev)}", flush=True)
    return ok


def phase_four_chips(params, train, test, dev) -> bool:
    from repro.distributed import context

    ok = True
    for bank, classes in BANKS.items():
        tenants = tenant_banks(params, train, classes)
        reqs = requests(test[2], SEED + classes)
        capacity = TENANTS * (16 if bank == "resident" else 48)
        for method in METHODS:
            out = {}
            for shards in (1, 2, 4):
                svc = build_service(method, "kernel", shards, capacity)
                out[shards] = serve(svc, tenants, reqs)
                cp, rows, nj = bank_regime(svc, shards)
                hlo = tick_hlo(svc)
                mesh = dict(context.get_mesh().shape)
                placed = svc.registry.bank_placements
                print(f"  {method} {bank} bank_shards={shards} mesh {mesh}: "
                      f"{cp} classes/shard x k={K} = {rows} rows/shard, "
                      f"{nj} chunk(s); tpu_custom_call "
                      f"{'tpu_custom_call' in hlo}, all-gather "
                      f"{'all-gather' in hlo}; bank placed {placed} time(s)",
                      flush=True)
                ok &= "tpu_custom_call" in hlo and placed == 1
            for shards in (2, 4):
                ok &= compare(f"{method} {bank} bank_shards={shards} vs 1",
                              out[shards], out[1])
            print(f"  peak device memory (device 0): {peak_bytes(dev)}",
                  flush=True)
    return ok


def run_phase(name: str, fn, *args) -> bool:
    print(f"[{name}]", flush=True)
    t0 = time.perf_counter()
    try:
        ok = fn(*args)
    except Exception:  # noqa: BLE001 — report the phase and go on
        traceback.print_exc(file=sys.stdout)
        ok = False
    print(f"[{name}] {'passed' if ok else 'FAILED'} "
          f"({time.perf_counter() - t0:.1f} s wall, compiles included)",
          flush=True)
    return ok


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the bank-sharded comparison on 4 chips")
    args = ap.parse_args()
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"FAILED: no repro package under {SRC}; run this script from "
              "a checkout of the repository", flush=True)
        return 2
    sys.path.insert(0, SRC)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax

    from repro.launch.compile_cache import use_compile_cache

    print(f"[a] compile cache: {use_compile_cache()}", flush=True)
    import importlib.metadata as md

    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = "not installed"
    devs = jax.devices()
    dev = devs[0]
    print(f"[a] jax {jax.__version__}, jaxlib {jax.lib.__version__}, "
          f"libtpu {libtpu}", flush=True)
    print(f"[a] devices: {devs}; kind {dev.device_kind!r}", flush=True)
    if dev.platform != "tpu":
        print(f"FAILED: platform is {dev.platform!r}, not 'tpu'; this "
              "script never falls back to the CPU", flush=True)
        return 1
    from repro.kernels import tuning

    if tuning.interpret_mode():
        print("FAILED: Pallas kernels would run in interpret mode", flush=True)
        return 1
    want = 4 if args.four_chips else 1
    if len(devs) < want:
        print(f"FAILED: {want} chip(s) needed, {len(devs)} found", flush=True)
        return 1

    ok = True
    if args.four_chips:
        params, train, test = front_end(train=False)
        ok &= run_phase("four chips", phase_four_chips, params, train, test,
                        dev)
    else:
        print("[b] training the student front-end", flush=True)
        params, train, test = front_end(train=True)
        ok &= run_phase("b", phase_front_end, params, train, test)
        ok &= run_phase("c", phase_serve, params, train, test, dev)
    if not ok:
        print("FAILED: see the phases above", flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
