"""A deployment made from the seed: the tenant fleet, its request pool, and
the service that serves it.

A configuration file (``bench/configs/<name>.json``) fixes the sizes and the
assumptions; everything random is drawn here from ``--seed``, on the host,
with numpy only. The service under test receives the fleet through its own
registration API (`HybridService.register_tenant`), so the plain reference
(`reference.py`) reads the very arrays made here and nothing the program
derived from them.

Each tenant of the fleet holds C classes over N features:

    base_t      ~ N(0, 1)^N                     the tenant's feature level
    proto_tc    = base_t + class_spread * u_tc  class prototypes, u ~ N(0, 1)
    sub_tck     = proto_tc + template_spread * v_tck   K sub-prototypes
    thr_t       = mean_c proto_tc               mean-based thresholds (Fig. 1)
    T_tck       = 1[sub_tck > thr_t]            binary point templates (Eq. 8)
    head_t      = nearest-centroid logits  f . proto_tc - |proto_tc|^2 / 2

A request of tenant t asks for one of its classes c: features
``proto_tc + query_noise * n`` with n ~ N(0, 1)^N.
"""
from __future__ import annotations

import dataclasses
import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

#: stream tags: each kind of draw has a generator of its own, so that
#: changing one (say the pool size) leaves the others as they were
FLEET, POOL, ARRIVALS, SAMPLE = 1, 2, 3, 4


def rng(seed: int, tag: int) -> np.random.Generator:
    """The generator for one stream of one seed (any non-negative int)."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), tag]))


def load_json(kind: str, name: str) -> dict:
    """``bench/<kind>/<name>.json``: a configuration or a traffic mix."""
    with open(os.path.join(HERE, kind, f"{name}.json")) as f:
        return json.load(f)


@dataclasses.dataclass(frozen=True)
class Fleet:
    thresholds: np.ndarray  # (T, N) float32
    templates: np.ndarray  # (T, C, K, N) float32 in {0, 1}
    protos: np.ndarray  # (T, C, N) float32
    head_w: np.ndarray  # (T, N, C) float32
    head_b: np.ndarray  # (T, C) float32

    @property
    def tenants(self) -> int:
        return self.templates.shape[0]


def make_fleet(cfg: dict, seed: int) -> Fleet:
    t, c, k, n = (cfg["tenants"], cfg["num_classes"],
                  cfg["templates_per_class"], cfg["num_features"])
    a = cfg["assumed"]
    g = rng(seed, FLEET)
    base = g.standard_normal((t, 1, n), np.float32)
    protos = base + np.float32(a["class_spread"]) * g.standard_normal(
        (t, c, n), np.float32)
    sub = protos[:, :, None, :] + np.float32(a["template_spread"]) * \
        g.standard_normal((t, c, k, n), np.float32)
    thr = protos.mean(axis=1, dtype=np.float32)
    templates = (sub > thr[:, None, None, :]).astype(np.float32)
    head_w = np.ascontiguousarray(protos.transpose(0, 2, 1))
    head_b = (-0.5 * np.sum(protos * protos, axis=-1)).astype(np.float32)
    return Fleet(thr, templates, protos, head_w, head_b)


@dataclasses.dataclass(frozen=True)
class Pool:
    """Requests made ahead of the window; the window cycles through them."""

    tenant: np.ndarray  # (P,) int
    features: np.ndarray  # (P, N) float32

    def __len__(self) -> int:
        return len(self.tenant)


def make_pool(cfg: dict, fleet: Fleet, weights: np.ndarray, size: int,
              seed: int) -> Pool:
    """``size`` requests: tenants drawn by ``weights`` (the mix's tenant
    popularity), classes uniform, features around the class prototype."""
    g = rng(seed, POOL)
    tenant = g.choice(fleet.tenants, size=size, p=weights)
    label = g.integers(0, cfg["num_classes"], size=size)
    noise = g.standard_normal((size, cfg["num_features"]), np.float32)
    feats = fleet.protos[tenant, label] + \
        np.float32(cfg["assumed"]["query_noise"]) * noise
    return Pool(tenant, feats.astype(np.float32))


def tenant_id(t: int) -> str:
    return f"tenant-{t}"


def service_spec(cfg: dict, *, trace: bool):
    """The `ServiceSpec` of a configuration: the kernel backend, exact
    answers (no shedding, no deadline) and an admission bound no mix
    reaches."""
    from repro.match.config import EngineConfig
    from repro.serve import spec as spec_lib

    buckets = -(-cfg["num_classes"] // cfg["class_bucket"])
    return spec_lib.ServiceSpec(
        registry=spec_lib.RegistrySpec(
            num_features=cfg["num_features"],
            k_max=cfg["templates_per_class"],
            class_bucket=cfg["class_bucket"],
            initial_classes=cfg["tenants"] * buckets * cfg["class_bucket"],
            initial_tenants=cfg["tenants"]),
        engine=EngineConfig(method=cfg["method"], backend="kernel",
                            margin=True),
        mesh=spec_lib.MeshSpec(bank_shards=cfg["bank_shards"]),
        scheduler=spec_lib.SchedulerSpec(slots=cfg["slots"]),
        cascade=spec_lib.CascadeSpec(tau=cfg["tau"], tau_units="count",
                                     max_queue=cfg["max_queue"]),
        obs=spec_lib.ObsSpec(profile_annotations=trace))


def make_service(cfg: dict, fleet: Fleet, *, trace: bool):
    """Build the service from its spec and register every tenant."""
    from repro.core.templates import TemplateBank
    from repro.serve.control import HybridService

    svc = HybridService.from_spec(service_spec(cfg, trace=trace))
    valid = np.ones(fleet.templates.shape[1:3], bool)
    for t in range(fleet.tenants):
        bits = fleet.templates[t]
        svc.register_tenant(
            tenant_id(t),
            TemplateBank(bits, bits, bits, valid, fleet.thresholds[t]),
            head=(fleet.head_w[t], fleet.head_b[t]))
    return svc
