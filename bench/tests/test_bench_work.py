"""The least work behind serve_kernel_roofline: counted from the deployment
and the tick's requests alone."""
import numpy as np
import pytest

import deploy
import drive
import traffic
import work
from tinycell import tiny

V5E = work.peaks("TPU v5 lite")


def test_counts_by_hand():
    cfg = tiny(num_features=784, num_classes=10)
    ops, nbytes = work.tick_work(cfg, [3, 3, 7])
    assert ops == 3 * 2 * 784 * 2 * 10
    assert nbytes == 2 * 10 * 2 * 784 / 8 + 3 * 784 * 4
    least = work.least_seconds(cfg, [3, 3, 7], V5E)
    assert least == max(ops / 393e12, nbytes / 819e9)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")


def _tick_tenants(cfg, backend, class_bucket):
    """Tenant indices of each tick of a service serving the same pool."""
    from repro.serve.acam_service import ClassifyRequest

    cfg = dict(cfg, class_bucket=class_bucket)
    fleet = deploy.make_fleet(cfg, 9)
    w = traffic.zipf_weights(cfg["tenants"], 1.2, 9)
    pool = deploy.make_pool(cfg, fleet, w, 40, 9)
    svc = deploy.make_service(cfg, fleet, trace=False)
    svc.scheduler.set_engine(svc.spec.engine._replace(backend=backend))
    rec = drive.Record({deploy.tenant_id(t): t
                        for t in range(cfg["tenants"])})
    drive.record_ticks(svc, rec)
    for t, f in zip(pool.tenant.tolist(), pool.features):
        svc.submit(ClassifyRequest(deploy.tenant_id(t), f))
    svc.drain()
    return rec.tick_tenants(0, len(rec.tick_fill))


def test_count_ignores_backend_and_bank_layout():
    """Another backend, or another padding of the super-bank (a bigger class
    bucket), serves the same ticks: the least work must not move."""
    cfg = tiny()
    base = _tick_tenants(cfg, "kernel", 8)
    for backend, bucket in (("reference", 8), ("kernel", 16)):
        other = _tick_tenants(cfg, backend, bucket)
        assert other == base
        assert [work.least_seconds(cfg, t, V5E) for t in other] == \
            [work.least_seconds(cfg, t, V5E) for t in base]
    assert np.sum([len(t) for t in base]) == 40
