"""The plain reference against the service, request by request, at a test's
size (kernels in interpret mode); and the control, which must fail."""
import numpy as np

import check
import deploy
import drive
import reference
import traffic
from tinycell import tiny


def serve_all(cfg, fleet, pool, backend="kernel"):
    """Serve every pool request once; -> (winner, margin, escalate, pred)."""
    from repro.serve.acam_service import ClassifyRequest

    svc = deploy.make_service(cfg, fleet, trace=False)
    if backend != "kernel":
        svc.scheduler.set_engine(svc.spec.engine._replace(backend=backend))
    rec = drive.Record({deploy.tenant_id(t): t
                        for t in range(cfg["tenants"])})
    drive.record_ticks(svc, rec)
    ids = [svc.submit(ClassifyRequest(deploy.tenant_id(t), f))
           for t, f in zip(pool.tenant.tolist(), pool.features)]
    got = {r.request_id: r for r in svc.drain()}
    win = dict(zip(rec.slot_rid, rec.slot_win))
    return (np.array([win[i] for i in ids]),
            np.array([got[i].margin for i in ids]),
            np.array([got[i].escalated for i in ids]),
            np.array([got[i].pred for i in ids]))


def make(cfg, seed=5, size=96):
    fleet = deploy.make_fleet(cfg, seed)
    w = traffic.zipf_weights(cfg["tenants"], 1.2, seed)
    return fleet, deploy.make_pool(cfg, fleet, w, size, seed)


def test_reference_matches_service_request_by_request():
    cfg = tiny()
    fleet, pool = make(cfg)
    got = serve_all(cfg, fleet, pool)
    want = reference.answers(fleet, pool.tenant, pool.features, cfg["tau"])
    for g, w, name in zip(got, want, ("winner", "margin", "escalate",
                                      "pred")):
        np.testing.assert_array_equal(g, w, err_msg=name)
    assert 0 < want[2].sum() < len(pool), "both cascade branches occur"


def test_reference_matches_jnp_reference_backend():
    cfg = tiny(num_classes=10, class_bucket=16)
    fleet, pool = make(cfg, seed=2**32 + 3)
    got = serve_all(cfg, fleet, pool, backend="reference")
    want = reference.answers(fleet, pool.tenant, pool.features, cfg["tau"])
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


def test_margin_is_capped_for_a_single_class_tenant():
    cfg = tiny(num_classes=1)
    fleet, pool = make(cfg, size=8)
    winner, margin, esc = reference.acam(fleet, pool.tenant, pool.features,
                                         cfg["tau"])
    assert (winner == 0).all()
    assert (margin == cfg["num_features"]).all() and not esc.any()


def test_round_mantissa():
    x = np.array([1.0, 1.00390625, 1.01171875, -3.14159], np.float32)
    np.testing.assert_array_equal(reference.round_mantissa(x, 7),
                                  [1.0, 1.0, 1.015625, -3.140625])
    np.testing.assert_array_equal(reference.round_mantissa(x, 3)[:1], [1.0])
    assert reference.round_mantissa(np.float32([1.0625]), 3)[0] == 1.0
    assert reference.round_mantissa(np.float32([1.1875]), 3)[0] == 1.25


def test_control_is_not_correct_at_the_cells_width():
    """The control (reference one precision step down, in the program's
    place) fails the limits of the configuration it guards: the real
    784-feature fleet, a pool the size of a short window's sample."""
    for name in ("cifar10-k2-t64", "cifar10-k2-t256"):
        cfg = deploy.load_json("configs", name)
        for seed in (1, 2**31 + 5, 77):
            fleet = deploy.make_fleet(cfg, seed)
            w = traffic.zipf_weights(cfg["tenants"], 1.2, seed)
            pool = deploy.make_pool(cfg, fleet, w, 4096, seed)
            idx = np.arange(len(pool))
            got = reference.answers(fleet, pool.tenant, pool.features,
                                    cfg["tau"], precision="control")
            values = check.numbers(fleet, pool, idx, got, cfg["tau"])
            values["unanswered"] = 0
            correct, _ = check.verdict(values, cfg["limits"])
            assert not correct, (name, seed, values)
            assert values["acam_mismatch"] > 0
