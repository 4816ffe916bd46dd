"""The scheduler tick crosses the host-device boundary once each way.

  * the packed tick (`scheduler._packed_tick`: one int32 operand buffer in,
    one int32 (4, slots) result out) gives pred, escalate, margin and winner
    score bit-equal to `scheduler._batched_classify` called directly on the
    unpacked operands — full and ragged ticks, padding slots (empty window,
    score clamped to 0.0), tau = -inf, the reference, kernel and device
    backends, and a bank-sharded plan on four CPU devices;
  * a tick makes exactly one `jax.device_put` and one `jax.device_get`, of
    the expected sizes, and the jitted tick takes one per-tick array and
    returns one;
  * under a bank-sharded plan on four CPU devices (1x4 and 2x2 meshes) the
    super-bank and thresholds table carry the plan's shardings, the packed
    buffer is uploaded on its batch sharding, and warm ticks run under
    ``jax.transfer_guard("disallow")`` with answers bit-equal to one bank
    shard; the registry places its arrays once per generation (fleet
    registration, a drained reshard, a rolling reshard's flip) and never
    per tick;
  * a dense-head service answers escalations from the tick's fifth row:
    the answers of `_escalate_heads` called directly, one upload and one
    copy back per escalating step, the head tables placed once per head
    generation (whole on every device under a bank sharding, 1x4 and 2x2).
"""
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.distributed import context
from repro.serve import acam_service as svc_lib
from repro.serve import scheduler as sched_lib
from repro.serve.registry import TemplateBankRegistry
from test_bank_sharding import run_sub


@pytest.fixture(autouse=True)
def restore_mesh_context():
    """`HybridService.from_spec` installs its serving mesh into the
    process-wide context; put back whatever was there so no later test in
    this process inherits it."""
    saved_axes, saved_mesh = context.get(), context.get_mesh()
    try:
        yield
    finally:
        context.clear()
        if saved_axes is not None:
            context.set_mesh_axes(saved_axes.dp, saved_axes.model,
                                  saved_mesh)


N = 64
SLOTS = 8


def _fleet(backend, tau=None, registry_kw=None):
    reg = TemplateBankRegistry(N, class_bucket=8, **(registry_kw or {}))
    protos = {}
    for t in range(4):
        bank, _, p = svc_lib.make_synthetic_tenant(
            300 + t, num_classes=5 + t, num_features=N)
        reg.register(f"t{t}", bank)
        protos[f"t{t}"] = p
    # class row 0 left unprogrammed: a padding slot (empty window, pred 0)
    # reads a -inf score there, which the tick clamps to 0.0
    reg.evict("t0")
    del protos["t0"]
    sched = sched_lib.MicroBatchScheduler(reg, slots=SLOTS, backend=backend)
    if tau is not None:  # t1 has no head: its tau stays -inf
        sched.tau_fn = lambda tid: None if tid == "t1" else tau
    return reg, sched, protos


def _items(protos, fill, seed=5):
    tids = sorted(protos)
    out = []
    for i in range(fill):
        tid = tids[i % len(tids)]
        f, _ = svc_lib.sample_tenant_queries(seed + i, protos[tid], 1,
                                             noise=0.9)
        out.append(sched_lib.WorkItem(i, tid, np.asarray(f[0]),
                                      time.perf_counter()))
    return out


def _unpacked(reg, sched, items):
    """The tick's operands built one array each, as before packing, and
    `_batched_classify` on them -> (pred, esc, margin, score) per slot."""
    feats = np.zeros((SLOTS, N), np.float32)
    slot, lo, hi = (np.zeros((SLOTS,), np.int32) for _ in range(3))
    tau = np.full((SLOTS,), -np.inf, np.float32)
    for i, item in enumerate(items):
        e = reg.lookup(item.tenant_id)
        feats[i], slot[i] = item.features, e.slot
        lo[i], hi[i] = e.window
        t = sched.tau_fn(item.tenant_id) if sched.tau_fn else None
        if t is not None:
            tau[i] = t
    pred, per_class, margin, esc = (np.asarray(a) for a in
                                    sched_lib._batched_classify(
        reg.device_bank(), reg.thresholds_table(), jnp.asarray(feats),
        jnp.asarray(slot), jnp.asarray(lo), jnp.asarray(hi),
        jnp.asarray(tau), config=sched.engine_config,
        mesh_gen=context.generation()))
    score = per_class[np.arange(SLOTS), pred]
    score = np.where(np.isfinite(score), score, 0.0).astype(np.float32)
    return pred, esc.astype(np.int32), margin.view(np.int32), \
        score.view(np.int32)


def _packed(reg, sched, items):
    for item in items:
        sched.submit(item)
    dead, batch, packed = sched._pack(len(items), time.perf_counter())
    assert not dead and len(batch) == len(items)
    assert packed.shape == (SLOTS, N + 4) and packed.dtype == np.int32
    out = sched_lib._packed_tick(
        reg.device_bank(), reg.thresholds_table(), jax.device_put(packed),
        classify=sched_lib._batched_classify, config=sched.engine_config,
        mesh_gen=context.generation())
    return np.asarray(jax.device_get(out))


@pytest.mark.parametrize("tau", [None, 10.0], ids=["tau-inf", "tau10"])
@pytest.mark.parametrize("fill", [SLOTS, SLOTS - 3], ids=["full", "ragged"])
@pytest.mark.parametrize("backend", ["reference", "kernel", "device"])
def test_packed_tick_bit_equal_to_unpacked(backend, fill, tau):
    reg, sched, protos = _fleet(backend, tau)
    items = _items(protos, fill)
    pred, esc, margin, score = _unpacked(reg, sched, items)
    got = _packed(reg, sched, items)
    np.testing.assert_array_equal(got, np.stack([pred, esc, margin, score]))
    # padding slots: empty window, never escalated, score clamped to 0.0
    pad = slice(fill, SLOTS)
    assert (got[1, pad] == 0).all()
    assert (got[3, pad] == np.float32(0.0).view(np.int32)).all()
    heads = [i for i, it in enumerate(items) if it.tenant_id != "t1"]
    if tau is None:
        assert not esc.any()
    else:  # slots with a head escalate below tau, the others never
        assert esc[heads].any()
        assert not np.delete(esc, heads).any()

    # and the served results of a whole tick carry the same bits
    for item in items:
        sched.submit(item)
    res = sched.tick()
    assert [r.item.request_id for r in res] == list(range(fill))
    for i, r in enumerate(res):
        assert r.pred_local + r.entry.offset == pred[i]
        assert r.escalate == bool(esc[i])
        assert np.float32(r.margin).view(np.int32) == margin[i]
        assert np.float32(r.score).view(np.int32) == score[i]


def test_one_upload_one_copy_back_per_tick(monkeypatch):
    reg, sched, protos = _fleet("reference", tau=10.0)
    items = _items(protos, 3 * SLOTS - 2)
    for item in items[:SLOTS]:
        sched.submit(item)
    sched.tick()  # compile outside the spied ticks
    puts, gets, calls = [], [], []
    put, get, tick = jax.device_put, jax.device_get, sched_lib._packed_tick

    def spy_put(x, *a, **k):
        puts.append(x)
        return put(x, *a, **k)

    def spy_get(x, *a, **k):
        out = get(x, *a, **k)
        gets.append(out)
        return out

    def spy_tick(*args, **kw):
        out = tick(*args, **kw)
        calls.append((args, out))
        return out

    monkeypatch.setattr(jax, "device_put", spy_put)
    monkeypatch.setattr(jax, "device_get", spy_get)
    monkeypatch.setattr(sched_lib, "_packed_tick", spy_tick)
    before = dict(sched.stats.as_dict())
    for item in items[SLOTS:]:
        sched.submit(item)
    assert len(sched.drain()) == 2 * SLOTS - 2
    ticks = 2
    assert len(puts) == len(gets) == len(calls) == ticks
    for x in puts:
        assert isinstance(x, np.ndarray) and x.nbytes == SLOTS * (N + 4) * 4
    for y in gets:
        assert isinstance(y, np.ndarray) and y.nbytes == 16 * SLOTS
    for args, out in calls:
        # the bank and thresholds are the registry's cached device arrays:
        # the one per-tick operand is the uploaded buffer
        assert len(args) == 3
        assert args[0] is reg.device_bank()
        assert args[1] is reg.thresholds_table()
        assert isinstance(args[2], jax.Array)
        assert args[2].shape == (SLOTS, N + 4)
        assert isinstance(out, jax.Array)
        assert out.shape == (4, SLOTS) and out.dtype == jnp.int32
    after = sched.stats.as_dict()
    for key in ("classify_dispatches", "h2d_transfers", "d2h_transfers"):
        assert after[key] - before[key] == ticks, key
    assert after["bytes_back"] - before["bytes_back"] == 16 * SLOTS * ticks


def test_packed_tick_bit_equal_bank_sharded():
    """Four CPU devices, a 2x2 (data, model) mesh, the super-bank split
    over the model axis: the packed tick still gives the unpacked bits."""
    out = run_sub("""
        import os, sys
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        sys.path.insert(0, %r)
        import numpy as np
        from repro import match
        from repro.distributed import context
        from repro.launch.mesh import make_mesh
        import test_scheduler_transfer as t

        mesh = make_mesh((2, 2), ("data", "model"))
        context.set_mesh_axes("data", "model", mesh)
        for tau in (None, 10.0):
            reg, sched, protos = t._fleet("kernel", tau,
                                          dict(bank_shards=2))
            assert reg.bank_shards == 2
            c = reg.device_bank().templates.shape[0]
            plan, _ = match.plan_for(batch=t.SLOTS, num_classes=c)
            assert plan.bank_sharded, plan
            for fill in (t.SLOTS, t.SLOTS - 3):
                items = t._items(protos, fill)
                want = np.stack(t._unpacked(reg, sched, items))
                got = t._packed(reg, sched, items)
                assert np.array_equal(got, want), (tau, fill)
                print("OK", tau, fill)
        """ % os.path.dirname(__file__))
    assert out.count("OK") == 4


SHARDED_TICKS = """
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, %r)
import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.distributed import context
from repro.launch.mesh import make_mesh
import test_scheduler_transfer as t

DATA, SHARDS = %d, %d


def serve(sched, items, ticks):
    out = []
    for _ in range(ticks):
        for item in items:
            sched.submit(item)
        out.append([(r.item.tenant_id, r.pred_local, r.escalate,
                      np.float32(r.margin).view(np.int32),
                      np.float32(r.score).view(np.int32))
                     for r in sched.tick()])
    return out


_, one, protos = t._fleet("kernel", 10.0)
items = t._items(protos, t.SLOTS)
want = serve(one, items, 1)[0]

mesh = make_mesh((DATA, SHARDS), ("data", "model"))
context.set_mesh_axes("data", "model", mesh)
reg, sched, _ = t._fleet("kernel", 10.0, dict(bank_shards=SHARDS))
assert serve(sched, items, 1) == [want]  # compiles and places
assert reg.bank_placements == 1
place, _ = sched._placement(sched.engine_config)
assert place.shards == SHARDS
assert place.bank.is_equivalent_to(NamedSharding(mesh, P("model")), 3)
assert place.table.is_equivalent_to(NamedSharding(mesh, P()), 2)
assert place.batch.is_equivalent_to(
    NamedSharding(mesh, P("data" if DATA > 1 else None)), 2)
bank = reg.device_bank(place)
for a in (bank.templates, bank.lower, bank.upper, bank.valid):
    assert a.sharding == place.bank
assert bank.thresholds.sharding == place.table
assert reg.thresholds_table(place).sharding == place.table

uploads = []
put = jax.device_put


def spy_put(x, *a, **k):
    out = put(x, *a, **k)
    uploads.append(out)
    return out


jax.device_put = spy_put
with jax.transfer_guard("disallow"):
    got = serve(sched, items, 20)
jax.device_put = put
assert got == [want] * 20
assert len(uploads) == 20
assert all(u.sharding == place.batch for u in uploads)
assert reg.bank_placements == 1
print("OK")
"""


@pytest.mark.parametrize("data,shards", [(1, 4), (2, 2)],
                         ids=["1x4", "2x2"])
def test_sharded_tick_moves_only_operands_and_results(data, shards):
    """After warm-up a bank-sharded tick moves its packed buffer in and its
    result out and nothing else: the bank, the thresholds table and the
    buffer already sit on the engine's shardings."""
    out = run_sub(SHARDED_TICKS % (os.path.dirname(__file__), data, shards))
    assert out.strip().endswith("OK")


def test_bank_placed_once_per_generation():
    """Four CPU devices, a service built from its spec: registering the
    fleet places nothing; the first tick places the bank on the mesh; 20
    more ticks place nothing; a drained reshard and a rolling reshard's
    flip each place once more. Answers stay those of one bank shard."""
    out = run_sub("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import numpy as np
        from repro.match.config import EngineConfig
        from repro.serve import acam_service as svc_lib
        from repro.serve import spec as spec_lib
        from repro.serve.control import HybridService

        def spec(shards):
            return spec_lib.ServiceSpec(
                registry=spec_lib.RegistrySpec(num_features=64,
                                               class_bucket=8,
                                               initial_classes=64),
                engine=EngineConfig(backend="kernel", margin=True),
                mesh=spec_lib.MeshSpec(bank_shards=shards),
                scheduler=spec_lib.SchedulerSpec(slots=8),
                cascade=spec_lib.CascadeSpec(tau=6.0, tau_units="count"))

        def boot(shards):
            svc = HybridService.from_spec(spec(shards))
            protos = {}
            for t in range(4):
                bank, head, p = svc_lib.make_synthetic_tenant(
                    400 + t, num_classes=5 + t, num_features=64)
                svc.register_tenant(f"t{t}", bank, head=head)
                protos[f"t{t}"] = p
            reqs = []
            for i, (tid, p) in enumerate(sorted(protos.items())):
                f, _ = svc_lib.sample_tenant_queries(7 + i, p, 40,
                                                     noise=0.9)
                reqs += [svc_lib.ClassifyRequest(tid, f[j])
                         for j in range(40)]
            return svc, reqs

        def answers(svc, reqs):
            return [(r.tenant_id, r.pred, r.escalated,
                     np.float32(r.margin).view(np.int32))
                    for r in svc.serve(reqs)]

        def placed(svc):
            h, s = svc.health(), svc.registry.stats()
            assert h["bank_placements"] == s["bank_placements"]
            assert h["bank_bytes_placed"] == s["bank_bytes_placed"]
            return h["bank_placements"], h["bank_bytes_placed"]

        one, reqs = boot(1)
        want = answers(one, reqs)
        svc, _ = boot(2)
        reg = svc.registry
        assert placed(svc) == (0, 0)  # registration places nothing
        ticks = svc.scheduler.stats.ticks
        assert answers(svc, reqs) == want
        assert svc.scheduler.stats.ticks - ticks == 20
        assert placed(svc) == (1, reg.placement_bytes())
        assert answers(svc, reqs) == want  # 20 more ticks
        assert placed(svc) == (1, reg.placement_bytes())
        svc.reconfigure(svc.spec._replace(mesh=spec_lib.MeshSpec(
            bank_shards=4)))
        assert reg.bank_shards == 4 and placed(svc)[0] == 1
        assert answers(svc, reqs) == want
        n, b = placed(svc)
        assert n == 2
        svc.rolling_reshard(svc.spec._replace(mesh=spec_lib.MeshSpec(
            bank_shards=2)))
        assert answers(svc, reqs) == want
        assert placed(svc) == (3, b + reg.placement_bytes())
        print("OK")
        """)
    assert out.strip().endswith("OK")


# -- the dense head inside the tick -----------------------------------------

HEAD_SLOTS = 64


def _head_service(backend, shards=1):
    """A dense-head `HybridService` over four tenants with a head and one
    ("bare") without -> (service, requests mixing all five)."""
    from repro.match.config import EngineConfig
    from repro.serve import spec as spec_lib
    from repro.serve.control import HybridService

    svc = HybridService.from_spec(spec_lib.ServiceSpec(
        registry=spec_lib.RegistrySpec(num_features=N, class_bucket=8,
                                       initial_classes=64),
        engine=EngineConfig(backend=backend, margin=True),
        mesh=spec_lib.MeshSpec(bank_shards=shards),
        scheduler=spec_lib.SchedulerSpec(slots=HEAD_SLOTS),
        cascade=spec_lib.CascadeSpec(tau=10.0, tau_units="count")))
    reqs = []
    for t, tid in enumerate(["t0", "t1", "t2", "t3", "bare"]):
        bank, head, p = svc_lib.make_synthetic_tenant(
            500 + t, num_classes=5 + t, num_features=N)
        svc.register_tenant(tid, bank, head=None if tid == "bare" else head)
        f, _ = svc_lib.sample_tenant_queries(11 + t, p, 60, noise=0.9)
        reqs += [svc_lib.ClassifyRequest(tid, f[j]) for j in range(60)]
    order = np.random.RandomState(0).permutation(len(reqs))
    return svc, [reqs[i] for i in order]


def _recorded_ticks(svc):
    """Wrap the scheduler's tick: every tick's `SlotResult`s, in order."""
    ticks, tick = [], svc.scheduler.tick

    def recorded():
        out = tick()
        ticks.append(out)
        return out

    svc.scheduler.tick = recorded
    return ticks


@pytest.mark.parametrize("fill", [HEAD_SLOTS, 37], ids=["full", "ragged"])
@pytest.mark.parametrize("backend", ["reference", "kernel"])
def test_head_in_tick_equals_head_called_directly(backend, fill):
    """Each response's pred, escalated and margin are what the tick's
    winner and `_escalate_heads`, called directly on the tick's escalated
    slots, give; every escalated answer was taken from the tick's row."""
    svc, reqs = _head_service(backend)
    ticks = _recorded_ticks(svc)
    responses = []
    for i in range(0, 2 * fill, fill):
        for r in reqs[i:i + fill]:
            svc.submit(r)
        responses += svc.step()
    assert [len(t) for t in ticks] == [fill, fill]
    by_id = {r.request_id: r for r in responses}
    w, b = svc._head_tables()
    n_esc = 0
    for tick in ticks:
        assert any(r.item.tenant_id == "bare" for r in tick)
        esc = [r for r in tick if r.escalate]
        assert esc and all(r.item.tenant_id != "bare" for r in esc)
        head = np.asarray(svc_lib._escalate_heads(
            w, b, jnp.asarray(np.stack([r.item.features for r in esc])),
            jnp.asarray([r.entry.slot for r in esc], jnp.int32),
            jnp.asarray([r.entry.num_classes for r in esc], jnp.int32)))
        want = {r.item.request_id: int(h) for r, h in zip(esc, head)}
        for r in tick:
            got = by_id[r.item.request_id]
            assert got.escalated == r.escalate
            assert got.pred == want.get(r.item.request_id, r.pred_local)
            assert np.float32(got.margin).view(np.int32) == \
                np.float32(r.margin).view(np.int32)
        n_esc += len(esc)
    m = svc.metrics()
    assert m["escalated"] == n_esc
    assert m["head_in_tick"] == m["escalated"]
    assert m["escalation_dispatches"] == 2


def test_escalating_step_one_upload_one_copy_back(monkeypatch):
    """A step whose tick escalates makes one `jax.device_put` and one
    `jax.device_get`, and calls the dense head nowhere but inside the
    traced tick."""
    svc, reqs = _head_service("reference")
    calls = []
    head = svc_lib._escalate_heads

    def spy_head(*args):
        calls.append(any(isinstance(a, jax.core.Tracer) for a in args))
        return head(*args)

    monkeypatch.setattr(svc_lib, "_escalate_heads", spy_head)
    for r in reqs[:HEAD_SLOTS]:
        svc.submit(r)
    svc.step()  # compiles the tick with the head and places its tables
    assert calls == [True]
    puts, gets = [], []
    put, get = jax.device_put, jax.device_get

    def spy_put(x, *a, **k):
        puts.append(x)
        return put(x, *a, **k)

    def spy_get(x, *a, **k):
        gets.append(x)
        return get(x, *a, **k)

    monkeypatch.setattr(jax, "device_put", spy_put)
    monkeypatch.setattr(jax, "device_get", spy_get)
    svc.reset_metrics()
    steps = 0
    for i in range(HEAD_SLOTS, len(reqs) - HEAD_SLOTS, HEAD_SLOTS):
        for r in reqs[i:i + HEAD_SLOTS]:
            svc.submit(r)
        del puts[:], gets[:]
        responses = svc.step()
        assert any(r.escalated for r in responses)
        assert len(puts) == len(gets) == 1
        assert puts[0].shape == (HEAD_SLOTS, N + 4)
        assert gets[0].shape == (5, HEAD_SLOTS)
        steps += 1
    assert calls == [True]  # no call outside the one trace
    m = svc.metrics()
    assert m["escalation_dispatches"] == steps > 1
    assert m["head_in_tick"] == m["escalated"] > 0
    st = svc.scheduler.stats
    assert st.h2d_transfers == st.d2h_transfers == st.ticks == steps
    assert st.bytes_back == 20 * HEAD_SLOTS * steps


def test_escalation_without_head_row_fails():
    """A scheduler driven without the service's ``head_fn`` returns no
    head row (``head_pred`` -1); the service refuses to answer such an
    escalation rather than dispatch the head a second time."""
    svc, reqs = _head_service("reference")
    svc.scheduler.head_fn = None
    ticks = _recorded_ticks(svc)
    for r in reqs[:HEAD_SLOTS]:
        svc.submit(r)
    with pytest.raises(RuntimeError, match="no head row"):
        svc.step()
    assert any(r.escalate for r in ticks[0])
    assert all(r.head_pred == -1 for r in ticks[0])
    assert svc.metrics()["head_in_tick"] == 0


HEAD_SHARDED = """
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, %r)
import jax
import numpy as np
from repro.serve import acam_service as svc_lib
import test_scheduler_transfer as t

SHARDS = %d
one, reqs = t._head_service("kernel")


def steps(s, n):
    out = []
    for i in range(n):
        for r in reqs[(i * 37) %% 200:(i * 37) %% 200 + t.HEAD_SLOTS]:
            s.submit(r)
        out.append([(r.tenant_id, r.pred, r.escalated,
                     np.float32(r.margin).view(np.int32))
                    for r in s.step()])
    return out


def placed(s):
    return s.health()["head_placements"], s.registry.bank_placements


want = steps(one, 20)
svc, _ = t._head_service("kernel", shards=SHARDS)
place, _ = svc.scheduler._placement(svc.scheduler.engine_config)
assert place.shards == SHARDS
assert place.batch.spec == jax.sharding.PartitionSpec(
    "data" if SHARDS < 4 else None)
assert steps(svc, 1) == want[:1]  # compiles, places bank and heads
assert placed(svc) == (1, 1)
w, b = svc._head_tables(place)
assert w.sharding == b.sharding == place.table
# a call without a placement is given the placed copy, not a second one
assert svc._head_tables()[0] is w and placed(svc) == (1, 1)

moved = []
put, get = jax.device_put, jax.device_get


def spy_put(x, *a, **k):
    moved.append("put")
    return put(x, *a, **k)


def spy_get(x, *a, **k):
    moved.append("get")
    return get(x, *a, **k)


def guarded(n):
    del moved[:]
    jax.device_put, jax.device_get = spy_put, spy_get
    try:
        with jax.transfer_guard("disallow"):
            got = steps(svc, n)
    finally:
        jax.device_put, jax.device_get = put, get
    assert moved == ["put", "get"] * n, moved
    assert all(any(r[2] for r in step) for step in got)
    return got


assert guarded(20) == want
assert placed(svc) == (1, 1)
m = svc.metrics()
assert m["head_in_tick"] == m["escalated"] > 0

# a new tenant's head places the tables once more, and only once
bank, head, p = svc_lib.make_synthetic_tenant(599, num_classes=7,
                                              num_features=t.N)
svc.register_tenant("late", bank, head=head)
steps(svc, 1)
assert placed(svc) == (2, 2)
guarded(20)
assert placed(svc) == (2, 2)
svc.update_tenant("late", bank, head=head)
steps(svc, 1)
assert placed(svc) == (3, 3)
guarded(20)
assert placed(svc) == (3, 3)
print("OK")
"""


@pytest.mark.parametrize("shards", [4, 2], ids=["1x4", "2x2"])
def test_sharded_head_tables_placed_once(shards):
    """Four CPU devices, the bank sharded four ways (1x4 mesh) or two ways
    with a batch-sharded buffer (2x2 mesh): 20 escalating steps under
    ``jax.transfer_guard("disallow")`` move the packed buffer in and the
    result out and nothing else, with the answers of one shard; the head
    tables sit whole on every device, placed once, and once more after a
    tenant's head is registered or updated."""
    out = run_sub(HEAD_SHARDED % (os.path.dirname(__file__), shards))
    assert out.strip().endswith("OK")
