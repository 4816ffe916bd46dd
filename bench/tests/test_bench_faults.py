"""A whole run on the CPU (the look for a chip skipped, kernels in interpret
mode), sound and with the timed path broken underneath: each fault must
turn ``correct`` false."""
import os
import subprocess
import sys

import pytest

from tinycell import BENCH, OFFLINE, SERVER, drive, tiny

#: the paper's width, so that a wrong head answer lies far below the best
WIDE = dict(num_features=784, num_classes=10, class_bucket=16, tau=4.0)


@pytest.mark.parametrize("mix", [OFFLINE, SERVER])
def test_sound_run_is_correct_and_reports_its_metrics(mix, tmp_path):
    out = drive(tiny(), mix, tmp_path)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    want = {"setup_s"} | ({"requests_per_s"} if mix is OFFLINE else
                          {"latency_p50_ms", "latency_p95_ms"})
    assert set(out["metrics"]) == want
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("mix", [OFFLINE, SERVER])
def test_traced_run_reports_host_metrics_and_window(mix, tmp_path):
    out = drive(tiny(), mix, tmp_path, trace=True)
    assert out["correct"]
    name = "offline" if mix is OFFLINE else "server"
    assert {f"submit_us.{name}", f"step_ms.{name}"} <= set(out["metrics"])
    assert out["device"]["window_s"] > 0


def test_answer_altered_where_produced(tmp_path, monkeypatch):
    from repro.serve import scheduler

    tick = scheduler._batched_classify

    def altered(*a, **k):
        pred, per_class, margin, esc = tick(*a, **k)
        return pred.at[0].add(1), per_class, margin, esc

    monkeypatch.setattr(scheduler, "_batched_classify", altered)
    out = drive(tiny(), OFFLINE, tmp_path)
    assert not out["correct"]
    assert out["checks"]["acam_mismatch"]["value"] > 0


def test_escalated_answer_altered_where_produced(tmp_path, monkeypatch):
    from repro.serve import acam_service

    head = acam_service._escalate_heads

    def altered(w, b, feats, slot, ncls):
        return (head(w, b, feats, slot, ncls) + 1) % ncls

    monkeypatch.setattr(acam_service, "_escalate_heads", altered)
    out = drive(tiny(**WIDE), OFFLINE, tmp_path)
    assert not out["correct"]
    assert out["checks"]["head_gap"]["value"] > \
        out["checks"]["head_gap"]["limit"]


@pytest.mark.parametrize("mix", [OFFLINE, SERVER])
def test_half_of_the_batch_left_out(tmp_path, monkeypatch, mix):
    from repro.serve.scheduler import MicroBatchScheduler

    tick = MicroBatchScheduler.tick

    def half(self):
        out = tick(self)
        return out[:(len(out) + 1) // 2]

    monkeypatch.setattr(MicroBatchScheduler, "tick", half)
    out = drive(tiny(), mix, tmp_path)
    assert not out["correct"]
    assert out["checks"]["unanswered"]["value"] > 0


SHARDED = r'''
import json, sys, time
sys.path[:0] = [{bench!r}, {tests!r}, {src!r}]
import jax
from repro.match import engine
import run
from tinycell import OFFLINE, tiny
if {broken}:
    def local_only(top1, gidx, top2, axis, num_shards, cap, strategy="x"):
        import jax.numpy as jnp
        return gidx, jnp.minimum(top1 - top2, cap)
    engine._reduce_margin = local_only
cfg = tiny(tenants=8, bank_shards=4)
out = run.execute(cfg, OFFLINE, [], seed=2**31 + 3, seconds=0.5,
                  trace=False, devices=jax.devices(),
                  t_start=time.perf_counter())
print(json.dumps(out))
'''


@pytest.mark.parametrize("broken", [False, True])
def test_exchange_between_chips_left_out(broken):
    """On four CPU devices, bank sharded four ways: sound, then with the
    cross-shard reduce replaced by each shard's local answer."""
    root = os.path.dirname(BENCH)
    code = SHARDED.format(bench=BENCH, tests=os.path.dirname(__file__),
                          src=os.path.join(root, "src"), broken=broken)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("REPRO_FORCE_MESH", None)
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    import json

    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["device"]["count"] == 4
    assert out["correct"] is (not broken), out["checks"]
