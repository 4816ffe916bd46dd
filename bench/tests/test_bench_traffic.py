"""The traffic generator: seeded, faithful to its parameters, and the
closed loop keeps every tick full."""
import os
import subprocess
import sys

import numpy as np
import pytest

import deploy
import drive
import traffic
from tinycell import BENCH, tiny

BIG_SEED = 2**31 + 12345


def test_same_seed_same_stream_other_seed_other_stream():
    cfg = tiny()
    a = traffic.arrivals(500.0, 4.0, BIG_SEED)
    np.testing.assert_array_equal(a, traffic.arrivals(500.0, 4.0, BIG_SEED))
    assert not np.array_equal(a[:50], traffic.arrivals(500.0, 4.0, 3)[:50])
    w = traffic.zipf_weights(cfg["tenants"], 1.2, BIG_SEED)
    fleet = deploy.make_fleet(cfg, BIG_SEED)
    p1 = deploy.make_pool(cfg, fleet, w, 64, BIG_SEED)
    p2 = deploy.make_pool(cfg, deploy.make_fleet(cfg, BIG_SEED), w, 64,
                          BIG_SEED)
    np.testing.assert_array_equal(p1.features, p2.features)
    np.testing.assert_array_equal(p1.tenant, p2.tenant)


@pytest.mark.parametrize("rate,seconds", [(200.0, 50.0), (20000.0, 2.0)])
def test_poisson_counts_and_gaps_agree_with_the_rate(rate, seconds):
    due = traffic.arrivals(rate, seconds, BIG_SEED)
    mean = rate * seconds
    assert abs(len(due) - mean) < 5 * np.sqrt(mean)
    assert (np.diff(due) >= 0).all() and 0 <= due[0] and due[-1] < seconds
    gaps = np.diff(due)
    assert abs(gaps.mean() * rate - 1) < 0.05
    assert abs(gaps.std() * rate - 1) < 0.05  # exponential: sd == mean
    # counts per 1/rate-scaled bin are Poisson: variance == mean
    counts = np.bincount((due * rate / 10).astype(int))
    assert abs(counts.var() / counts.mean() - 1) < 0.15


def test_zipf_shares_agree_with_their_parameter():
    t, a = 64, 1.2
    w = traffic.zipf_weights(t, a, BIG_SEED)
    ranked = np.sort(w)[::-1]
    want = 1.0 / np.arange(1, t + 1) ** a
    np.testing.assert_allclose(ranked, want / want.sum(), rtol=1e-12)
    assert not np.array_equal(w, traffic.zipf_weights(t, a, 4))
    cfg = tiny(tenants=t)
    pool = deploy.make_pool(cfg, deploy.make_fleet(cfg, 1), w, 100000, 1)
    share = np.bincount(pool.tenant, minlength=t) / len(pool)
    sd = np.sqrt(w * (1 - w) / len(pool))
    assert (np.abs(share - w) <= 5 * sd + 1e-4).all()


class _Sched:
    def __init__(self):
        self.qsize = 0


class _Svc:
    """Stands in for the service: counts the queue and records its depth
    at every step."""

    def __init__(self, slots):
        self.scheduler = _Sched()
        self.slots = slots
        self.depths = []
        self.rid = 0

    def submit(self, req):
        self.scheduler.qsize += 1
        self.rid += 1
        return self.rid

    def step(self):
        self.depths.append(self.scheduler.qsize)
        self.scheduler.qsize -= min(self.slots, self.scheduler.qsize)
        return []

    def drain(self):
        self.scheduler.qsize = 0
        return []


def test_offline_keeps_at_least_slots_requests_queued():
    svc = _Svc(slots=64)
    drive.closed_loop(svc, list(range(100)), drive.Record({}), depth=2 * 64,
                      seconds=0.05, trace=False)
    assert len(svc.depths) > 10
    assert min(svc.depths) >= 64 and max(svc.depths) == 128


def test_mix_files_are_valid():
    for name in os.listdir(os.path.join(BENCH, "mixes")):
        traffic.check_mix(deploy.load_json("mixes", name[:-5]))
    with pytest.raises(ValueError):
        traffic.check_mix({"kind": "poisson", "zipf_a": 1.2, "pool": 8})


def test_run_refuses_a_cpu_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "t64-offline", "--seed", str(BIG_SEED), "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "refused" in p.stderr


def test_run_refuses_without_the_system_under_test(tmp_path):
    """A checkout of only BENCHMARK.json and bench/ has no program."""
    import shutil

    root = os.path.dirname(BENCH)
    shutil.copy(os.path.join(root, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "t64-offline",
         "--seed", "1", "--seconds", "1", "--trace", "0"], cwd=tmp_path,
        capture_output=True, text=True, env=env, timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""
