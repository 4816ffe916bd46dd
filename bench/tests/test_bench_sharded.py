"""The bank-sharded cell's configuration (``cifar10-k2-t512-4shard``) at a
test's size on four CPU devices, sound and with the cross-shard reduce
broken, and the reader of its collectives' device time."""
import dataclasses
import json
import os
import subprocess
import sys

import pytest

import trace_reduce as tr
from test_bench_trace import DATA, hand_made
from tinycell import BENCH, load_run

SHARDED_CELL = r'''
import json, sys, time
sys.path[:0] = [{bench!r}, {tests!r}, {src!r}]
import jax
from repro.match import engine
import run
from tinycell import OFFLINE, tiny
if {broken}:
    def shard0(top1, gidx, top2, axis, num_shards, cap, strategy="x"):
        import jax.numpy as jnp
        t, i, r = (jax.lax.all_gather(x, axis)[0] for x in (top1, gidx, top2))
        top2g = jnp.maximum(r, t - cap)
        margin = jnp.where(jnp.isfinite(t), t - top2g, 0.0)
        return i.astype(jnp.int32), margin.astype(jnp.float32)
    engine._reduce_margin = shard0
cfg = tiny("cifar10-k2-t512-4shard", tenants=8)
assert cfg["bank_shards"] == 4
entries = [{{"name": m, "unit": "-"}} for m in ("requests_per_s", "setup_s")]
out = run.execute(cfg, OFFLINE, entries, seed=2**31 + 5, seconds=0.5,
                  trace=False, devices=jax.devices(),
                  t_start=time.perf_counter())
print(json.dumps(out))
'''


@pytest.mark.parametrize("broken", [False, True], ids=["sound", "shard0"])
def test_bank_sharded_configuration(broken):
    """``cifar10-k2-t512-4shard`` at a test's size (8 tenants, the file's
    four bank shards) on four CPU devices: sound, it is correct and reports
    its metrics; with the cross-shard reduce replaced by shard 0's own
    partial, its answers differ from the reference."""
    root = os.path.dirname(BENCH)
    code = SHARDED_CELL.format(bench=BENCH, tests=os.path.dirname(__file__),
                               src=os.path.join(root, "src"), broken=broken)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("REPRO_FORCE_MESH", None)
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["device"]["count"] == 4
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"requests_per_s", "setup_s"}
    assert out["correct"] is (not broken), out["checks"]
    if broken:
        assert out["checks"]["acam_mismatch"]["value"] > 0


@dataclasses.dataclass
class Window:
    ticks_in_window: int


@dataclasses.dataclass
class Run:
    trace: object
    window: Window


def test_collective_reader():
    """collective_us: the collectives' device time on device 0 inside the
    window, over the window's ticks; nothing from an untraced run or a
    trace without a collective."""
    read = load_run().reader("collective_us.offline")
    ops = {0: [(5, 10, "all-gather.1 s32[4,64]"),
               (12, 20, "acam_match_serve.1 (f32[64,2048]"),
               (20, 23, "all-gather.2 f32[4,64]"),
               (30, 37, "all-reduce.3 f32[64,1]"),
               (50, 52, "collective-permute.4 f32[64]"),
               (120, 130, "all-reduce.5 f32[64,1]")],  # after the window
           1: [(5, 45, "all-gather.1 s32[4,64]")]}  # another device
    t = tr.Trace(window=(0, 100), ops=ops, host=[(0, 100, tr.WINDOW)])
    assert read(Run(t, Window(3))) == pytest.approx((5 + 3 + 7 + 2) / 3e3)
    assert read(Run(t, Window(0))) is None
    assert read(Run(None, Window(3))) is None
    assert read(Run(hand_made(), Window(1))) == pytest.approx(10e-3)
    one_chip = tr.load(os.path.join(DATA, "t64-offline.xplane.pb"))
    assert read(Run(one_chip, Window(8))) is None
