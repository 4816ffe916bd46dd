"""Helpers of the benchmark's tests: a configuration at a test's size, and a
whole run driven on the CPU (the look for a chip skipped)."""
import importlib.util
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def tiny(name: str = "cifar10-k2-t64", **over) -> dict:
    """A configuration file's keys at a test's size: 64 features, 4 classes,
    4 tenants, 8 slots."""
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        cfg = json.load(f)
    cfg.update(num_features=64, num_classes=4, tenants=4, class_bucket=8,
               slots=8, tau=2.0)
    cfg.update(over)
    return cfg


OFFLINE = {"kind": "closed", "depth_ticks": 2, "zipf_a": 1.2, "pool": 256}
SERVER = {"kind": "poisson", "rate_per_s": 400.0, "zipf_a": 1.2, "pool": 256}


def load_run():
    """``bench/run.py`` as a module of its own name: a plain ``import run``
    can find another ``run`` module (``benchmarks/run.py``) first when the
    whole suite shares a process."""
    spec = importlib.util.spec_from_file_location(
        "bench_run", os.path.join(BENCH, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules["bench_run"] = mod  # before exec: its dataclasses look it up
    spec.loader.exec_module(mod)
    return mod


#: the metrics a test run reads, by mix and trace (units as in BENCHMARK.json)
METRICS = {
    ("closed", False): ["requests_per_s", "setup_s"],
    ("poisson", False): ["latency_p50_ms", "latency_p95_ms", "setup_s"],
    ("closed", True): ["submit_us.offline", "step_ms.offline",
                       "dispatch_ms.offline", "serve_kernel_us.offline",
                       "serve_kernel_roofline.offline",
                       "device_idle_share.offline"],
    ("poisson", True): ["submit_us.server", "step_ms.server",
                        "dispatch_ms.server", "serve_kernel_us.server",
                        "device_idle_share.server",
                        "latency_p99_ms.server"],
}


def drive(cfg: dict, mix: dict, tmp_path, *, seed: int = 2**31 + 11,
          seconds: float = 0.5, trace: bool = False) -> dict:
    """`run.execute` on the host's devices: the result line of one run."""
    import jax

    run = load_run()

    entries = [{"name": m, "unit": "-"}
               for m in METRICS[(mix["kind"], trace)]]
    return run.execute(cfg, mix, entries, seed=seed, seconds=seconds,
                       trace=trace, devices=jax.devices(),
                       t_start=time.perf_counter(),
                       trace_dir=str(tmp_path / "trace"))
