#!/usr/bin/env python3
"""The chip benchmark of the multi-tenant ACAM serving path: one cell per run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json`` (at the root of
the checkout): it names a configuration (its file is given under
``configs``) and a traffic mix (``bench/mixes/<traffic>.json``). The run:

  1. refuses anything but a TPU with the cell's chips (exit 1, no result);
  2. makes the deployment from ``--seed`` (`deploy.py`), builds the service
     through `HybridService.from_spec`, registers the fleet, and warms up
     every shape the window uses: the serve tick and the escalation head's
     power-of-two buckets (all of that is ``setup_s``);
  3. drives the service for ``--seconds`` with the mix (`drive.py`);
  4. reads the peak device memory, then compares the answers with the plain
     reference (`check.py`), which decides ``correct``;
  5. prints the metrics: with ``--trace 0`` the cell's end-to-end metrics,
     with ``--trace 1`` (the window traced by the JAX profiler) its
     per-layer metrics. Each metric is read by ``bench/metrics/<name>.py``,
     or by the reader of its base name (`reader_path`).

Diagnostics go to standard error; the compared numbers, each beside its
limit, are its last lines. The last line of standard output is the result:
one JSON object with ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` (with ``--trace 1`` also ``breakdown``) and, last, ``checks``.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
SRC = os.path.join(ROOT, "src")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")
#: full ticks served while warming up, before the escalation buckets
WARM_TICKS = 8

if HERE not in sys.path:
    sys.path.insert(0, HERE)

from trace_reduce import WINDOW  # noqa: E402  (bench/ on the path first)


class Refused(RuntimeError):
    """The run cannot measure here: nothing is printed on standard output."""


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def load_cell(name: str, root: str = ROOT) -> tuple[dict, dict, dict]:
    """(cell, configuration, mix) of the ``BENCHMARK.json`` workload."""
    from deploy import load_json
    from traffic import check_mix

    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    files = {c["name"]: c["file"] for c in bench["configs"]}
    with open(os.path.join(root, files[cell["config"]])) as f:
        cfg = json.load(f)
    return cell, cfg, check_mix(load_json("mixes", cell["traffic"]))


def metric_entries(name: str, trace: bool, root: str = ROOT) -> list:
    """The cell's metrics: its end-to-end ones, or with ``trace`` its
    per-layer ones."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    if not trace:
        return e2e
    mine = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if name in m.get("workloads", [name] if m["moves"] in mine
                             else [])]


def reader_path(metric: str) -> str:
    """The reader of a metric: ``bench/metrics/<metric>.py`` where there is
    one, else that of its base name, the part before the first ``.``
    (``step_ms.server`` is read by ``step_ms.py`` unless a mix brings a
    reader of its own)."""
    own = os.path.join(HERE, "metrics", f"{metric}.py")
    if os.path.exists(own):
        return own
    return os.path.join(HERE, "metrics", f"{metric.split('.')[0]}.py")


def reader(metric: str):
    """``read(run)`` of the metric's reader (`reader_path`)."""
    path = reader_path(metric)
    spec = importlib.util.spec_from_file_location(
        "metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def require_chip(chips: int):
    """The devices to measure on; `Refused` off a TPU, with fewer chips
    than the cell asks for, or with the Pallas interpreter on."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Refused(f"platform is {devs[0].platform!r}, not 'tpu': this "
                      "benchmark never falls back to the CPU")
    if len(devs) < chips:
        raise Refused(f"the cell needs {chips} chips, {len(devs)} found")
    from repro.kernels import tuning

    if tuning.interpret_mode():
        raise Refused("Pallas kernels would run in interpret mode")
    return devs


class CompileCounter:
    """Counts traces and backend compiles, through `jax.monitoring`."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration_secs, **kwargs):
        if event in self.EVENTS:
            self.n += 1


@dataclasses.dataclass
class Run:
    """Everything a metric reader may read."""

    cfg: dict
    window: object  # drive.Window
    setup_s: float
    trace: object = None  # trace_reduce.Trace, traced runs only
    device_kind: str = ""


def warm_up(svc, reqs: list, cfg: dict) -> None:
    """Compile and run every shape the window uses: full serve ticks (a
    partly filled tick pads to the same shape), then the escalation head at
    each power-of-two bucket up to ``slots``."""
    import jax.numpy as jnp
    import numpy as np

    from repro.serve.acam_service import _escalate_heads

    slots = cfg["slots"]
    for t in range(WARM_TICKS):
        for r in reqs[t * slots:(t + 1) * slots]:
            svc.submit(r)
        svc.drain()
    w, b = svc._head_tables()
    n = cfg["num_features"]
    size = 1
    while True:
        np.asarray(_escalate_heads(
            w, b, jnp.asarray(np.zeros((size, n), np.float32)),
            jnp.asarray(np.zeros((size,), np.int32)),
            jnp.asarray(np.ones((size,), np.int32))))
        if size >= slots:
            break
        size *= 2
    svc.reset_metrics()


def measure(cfg: dict, mix: dict, *, seed: int, seconds: float, trace: bool,
            devices: list, t_start: float, counter=None,
            trace_dir: str = TRACE_DIR) -> tuple[Run, object, object]:
    """Set up, warm up, run the window. -> (run, fleet, pool)."""
    from repro.serve.acam_service import ClassifyRequest

    import deploy
    import drive
    import traffic

    def phase(name):
        log(f"set-up: {name} at {time.perf_counter() - t_start:.3f} s")

    phase("devices found")
    fleet = deploy.make_fleet(cfg, seed)
    weights = traffic.zipf_weights(cfg["tenants"], mix["zipf_a"], seed)
    pool = deploy.make_pool(cfg, fleet, weights, mix["pool"], seed)
    reqs = [ClassifyRequest(deploy.tenant_id(t), f)
            for t, f in zip(pool.tenant.tolist(), pool.features)]
    phase("fleet and requests made")
    svc = deploy.make_service(cfg, fleet, trace=trace)
    phase("service built, fleet registered")
    warm_up(svc, reqs, cfg)
    phase("warmed up")
    rec = drive.Record({deploy.tenant_id(t): t
                        for t in range(cfg["tenants"])})
    drive.record_ticks(svc, rec)
    due = traffic.arrivals(mix["rate_per_s"], seconds, seed) \
        if mix["kind"] == "poisson" else None
    gc.collect()  # set-up's garbage is not the window's to collect
    full0 = gc.get_stats()[2]["collections"]
    compiles0 = counter.n if counter else 0
    if trace:
        import jax

        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    setup_s = time.perf_counter() - t_start
    span = drive.spans(trace)
    with span(WINDOW):
        if mix["kind"] == "closed":
            win = drive.closed_loop(svc, reqs, rec,
                                    depth=mix["depth_ticks"] * cfg["slots"],
                                    seconds=seconds, trace=trace)
        else:
            win = drive.open_loop(svc, reqs, rec, due=due, seconds=seconds,
                                  trace=trace)
    full = gc.get_stats()[2]["collections"] - full0
    if counter:
        log(f"compiles inside the window: {counter.n - compiles0}")
    log(f"full garbage collections inside the window: {full}")
    if trace:
        import jax

        jax.profiler.stop_trace()
    rec.add_answers(win.seconds, svc.drain())  # checked, not counted
    run = Run(cfg=cfg, window=win, setup_s=setup_s,
              device_kind=devices[0].device_kind)
    esc, answered = sum(rec.esc), len(rec.rid)
    log(f"window {win.seconds:.3f} s: sent {win.sent}, counted "
        f"{win.population}, answered {answered}, escalated "
        f"{esc / max(answered, 1):.4f}, ticks {win.ticks_in_window}")
    if win.late is not None and len(win.late):
        import numpy as np

        p50, p99 = np.percentile(win.late, [50, 99]) * 1e3
        log(f"generator lateness (send - due): p50 {p50:.4f} ms, "
            f"p99 {p99:.4f} ms")
        lat = win.latencies_s() * 1e3
        log("latency p50/p90/p95/p99/p99.9 (ms): " + ", ".join(
            f"{drive.quantile(lat, q):.4f}"
            for q in (0.5, 0.9, 0.95, 0.99, 0.999)))
        quarter = np.minimum((win.due / seconds * 4).astype(int), 3)
        log("latency p99 by quarter of the window (ms): " + ", ".join(
            f"{drive.quantile(lat[quarter == q], 0.99):.3f}"
            for q in range(4) if np.any(quarter == q)))
    log_stalls(win.steps)
    return run, fleet, pool


def log_stalls(steps) -> None:
    """The longest `step` calls of the loop, where a stall of the host
    shows."""
    import numpy as np

    longest = np.argsort(steps[:, 1])[::-1][:5]
    log("longest steps (start s, ms): " + ", ".join(
        f"{steps[i, 0]:.3f} {steps[i, 1] * 1e3:.2f}" for i in longest))
    log(f"steps over 20 ms: {int(np.sum(steps[:, 1] > 0.02))} of "
        f"{len(steps)}")


def peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def main(argv=None) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        log("refused: --seed must be >= 0 and --seconds > 0")
        return 2
    try:
        cell, cfg, mix = load_cell(args.workload)
        if not os.path.isdir(os.path.join(SRC, "repro")):
            raise Refused(f"no system under test: {SRC}/repro is missing")
        sys.path.insert(0, SRC)
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        import jax

        devices = require_chip(cell["chips"])[:cell["chips"]]
    except Refused as e:
        log(f"refused: {e}")
        return 1
    from repro.launch.compile_cache import use_compile_cache

    log(f"compile cache: {use_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    trace = bool(args.trace)
    result = execute(cfg, mix, metric_entries(args.workload, trace),
                     seed=args.seed, seconds=args.seconds, trace=trace,
                     devices=devices, t_start=t_start,
                     counter=CompileCounter())
    print(json.dumps(result), flush=True)
    return 0


def execute(cfg: dict, mix: dict, entries: list, *, seed: int,
            seconds: float, trace: bool, devices: list, t_start: float,
            counter=None, trace_dir: str = TRACE_DIR) -> dict:
    """Everything of a run after the look for a chip: measure, read the
    device, compare with the reference, read the metrics of ``entries``
    (`metric_entries`). -> the result line as a dict."""
    import jax

    import check

    run, fleet, pool = measure(cfg, mix, seed=seed, seconds=seconds,
                               trace=trace, devices=devices, t_start=t_start,
                               counter=counter, trace_dir=trace_dir)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(jax.devices()),
              "memory_peak_bytes": peak_bytes(devices)}
    log(f"peak device memory: {device['memory_peak_bytes']} B")
    out = {}
    if trace:
        import trace_reduce

        run.trace = trace_reduce.load(trace_dir)
        busy = [trace_reduce.busy_s(run.trace, i) for i in
                range(len(devices))]
        device["busy_s"] = sum(busy) / len(busy)
        device["window_s"] = run.trace.window_s
        out["breakdown"] = {"device_ops": trace_reduce.top_ops(run.trace),
                            "idle_gaps": trace_reduce.idle_gaps(run.trace)}
    values = check.compare(cfg, fleet, pool, run.window, seed)
    correct, checks = check.verdict(values, cfg["limits"])
    metrics = {}
    for m in entries:
        v = reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    log(f"compared {values['checked']} answers ({values['escalated']} "
        "escalated) with the reference")
    for k, c in checks.items():
        log(f"check {k}: {c['value']} (limit {c['limit']})")
    return {"correct": correct, "attempted": run.window.population,
            "failed": values["unanswered"], "metrics": metrics,
            "device": device, **out, "checks": checks}


if __name__ == "__main__":
    sys.exit(main())
