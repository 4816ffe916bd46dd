#!/usr/bin/env python3
"""The readings behind a configuration's limits, on the chip.

    python3 bench/readings.py --workload t64-offline --seconds 3 \\
        --seeds 1,2,3,4,5,6,7,8,9,10,11,12

For each seed, in one process: the cell's deployment is made anew from the
seed, served for a short window at the cell's own load, and its answers are
compared with the reference (the program's readings); then the control
(`reference.py`, one precision step down) is put in the program's place on
the same sampled requests (the control's readings). One JSON line per seed.
A limit lies above every program reading and below every control reading
(see PERF.md). The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    import run

    try:
        cell, cfg, mix = run.load_cell(args.workload)
        sys.path.insert(0, run.SRC)
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        devices = run.require_chip(cell["chips"])[:cell["chips"]]
    except run.Refused as e:
        run.log(f"refused: {e}")
        return 1
    import jax

    import check
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    for seed in (int(s) for s in args.seeds.split(",")):
        r, fleet, pool = run.measure(cfg, mix, seed=seed,
                                     seconds=args.seconds, trace=False,
                                     devices=devices,
                                     t_start=time.perf_counter())
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "program": check.compare(cfg, fleet, pool, r.window, seed),
            "control": check.control(cfg, fleet, pool, r.window, seed)}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
