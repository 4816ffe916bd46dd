#!/usr/bin/env python3
"""Two sets of runs of one cell, and the spread of each metric, on the chip.

    python3 bench/sets.py --workload t64-offline --seeds 11,12,13,14,15,16 \\
        --sets 2 --seconds 10 --out chiprun_out/sets

Each run is ``bench/run.py`` in a process of its own, one after the other
(the chip belongs to one process at a time; this script never touches JAX).
The sets use the same seeds. Each run's standard output and error go to
``<out>/<workload>.<set>.<seed>.{out,err}``; one JSON line per run is printed
with its result, then one line per metric with each set's median and spread:
the distance between the first and third quartile
(``statistics.quantiles(values, n=4)``) as a share of the median. A bound is
set at about five times the widest spread (see PERF.md).
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
#: a run that compiles may take this long; one that runs on is ended
RUN_LIMIT_S = 1200


def spread(values: list) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    os.makedirs(args.out, exist_ok=True)
    seeds = [int(s) for s in args.seeds.split(",")]
    values: dict = {}
    for k in range(args.sets):
        for seed in seeds:
            stem = os.path.join(args.out, f"{args.workload}.{k}.{seed}")
            with open(stem + ".out", "w") as out, \
                    open(stem + ".err", "w") as err:
                try:
                    rc = subprocess.run(
                        [sys.executable, os.path.join(HERE, "run.py"),
                         "--workload", args.workload, "--seed", str(seed),
                         "--seconds", str(args.seconds), "--trace",
                         str(args.trace)], stdout=out, stderr=err,
                        timeout=RUN_LIMIT_S).returncode
                except subprocess.TimeoutExpired:
                    rc = 124
            with open(stem + ".out") as f:
                lines = f.read().strip().splitlines()
            res = json.loads(lines[-1]) if rc == 0 and lines else None
            print(json.dumps({"set": k, "seed": seed, "rc": rc,
                              "result": res}), flush=True)
            for name, m in (res or {}).get("metrics", {}).items():
                values.setdefault(name, [[] for _ in range(args.sets)])
                values[name][k].append(m["value"])
    for name, sets in values.items():
        print(json.dumps({
            "metric": name,
            "median": [statistics.median(v) for v in sets if v],
            "spread": [spread(v) for v in sets if len(v) >= 2]}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
