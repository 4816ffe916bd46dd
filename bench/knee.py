#!/usr/bin/env python3
"""Find the knee of a configuration under open-loop Poisson load, on the chip.

    python3 bench/knee.py --config cifar10-k2-t64 --seed 7 --seconds 5 \\
        --rates 2000,4000,6000

One process builds the deployment once, warms it up, then offers each rate
for ``--seconds`` (`drive.open_loop`) and prints one JSON line per rate:
p50 and p99 of due-to-answer latency, the same p99 over the last quarter of
the window (a backlog that grows shows there first), the answered rate and
the generator's lateness. The knee is the highest rate whose p99 stays at
or under the limit (15 ms, MLPerf Inference Server's bound for image
classification) with the last quarter's p99 no worse. A Server mix offers
0.8 x the knee, fixed in its mix file; this script is not part of a run.
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
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--rates", required=True,
                    help="comma-separated offered rates, requests/s")
    ap.add_argument("--zipf-a", type=float, default=1.0)
    ap.add_argument("--pool", type=int, default=16384)
    args = ap.parse_args(argv)
    import run

    try:
        sys.path.insert(0, run.SRC)
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        run.require_chip(1)
    except run.Refused as e:
        run.log(f"refused: {e}")
        return 1
    import numpy as np

    from repro.launch.compile_cache import use_compile_cache
    from repro.serve.acam_service import ClassifyRequest

    import deploy
    import drive
    import traffic

    use_compile_cache()
    cfg = deploy.load_json("configs", args.config)
    fleet = deploy.make_fleet(cfg, args.seed)
    weights = traffic.zipf_weights(cfg["tenants"], args.zipf_a, args.seed)
    pool = deploy.make_pool(cfg, fleet, weights, args.pool, args.seed)
    reqs = [ClassifyRequest(deploy.tenant_id(t), f)
            for t, f in zip(pool.tenant.tolist(), pool.features)]
    svc = deploy.make_service(cfg, fleet, trace=False)
    run.warm_up(svc, reqs, cfg)
    rec = drive.Record({deploy.tenant_id(t): t
                        for t in range(cfg["tenants"])})
    drive.record_ticks(svc, rec)
    run.log(f"set-up {time.perf_counter() - t_start:.1f} s")
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        due = traffic.arrivals(rate, args.seconds, args.seed + i)
        rec.clear()
        win = drive.open_loop(svc, reqs, rec, due=due,
                              seconds=args.seconds, trace=False)
        lat = win.latencies_s() * 1e3
        last = lat[due >= 0.75 * args.seconds]
        fills = np.frombuffer(rec.tick_fill, np.int64)
        print(json.dumps({
            "rate_per_s": rate, "requests": int(win.population),
            "p50_ms": drive.quantile(lat, 0.5),
            "p99_ms": drive.quantile(lat, 0.99),
            "p99_last_quarter_ms": drive.quantile(last, 0.99),
            "answered_per_s": win.population / max(max(rec.t),
                                                   args.seconds),
            "mean_fill": float(np.mean(fills)) if len(fills) else 0.0,
            "late_p99_ms": float(np.percentile(win.late, 99) * 1e3)}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
