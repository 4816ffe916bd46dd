"""Benchmark harness — one entry per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows per the harness contract, plus
the full result dictionaries. ``REPRO_BENCH_FAST=1`` shrinks the training
budget for CI-speed runs.
"""
from __future__ import annotations

import json
import time


def main() -> None:
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    t_all = time.time()
    rows: list[tuple[str, float, str]] = []
    details: dict = {}

    def timed(name, fn):
        t0 = time.time()
        out = fn()
        us = (time.time() - t0) * 1e6
        details[name] = out
        return name, us, out

    from benchmarks import (energy_model, fig1_thresholds, fig6_7_confusion,
                            kernel_bench, table1_compression, table2_templates)
    from benchmarks import common

    # model training is shared; charge it to its own row
    name, us, _ = timed("train_models", common.models)
    rows.append((name, us, f"n_per_class={common.N_PER_CLASS}"))

    name, us, out = timed("table1_compression", table1_compression.run)
    opt = next(r for r in out if r["model"] == "student_optimised")
    rows.append((name, us, f"opt_student_acc={opt['accuracy']:.4f}"))

    name, us, out = timed("table2_templates", table2_templates.run)
    accs = [r["accuracy"] for r in out if "accuracy" in r]
    rows.append((name, us, "k1/k2/k3=" + "/".join(f"{a:.4f}" for a in accs)))

    name, us, out = timed("fig1_thresholds", fig1_thresholds.run)
    rows.append((name, us,
                 f"mean={out['accuracy_mean']:.4f},median={out['accuracy_median']:.4f}"))

    name, us, out = timed("fig6_7_confusion", fig6_7_confusion.run)
    rows.append((name, us, f"acc={out['accuracy']:.4f}"))

    name, us, out = timed("energy_model", energy_model.run)
    rows.append((name, us, f"total={out['paper_total_nj']}nJ,"
                 f"reduction={out['paper_reduction_x']}x"))

    for r in kernel_bench.run():
        rows.append((r["name"], r["us_per_call"], r["derived"]))

    print("\nname,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")

    with open("bench_details.json", "w") as f:
        json.dump(details, f, indent=1, default=str)
    print(f"\ntotal {time.time()-t_all:.1f}s; details in bench_details.json")


if __name__ == "__main__":
    main()
