"""Pallas TPU kernels for the paper's compute hot-spots.

Each kernel package ships <name>.py (the pallas_call), ops.py (public
wrappers: interpret mode on CPU, compiled on TPU, block sizes resolved via
the `repro.kernels.tuning` autotuner cache) and ref.py (pure-jnp oracle).

`repro.match` dispatches the ACAM hot path here by default; the serve
kernels take the (K, Cp, N) bank stack of `repro.kernels.layout`.
Ref-vs-kernel timings are tracked in BENCH_kernels.json
(benchmarks/kernel_bench.py).
"""
