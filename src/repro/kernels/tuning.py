"""Block-size autotuner for the Pallas ACAM kernels.

The kernels historically ran with a single hardcoded ``DEFAULT_BLOCK``. This
module replaces that with a two-layer scheme:

  1. **Lookup** (`get_block`) — a pure, trace-time-safe read: consult the
     persistent JSON cache for a tuned block matching
     ``kernel|backend|shape|dtype``; fall back to the kernel's per-backend
     default. Safe to call while tracing a jitted caller (no timing, no IO
     beyond a once-per-process cache load).
  2. **Tuning** (`autotune`) — an explicit, eager grid-search over
     MXU/VREG-aligned candidate blocks, timing real calls and writing the
     winner back to the cache. Run it offline (``python -m
     repro.kernels.tuning``) or via ``benchmarks/kernel_bench.py --tune``.

Cache file
----------
``$REPRO_TUNING_CACHE`` if set, else ``~/.cache/repro/pallas_blocks.json``:

    {"version": 2,
     "entries": {"acam_match|cpu+interp|b256_m10_n784|float32":
                 {"block": [128, 128, 512], "us": 83.1}}}

Keys are exact-shape (no bucketing): the ACAM deployment shapes are few and
static (the bank is programmed once), so exact keys stay small and never
mis-tune. The platform token grows a ``+interp`` suffix when the kernels
run under the Pallas interpreter (CPU): interpreted timings favour very
different blocks than compiled ones, and v1's bare-platform keys let a
cache tuned in interpret mode poison a compiled run on the same platform
string. v2 keys separate the two populations; v1 caches are discarded on
load (version gate), so stale keys can never be consulted. Writes are
atomic (tmp + rename) so concurrent benchmark runs cannot corrupt the
cache. Tune offline with ``python -m repro.kernels.tuning`` or
``python benchmarks/kernel_bench.py --tune`` (grid-searches every
benchmarked shape and persists the winners here).

Candidate grids
---------------
All candidates keep the TPU tiling contract: second-to-last block dims are
multiples of 8 (f32 sublanes), last dims multiples of 128 (lanes), and the
working set per grid step is capped below VMEM (~16 MB/core, we budget 8).

  acam_match      (MXU matmul):   bm,bn in {128, 256}, bk in {256, 512, 1024}
  acam_similarity (VPU 3D fuse):  bm in {8, 16, 32, 64}, bn in {128, 256},
                                  bk in {128, 256, 512}
"""
from __future__ import annotations

import functools
import json
import os
import time
from typing import Callable, Iterable, Sequence

import jax

Block = tuple[int, int, int]

_VMEM_BUDGET_BYTES = 8 * 1024 * 1024
CACHE_VERSION = 2


def cache_path() -> str:
    env = os.environ.get("REPRO_TUNING_CACHE")
    if env:
        return env
    return os.path.join(os.path.expanduser("~"), ".cache", "repro",
                        "pallas_blocks.json")


def backend() -> str:
    return jax.devices()[0].platform  # "cpu" | "tpu" | "gpu"


def interpret_mode() -> bool:
    """Shared predicate: kernels run via the pallas interpreter off-TPU CPU."""
    return backend() == "cpu"


def resolve_block(kernel: str, operand: jax.Array, m: int, block):
    """ops.py helper: explicit ``block`` wins, else cached/tuned lookup."""
    if block is not None:
        return tuple(block)
    b, n = operand.shape
    return get_block(kernel, (b, m, n), operand.dtype)


def clamp_block(block, b: int, n: int) -> tuple[int, int, int]:
    """Cap ``(bm, bn, bk)`` to the data: bm to the sublane-padded batch, bk
    to the lane-padded feature width.

    Tiling past the operand only adds padding work — padded batch rows are
    row-independent and padded feature columns contribute exact zeros (or
    exactly-corrected constants, recomputed by each wrapper from its own
    padded width) — so the cap is bit-safe and a pure win in the serving
    tick's small regime (B = scheduler slots, N = 64-ish front-end maps),
    where the default (128, ., 512) tile would 4-8x every block op.
    """
    bm, bn, bk = block
    return (min(bm, -(-b // 8) * 8), bn, min(bk, -(-n // 128) * 128))


def shape_key(b: int, m: int, n: int) -> str:
    return f"b{b}_m{m}_n{n}"


def entry_key(kernel: str, shape: tuple[int, int, int], dtype,
              device: str | None = None) -> str:
    b, m, n = shape
    dt = jax.numpy.dtype(dtype).name
    if device is None:
        device = backend() + ("+interp" if interpret_mode() else "")
    return f"{kernel}|{device}|{shape_key(b, m, n)}|{dt}"


# ---------------------------------------------------------------------------
# Cache IO
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=1)
def _load_cache() -> dict:
    path = cache_path()
    try:
        with open(path) as f:
            data = json.load(f)
        if data.get("version") != CACHE_VERSION:
            return {}
        return dict(data.get("entries", {}))
    except (OSError, ValueError):
        return {}


def _save_entry(key: str, block: Block, us: float) -> None:
    path = cache_path()
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    entries = dict(_load_cache())
    entries[key] = {"block": list(block), "us": round(us, 2)}
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump({"version": CACHE_VERSION, "entries": entries}, f, indent=1)
    os.replace(tmp, path)
    _load_cache.cache_clear()


def clear_cache_for_tests() -> None:
    """Drop the in-process cache view (tests point REPRO_TUNING_CACHE at tmp)."""
    _load_cache.cache_clear()


# ---------------------------------------------------------------------------
# Candidates
# ---------------------------------------------------------------------------

def _fits(bm: int, bn: int, bk: int, *, bufs: int) -> bool:
    # inputs (bm,bk)+(bn,bk), accumulators/outputs bufs x (bm,bn), f32.
    words = bm * bk + bn * bk + bufs * bm * bn
    return words * 4 <= _VMEM_BUDGET_BYTES


def candidates(kernel: str) -> list[Block]:
    """MXU/VREG-aligned candidate blocks for a kernel family."""
    if kernel == "acam_match":
        grid = [(bm, bn, bk)
                for bm in (128, 256) for bn in (128, 256)
                for bk in (256, 512, 1024) if _fits(bm, bn, bk, bufs=1)]
    elif kernel == "acam_similarity":
        # the kernel broadcasts to a (bm, bn, bk) tile: count that too.
        grid = [(bm, bn, bk)
                for bm in (8, 16, 32, 64) for bn in (128, 256)
                for bk in (128, 256, 512)
                if (bm * bn * bk + 3 * bm * bn) * 4 <= _VMEM_BUDGET_BYTES]
    else:
        raise ValueError(f"no candidate grid for kernel {kernel!r}")
    assert all(bm % 8 == 0 or bm < 8 for bm, _, _ in grid)
    assert all(bn % 128 == 0 and bk % 128 == 0 for _, bn, bk in grid)
    return grid


_DEFAULTS: dict[tuple[str, str], Block] = {
    ("acam_match", "tpu"): (128, 128, 512),
    ("acam_match", "cpu"): (128, 128, 512),
    ("acam_similarity", "tpu"): (8, 128, 128),
    # interpret mode pays per-grid-step Python/HLO overhead: favour fewer,
    # fatter steps on CPU.
    ("acam_similarity", "cpu"): (64, 128, 256),
}


def default_block(kernel: str, device: str | None = None) -> Block:
    device = device or backend()
    return _DEFAULTS.get((kernel, device), _DEFAULTS[(kernel, "tpu")])


def get_block(kernel: str, shape: tuple[int, int, int], dtype,
              device: str | None = None) -> Block:
    """Tuned block for (kernel, shape, dtype) or the per-backend default.

    Pure lookup — never times anything, so it is safe at jit trace time.
    """
    hit = _load_cache().get(entry_key(kernel, shape, dtype, device))
    if hit is not None:
        return tuple(hit["block"])  # type: ignore[return-value]
    return default_block(kernel, device)


# ---------------------------------------------------------------------------
# Tuning
# ---------------------------------------------------------------------------

def _time_call(fn: Callable[[], jax.Array], iters: int) -> float:
    out = fn()
    jax.tree_util.tree_map(lambda x: x.block_until_ready(), out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn()
    jax.tree_util.tree_map(lambda x: x.block_until_ready(), out)
    return (time.perf_counter() - t0) / iters * 1e6


def autotune(kernel: str, shape: tuple[int, int, int], dtype,
             run: Callable[[Block], jax.Array], *,
             cands: Sequence[Block] | None = None, iters: int = 5,
             save: bool = True) -> Block:
    """Grid-search `run(block)` over the candidate blocks; cache the winner.

    `run` must execute the kernel end to end for a given block (the caller
    binds the concrete operands). Candidates that fail to lower (e.g. VMEM
    overflow on a real TPU) are skipped; when none compiles, the kernel
    cannot run at this shape and the first failure is raised.
    """
    best: tuple[float, Block] | None = None
    failures: list[tuple[Block, Exception]] = []
    for block in (cands if cands is not None else candidates(kernel)):
        try:
            us = _time_call(lambda: run(block), iters)
        except Exception as e:  # noqa: BLE001 — a block that fails loses
            failures.append((block, e))
            continue
        if best is None or us < best[0]:
            best = (us, block)
    if best is None:
        block, err = failures[0]
        raise RuntimeError(
            f"autotune {kernel} at {shape}: none of {len(failures)} candidate "
            f"blocks ran (first, {block}: {type(err).__name__}: {err})"
        ) from err
    if save:
        _save_entry(entry_key(kernel, shape, dtype), best[1], best[0])
    return best[1]


def autotune_acam(shapes: Iterable[tuple[int, int, int]] = ((1, 16, 784),
                                                            (256, 16, 784)),
                  *, iters: int = 5) -> dict[str, Block]:
    """Tune both ACAM kernels over deployment shapes; returns {key: block}."""
    import jax.numpy as jnp

    from repro.kernels.acam_match.acam_match import acam_match
    from repro.kernels.acam_similarity.acam_similarity import acam_similarity

    interp = backend() == "cpu"
    out: dict[str, Block] = {}
    key = jax.random.PRNGKey(0)
    for b, m, n in shapes:
        f = jax.random.normal(key, (b, n), jnp.float32)
        thr = jnp.zeros((n,), jnp.float32)
        t = (jax.random.uniform(key, (m, n)) > 0.5).astype(jnp.float32)
        out[entry_key("acam_match", (b, m, n), jnp.float32)] = autotune(
            "acam_match", (b, m, n), jnp.float32,
            lambda blk: acam_match(f, thr, t, block=blk, interpret=interp),
            iters=iters)
        lo = jnp.zeros((m, n), jnp.float32)
        hi = jnp.ones((m, n), jnp.float32)
        out[entry_key("acam_similarity", (b, m, n), jnp.float32)] = autotune(
            "acam_similarity", (b, m, n), jnp.float32,
            lambda blk: acam_similarity(f, lo, hi, block=blk,
                                        interpret=interp),
            iters=iters)
    return out


if __name__ == "__main__":
    for k, blk in autotune_acam().items():
        print(f"{k} -> {blk}")
