"""Compile-only guards: the serving kernels lower for a TPU v5e.

Interpret mode (every other kernel test) cannot see what Mosaic refuses:
unsupported primitives, unaligned slices, scoped-VMEM overflows. These
tests compile the main-path kernels with ``interpret=False`` for one chip
of a *described* ``v5e:2x2`` topology, at the paper's width (N = 784
features) and a scheduler tick of B = 64 slots, and check that the compiled
program holds the Pallas kernel (``tpu_custom_call``). Nothing runs: a pass
says the kernel compiles, not that it is correct or fast.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every xdist worker
imports this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import layout, tuning
from repro.kernels.acam_match.acam_match import acam_match_serve
from repro.kernels.acam_similarity.acam_similarity import acam_similarity_serve
from repro.match.backends import MAX_FUSED_ROWS

B, N = 64, 784  # scheduler slots; the student CNN's feature width
TENANTS = 64
#: (bank regime, padded classes, templates per class): 64 tenants x 16
#: classes x k=2 is exactly MAX_FUSED_ROWS rows (resident, one chunk);
#: 64 tenants x 48 classes x k=2 (40 classes bucketed to 48) is past it
BANKS = {"resident": (1024, 2), "chunked": (3072, 2)}
#: one shard of `bench/configs/cifar10-k2-t512-4shard.json`'s class-sharded
#: super-bank: (padded classes, templates per class) per chip
SHARD_BANK = (2048, 2)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without the chip: keep the cache off around it."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _block(kernel: str, rows: int, *, clamp: bool):
    blk = tuning.get_block(kernel, (B, rows, N), jnp.float32, device="tpu")
    return tuning.clamp_block(blk, B, N) if clamp else blk


def _compiled_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


@pytest.mark.parametrize("regime", sorted(BANKS))
@pytest.mark.parametrize("method", ["feature_count", "similarity"])
def test_serve_kernel_compiles(one_chip, no_persistent_cache, method, regime):
    cp, k = BANKS[regime]
    chunk = layout.class_chunk(cp, k, MAX_FUSED_ROWS)
    assert (cp // chunk == 1) == (regime == "resident")

    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    row = s((B,), jnp.int32)
    if method == "feature_count":
        blk = _block("acam_match", cp * k, clamp=True)

        def fn(f, thr, slot, t, v, lo, hi, tau):
            return acam_match_serve(f, thr, slot, t, v, lo, hi, tau, cp,
                                    chunk=chunk, block=blk, interpret=False)

        banks = (s((k, cp, N)),)
    else:
        blk = _block("acam_similarity", cp * k, clamp=True)

        def fn(f, thr, slot, lo_t, hi_t, v, lo, hi, tau):
            return acam_similarity_serve(f, thr, slot, lo_t, hi_t, v, lo, hi,
                                         tau, cp, chunk=chunk, block=blk,
                                         interpret=False)

        banks = (s((k, cp, N)), s((k, cp, N)))
    text = _compiled_text(fn, s((B, N)), s((TENANTS, N)), row, *banks,
                          s((k, cp)), row, row, s((B,)))
    assert "tpu_custom_call" in text


def test_margins_chunked_compiles(one_chip, no_persistent_cache):
    """The feature_count single-tenant face (`ops.classify_fused_margins`:
    one thresholds row, slot 0, tau -inf) at one bank shard of the
    512-tenant four-chip fleet: 2048 classes, K = 2, two chunks of 1024."""
    cp, k = SHARD_BANK
    chunk = layout.class_chunk(cp, k, MAX_FUSED_ROWS)
    assert cp // chunk == 2
    blk = _block("acam_match", cp * k, clamp=True)

    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def fn(f, thr, t, v, lo, hi):
        slot = jnp.zeros((B,), jnp.int32)
        tau = jnp.full((B,), -jnp.inf, jnp.float32)
        return acam_match_serve(f, thr[None, :], slot, t, v, lo, hi, tau, cp,
                                chunk=chunk, block=blk, interpret=False)[:3]

    row = s((B,), jnp.int32)
    text = _compiled_text(fn, s((B, N)), s((N,)), s((k, cp, N)), s((k, cp)),
                          row, row)
    assert "tpu_custom_call" in text
