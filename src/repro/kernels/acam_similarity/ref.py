"""Pure-jnp oracle for the acam_similarity kernel (paper Eq. 9-11)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.acam_similarity.acam_similarity import eq11


def acam_similarity_ref(queries: jax.Array, lower: jax.Array,
                        upper: jax.Array, *, alpha: float = 1.0) -> jax.Array:
    q = queries[:, None, :].astype(jnp.float32)
    lo = lower[None, :, :].astype(jnp.float32)
    hi = upper[None, :, :].astype(jnp.float32)
    above = jnp.maximum(q - hi, 0.0)
    below = jnp.maximum(lo - q, 0.0)
    d = jnp.sum(above**2 + below**2, axis=-1)
    hits = jnp.sum(((q >= lo) & (q <= hi)).astype(jnp.float32), axis=-1)
    return eq11(hits, d, q.shape[-1], alpha)
