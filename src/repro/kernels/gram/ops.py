"""Public wrapper for the weighted-gram Hessian kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import demotions
from repro.kernels.gram.kernel import weighted_gram_pallas


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def weighted_gram(x: jax.Array, r: jax.Array | None = None) -> jax.Array:
    """(X·r)ᵀ(X·r) with fp32 accumulation; pads to kernel-aligned tiles.

    A 3-D ``x`` of shape (E, N, d) (stacked-expert capacity buffers) maps
    to E independent grams via vmap over the Pallas grid."""
    if x.ndim == 3:
        if r is None:
            return jax.vmap(weighted_gram)(x)
        return jax.vmap(weighted_gram)(x, r)
    n, d = x.shape
    if r is None:
        r = jnp.ones((n,), jnp.float32)
    d_blk = 256 if d % 256 == 0 else (128 if d % 128 == 0 else None)
    t_blk = 512
    while n % t_blk and t_blk > 1:
        t_blk //= 2
    if d_blk is None or t_blk < 8:
        # shape not tileable: fall back to the oracle (still fp32 gram)
        from repro.kernels.gram.ref import weighted_gram_ref
        demotions.record("weighted_gram", f"untileable (n={n}, d={d})")
        return weighted_gram_ref(x, r)
    return weighted_gram_pallas(x, r, d_blk=d_blk, t_blk=t_blk,
                                interpret=_interpret())
