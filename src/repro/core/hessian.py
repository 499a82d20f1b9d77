"""Step 3 input: weighted second-order statistics H = 2 · X R² Xᵀ.

``accumulate`` is the single entry point the calibration engine routes every
dense *and* stacked-expert update through: 2-D inputs ``(N, d)`` produce one
``(d, d)`` gram; 3-D inputs ``(E, C, d)`` (per-expert capacity buffers)
produce a batch of ``(E, d, d)`` independent grams.  ``use_kernel=True``
dispatches the tiled Pallas ``gram`` kernel (kernels/gram) instead of the
pure-jnp contraction — the pipeline turns this on automatically on TPU.

Streaming sharded accumulation
------------------------------
``n_shards=S > 1`` switches the accumulator to its *streaming* layout: the
token rows are split into S contiguous chunks and each chunk contributes its
own partial gram, so ``h`` carries a leading shard axis — ``(S, d, d)`` for
dense weights, ``(S, E, d, d)`` for expert stacks.  When that leading axis
is placed on the data axis of a mesh (``ParallelCtx.shard_leading``), every
device accumulates only its local partial and *no* per-batch cross-device
reduction happens; ``reduce_shards`` performs the single solve-time
reduction (one psum under GSPMD, or the explicit ring in
``core/distributed.make_sharded_hessian_fn``).  Rows that don't divide by S
are zero-padded — zero rows contribute nothing to a gram, so the padding is
exact.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def accumulate(h: jax.Array | None, x: jax.Array, r: jax.Array | None = None,
               *, use_kernel: bool = False, n_shards: int = 1) -> jax.Array:
    """h: (d, d) fp32 (or (E, d, d) for stacked experts) or None;
    x: (N, d) tokens-by-features or (E, C, d) expert capacity buffers;
    r: (N,) / (E, C) token importances (None = uniform).
    Returns h + 2·XᵀR²X (batched over the leading expert axis for 3-D x).
    With ``n_shards=S > 1`` the result carries a leading (S,) partial-sum
    axis instead of being fully reduced — see module docstring."""
    lead = x.shape[:-2] if x.ndim >= 3 else ()
    xf = x.reshape((-1,) + x.shape[-2:]).astype(jnp.float32)  # (B, N, d)
    if r is not None:
        xf = xf * r.reshape(xf.shape[0], xf.shape[1], 1).astype(jnp.float32)
    if n_shards > 1:
        b, n, d = xf.shape
        pad = (-n) % n_shards
        if pad:  # zero rows are gram-neutral (r already folded into xf)
            xf = jnp.concatenate(
                [xf, jnp.zeros((b, pad, d), xf.dtype)], axis=1)
        # (B, S, N/S, d) -> (S, B, N/S, d): shard axis leads so it can sit
        # on the data axis of a mesh; chunks are contiguous token ranges,
        # matching a P("dp", ...)-sharded batch
        xf = xf.reshape(b, n_shards, -1, d).transpose(1, 0, 2, 3)
        lead = (n_shards,) + lead
        xf = xf.reshape((-1,) + xf.shape[-2:])
    if use_kernel:
        from repro.kernels.gram import ops as gram_ops
        upd = 2.0 * gram_ops.weighted_gram(xf)
    else:
        # full f32 precision: a TPU's default f32 dot is one bf16 pass
        upd = 2.0 * jnp.einsum("bnd,bne->bde", xf, xf,
                               precision=jax.lax.Precision.HIGHEST)
    upd = upd.reshape(lead + upd.shape[-2:])
    if h is None:
        return upd
    return h + upd


def reduce_shards(h: jax.Array) -> jax.Array:
    """Collapse a streaming ``(S, ...)`` accumulator to the dense Hessian.

    This is the *one* solve-time reduction of the sharded path: when the
    leading axis is mesh-sharded, GSPMD lowers the sum to a single psum per
    weight (vs one per calibration batch for replicated accumulators)."""
    return jnp.sum(h, axis=0)


def hessian_diag_mean(h: jax.Array) -> jax.Array:
    return jnp.mean(jnp.diag(h))
