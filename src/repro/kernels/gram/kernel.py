"""Weighted gram / Hessian accumulation kernel: H = (X·r)ᵀ (X·r).

TPU adaptation of the cuBLAS syrk call in GPU GPTQ: the (d x d) output is
tiled over a 2-D grid; the token dim streams through VMEM in chunks along
the innermost grid axis with the r scaling fused into the load, and fp32
accumulation lives in the output tile across the reduction steps
(dimension_semantics marks the token axis "arbitrary" = sequential).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gram_kernel(x_i_ref, x_j_ref, r_ref, o_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    r = r_ref[...].astype(jnp.float32)  # (t_blk, 1)
    xi = x_i_ref[...].astype(jnp.float32) * r  # (t_blk, d_blk_i)
    xj = x_j_ref[...].astype(jnp.float32) * r
    o_ref[...] += jnp.dot(xi.T, xj, preferred_element_type=jnp.float32,
                          precision=jax.lax.Precision.HIGHEST)


@functools.partial(jax.jit,
                   static_argnames=("d_blk", "t_blk", "interpret"))
def weighted_gram_pallas(x: jax.Array, r: jax.Array, *, d_blk: int = 256,
                         t_blk: int = 512, interpret: bool = True):
    """x: (n, d); r: (n,). Returns (d, d) fp32 = (X·r)ᵀ(X·r)."""
    n, d = x.shape
    d_blk = min(d_blk, d)
    t_blk = min(t_blk, n)
    assert d % d_blk == 0 and n % t_blk == 0, (n, d, t_blk, d_blk)
    r2 = r.reshape(n, 1).astype(jnp.float32)
    grid = (d // d_blk, d // d_blk, n // t_blk)
    return pl.pallas_call(
        _gram_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((t_blk, d_blk), lambda i, j, k: (k, i)),
            pl.BlockSpec((t_blk, d_blk), lambda i, j, k: (k, j)),
            pl.BlockSpec((t_blk, 1), lambda i, j, k: (k, 0)),
        ],
        out_specs=pl.BlockSpec((d_blk, d_blk), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((d, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, x, r2)
