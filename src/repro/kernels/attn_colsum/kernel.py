"""Streaming attention-concentration kernel (AttnCon importance).

The paper's AttnCon scores are column sums of the softmax attention map:
R_j = sum_{heads, i} A[h, i, j].  Materializing (H, T, T) at T = 4096+ is
exactly what RSQ's calibration cannot afford, so this kernel computes the
sums in two flash-style passes that never form the map:

  pass 1 — per-query running (max m_i, denominator l_i), standard
           streaming-softmax over KV blocks;
  pass 2 — col[j] += sum_i exp(q_i·k_j - m_i) / l_i, accumulated over query
           blocks with the (m, l) from pass 1.

O(T^2) FLOPs (MXU qk^T tiles), O(T) memory.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _rowstats_kernel(q_ref, k_ref, m_ref, l_ref, *, blk_q, blk_k, scale,
                     causal):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32) * scale  # (blk_q, d)
    k = k_ref[0].astype(jnp.float32)  # (blk_k, d)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
    if causal:
        q_pos = pl.program_id(1) * blk_q + jax.lax.broadcasted_iota(
            jnp.int32, (blk_q, blk_k), 0)
        k_pos = pl.program_id(2) * blk_k + jax.lax.broadcasted_iota(
            jnp.int32, (blk_q, blk_k), 1)
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)
    m_old = m_ref[...]  # (1, blk_q)
    m_new = jnp.maximum(m_old, jnp.max(s, axis=-1)[None])
    alpha = jnp.exp(m_old - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(
        jnp.exp(s - m_new[0][:, None]), axis=-1)[None]
    m_ref[...] = m_new


def _colsum_kernel(q_ref, k_ref, m_ref, l_ref, o_ref, *, blk_q, blk_k,
                   scale, causal):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    q = q_ref[0].astype(jnp.float32) * scale
    k = k_ref[0].astype(jnp.float32)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
    if causal:
        q_pos = pl.program_id(2) * blk_q + jax.lax.broadcasted_iota(
            jnp.int32, (blk_q, blk_k), 0)
        k_pos = pl.program_id(1) * blk_k + jax.lax.broadcasted_iota(
            jnp.int32, (blk_q, blk_k), 1)
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)
    m = m_ref[...][0]  # (blk_q,)
    l = jnp.maximum(l_ref[...][0], 1e-30)
    p = jnp.exp(s - m[:, None]) / l[:, None]
    o_ref[...] += jnp.sum(p, axis=0)[None]


@functools.partial(jax.jit, static_argnames=("causal", "blk", "interpret"))
def attn_colsum_pallas(q: jax.Array, k: jax.Array, *, causal: bool = True,
                       blk: int = 256, interpret: bool = True) -> jax.Array:
    """q, k: (BH, T, d). Returns (BH, T) column sums of softmax(q kᵀ)."""
    bh, t, d = q.shape
    blk = min(blk, t)
    assert t % blk == 0, (t, blk)
    scale = d ** -0.5
    grid = (bh, t // blk, t // blk)
    qspec = pl.BlockSpec((1, blk, d), lambda b, i, j: (b, i, 0))
    kspec = pl.BlockSpec((1, blk, d), lambda b, i, j: (b, j, 0))
    statspec_q = pl.BlockSpec((1, blk), lambda b, i, j: (b, i))

    m, l = pl.pallas_call(
        functools.partial(_rowstats_kernel, blk_q=blk, blk_k=blk,
                          scale=scale, causal=causal),
        grid=grid,
        in_specs=[qspec, kspec],
        out_specs=[statspec_q, statspec_q],
        out_shape=[jax.ShapeDtypeStruct((bh, t), jnp.float32),
                   jax.ShapeDtypeStruct((bh, t), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k)

    # pass 2: grid (bh, kv blocks, q blocks) — innermost q accumulates
    col = pl.pallas_call(
        functools.partial(_colsum_kernel, blk_q=blk, blk_k=blk,
                          scale=scale, causal=causal),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, blk, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, blk, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, blk), lambda b, j, i: (b, i)),
            pl.BlockSpec((1, blk), lambda b, j, i: (b, i)),
        ],
        out_specs=pl.BlockSpec((1, blk), lambda b, j, i: (b, j)),
        out_shape=jax.ShapeDtypeStruct((bh, t), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, m, l)
    return col
