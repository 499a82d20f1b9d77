"""Packed weight-only quantized matmul: y = x @ dequant(W_packed).

TPU analogue of Marlin-style CUDA WoQ GEMMs: int codes are packed
``values_per_word`` per uint32 along d_in; the kernel unpacks a
(k_blk, n_blk) weight tile in VMEM with shift/mask VPU ops, applies the
per-group (scale, zero), and feeds the MXU in the compute dtype.  Packing
cuts HBM weight traffic by 16/bits vs bf16 — decode-shape GEMMs are
memory-bound, so that factor is the speedup bound.

Two kernels share the tile dequant:

  * :func:`quant_matmul_pallas`     — y = x @ dequant(W), the forward GEMM
    (contraction over the packed d_in axis).
  * :func:`quant_matmul_t_pallas`   — y = x @ dequant(W)ᵀ, the *latent
    layout* used by MLA's absorbed decode: W stays packed along its first
    axis (kvr), the contraction runs over the columns (per-head dn / dv),
    and the packed axis becomes the output.  Same HBM story — the weight
    is read packed either way — so absorbed decode stops being the one
    step that re-materialized an fp weight per token.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _dequant_tile(wq_ref, scale_ref, zero_ref, *, bits: int, vpw: int,
                  rows: int):
    """Unpack + dequantize one (rows, cols) weight tile in VMEM.

    ``wq_ref``: (rows // vpw, cols) uint32; ``scale_ref``/``zero_ref``:
    (rows // gs, cols).  Shift/mask unpack on the VPU, then the per-group
    affine — shared by the forward and the transposed (latent) kernel."""
    wq = wq_ref[...]
    shifts = (jnp.arange(vpw, dtype=jnp.uint32) * bits)[None, :, None]
    mask = jnp.uint32(2 ** bits - 1)
    # codes < 2**bits: the int32 hop is exact (Mosaic has no u32 -> f32)
    codes = ((wq[:, None, :] >> shifts) & mask).astype(jnp.int32)
    codes = codes.astype(jnp.float32)
    codes = codes.reshape(rows, -1)
    scale = scale_ref[...].astype(jnp.float32)
    zero = zero_ref[...].astype(jnp.float32)
    reps = rows // scale.shape[0]
    scale = jnp.repeat(scale, reps, axis=0)
    zero = jnp.repeat(zero, reps, axis=0)
    return scale * (codes - zero)


def _qmm_kernel(x_ref, wq_ref, scale_ref, zero_ref, o_ref, *,
                bits: int, vpw: int, group_size: int, k_blk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...].astype(jnp.float32)  # (m_blk, k_blk)
    w = _dequant_tile(wq_ref, scale_ref, zero_ref, bits=bits, vpw=vpw,
                      rows=k_blk)  # (k_blk, n_blk)
    o_ref[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=(
    "bits", "group_size", "m_blk", "n_blk", "k_blk", "interpret"))
def quant_matmul_pallas(x: jax.Array, w_packed: jax.Array, scale: jax.Array,
                        zero: jax.Array, *, bits: int, group_size: int,
                        m_blk: int = 128, n_blk: int = 256, k_blk: int = 512,
                        interpret: bool = True) -> jax.Array:
    """x: (m, k); w_packed: (k // vpw, n) uint32; scale/zero: (k // gs, n).

    Returns (m, n) in x.dtype (fp32 accumulation)."""
    m, k = x.shape
    vpw = 32 // bits
    n = w_packed.shape[1]
    m_blk = min(m_blk, m)
    n_blk = min(n_blk, n)
    k_blk = min(k_blk, k)
    assert m % m_blk == 0 and n % n_blk == 0 and k % k_blk == 0
    assert k_blk % vpw == 0 and k_blk % group_size == 0
    kernel = functools.partial(_qmm_kernel, bits=bits, vpw=vpw,
                               group_size=group_size, k_blk=k_blk)
    grid = (m // m_blk, n // n_blk, k // k_blk)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((m_blk, k_blk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((k_blk // vpw, n_blk), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((k_blk // group_size, n_blk),
                         lambda i, j, kk: (kk, j)),
            pl.BlockSpec((k_blk // group_size, n_blk),
                         lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((m_blk, n_blk), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, w_packed, scale, zero)
    return out.astype(x.dtype)


def _qmm_t_kernel(x_ref, wq_ref, scale_ref, zero_ref, o_ref, *,
                  bits: int, vpw: int, k_blk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...].astype(jnp.float32)  # (m_blk, d_blk)
    w = _dequant_tile(wq_ref, scale_ref, zero_ref, bits=bits, vpw=vpw,
                      rows=k_blk)  # (k_blk, d_blk)
    # contract the (unpacked) columns: (m, d) x (k, d) -> (m, k)
    o_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=(
    "bits", "group_size", "m_blk", "k_blk", "d_blk", "interpret"))
def quant_matmul_t_pallas(x: jax.Array, w_packed: jax.Array,
                          scale: jax.Array, zero: jax.Array, *, bits: int,
                          group_size: int, m_blk: int = 128,
                          k_blk: int = 256, d_blk: int = 512,
                          interpret: bool = True) -> jax.Array:
    """Transposed (latent-layout) packed GEMM: y = x @ dequant(W)ᵀ.

    x: (m, d); w_packed: (k // vpw, d) uint32 packed along its *first*
    axis (the quantized d_in, e.g. MLA's kv_lora_rank); scale/zero:
    (k // gs, d).  Returns (m, k) in x.dtype (fp32 accumulation) — the
    packed axis is the *output* here, the reduction runs over the weight's
    columns, and the codes are never unpacked outside a VMEM tile."""
    m, d = x.shape
    vpw = 32 // bits
    k = w_packed.shape[0] * vpw
    m_blk = min(m_blk, m)
    k_blk = min(k_blk, k)
    d_blk = min(d_blk, d)
    assert m % m_blk == 0 and k % k_blk == 0 and d % d_blk == 0
    assert k_blk % vpw == 0 and k_blk % group_size == 0
    kernel = functools.partial(_qmm_t_kernel, bits=bits, vpw=vpw,
                               k_blk=k_blk)
    grid = (m // m_blk, k // k_blk, d // d_blk)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((m_blk, d_blk), lambda i, j, dd: (i, dd)),
            pl.BlockSpec((k_blk // vpw, d_blk), lambda i, j, dd: (j, dd)),
            pl.BlockSpec((k_blk // group_size, d_blk),
                         lambda i, j, dd: (j, dd)),
            pl.BlockSpec((k_blk // group_size, d_blk),
                         lambda i, j, dd: (j, dd)),
        ],
        out_specs=pl.BlockSpec((m_blk, k_blk), lambda i, j, dd: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, k), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, w_packed, scale, zero)
    return out.astype(x.dtype)
