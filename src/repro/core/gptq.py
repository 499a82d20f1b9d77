"""GPTQ / OBC solver, TPU-adapted.

The GPU reference runs a per-column loop with rank-1 updates.  Here the
mathematically identical recursion is restructured for the MXU: a
``lax.scan`` over 128-row blocks; inside a block a ``fori_loop`` performs the
(cheap, VPU-bound) per-row quantize+compensate; across blocks the deferred
compensation is one dense (B, d_in) x (B, d_out) matmul.  Everything is
jittable with static shapes and vmaps over batched weights.

Math (paper Eq. 2): quantize row q, then
    delta = -(w_q - quant(w_q)) / [H^-1]_qq * [H^-1]_{q,:}
implemented via the upper-Cholesky factor U of H^-1 (H^-1 = U^T U), exactly
as in the reference implementation.

RSQ enters only through the Hessian: H = 2 X R^2 X^T (see hessian.py); the
solver is oblivious to token scaling — that is what makes the paper's
technique integrate "seamlessly" into GPTQ.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.quantizer import QuantSpec, dequantize, find_params, quantize_rtn

# The solver's matmuls (triangular inverse, block compensation) run at full
# f32 precision: a TPU's default f32 dot takes one bf16 pass, and the
# inverse of an ill-conditioned Hessian does not survive that.
_mm = partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)


def prepare_hessian(h: jax.Array, damp: float = 0.01) -> jax.Array:
    """Symmetrize, fix dead rows, dampen."""
    hf = h.astype(jnp.float32)
    hf = 0.5 * (hf + hf.T)
    d = jnp.diag(hf)
    dead = d <= 0.0
    hf = hf + jnp.diag(jnp.where(dead, 1.0, 0.0))
    mean_d = jnp.mean(jnp.where(dead, 0.0, d))
    hf = hf + damp * jnp.maximum(mean_d, 1e-8) * jnp.eye(hf.shape[0])
    return hf


def _inv_upper(u: jax.Array, block: int = 64) -> jax.Array:
    """Batch-invariant inverse of an upper-triangular matrix.

    Recursive 2x2 block back-substitution: diagonal blocks <= ``block``
    invert by masked row back-substitution, off-diagonal blocks combine as
    -A⁻¹·C·D⁻¹.  Everything is matmuls + elementwise ops, which XLA rounds
    identically whether the program is vmapped or not — unlike the library
    ``solve_triangular`` (trsm), whose batched CPU path accumulates in a
    different order than the single-matrix call and seeds the ulp-level
    drift that flips GPTQ codes at 2-bit/small-group settings (the vmap
    parity regression in tests/test_pipeline_perf.py)."""
    d = u.shape[-1]
    if d <= block:
        eye = jnp.eye(d, dtype=u.dtype)

        def body(k, v):
            i = d - 1 - k
            ui = jax.lax.dynamic_slice_in_dim(u, i, 1, 0)[0]
            e_i = jax.lax.dynamic_slice_in_dim(eye, i, 1, 0)[0]
            uii = jax.lax.dynamic_slice(u, (i, i), (1, 1))[0, 0]
            row = (e_i - _mm(ui, v)) / uii
            return jax.lax.dynamic_update_slice_in_dim(v, row[None], i, 0)

        return jax.lax.fori_loop(0, d, body, jnp.zeros_like(u))
    m = d // 2
    a, c, dd = u[:m, :m], u[:m, m:], u[m:, m:]
    ai, di = _inv_upper(a, block), _inv_upper(dd, block)
    tr = -_mm(_mm(ai, c), di)
    top = jnp.concatenate([ai, tr], axis=1)
    bot = jnp.concatenate([jnp.zeros((d - m, m), u.dtype), di], axis=1)
    return jnp.concatenate([top, bot], axis=0)


def hinv_cholesky(h: jax.Array) -> jax.Array:
    """Upper-triangular U with H^-1 = U^T U.

    Direct formulation: factor the index-reversed H as J·H·J = L̃ L̃^T, so
    H = Ũ Ũ^T with Ũ = J·L̃·J upper-triangular (a "UL" factorization), and
    H^-1 = Ũ^-T Ũ^-1, i.e. U = Ũ^-1.  One Cholesky + one triangular inverse
    — versus the naive Cholesky → full inverse → re-Cholesky chain, this
    halves the O(d^3) setup work per solve.  U equals the upper Cholesky
    factor of H^-1 (unique for a positive diagonal) up to rounding.

    The triangular inverse uses the batch-invariant blocked form
    (``_inv_upper``) so batched (vmapped) and sequential solves produce
    bit-identical U — a precondition for exact batched-vs-sequential code
    parity (Cholesky itself is already batch-invariant on all backends we
    run)."""
    lr = jnp.linalg.cholesky(h[::-1, ::-1])
    ut = lr[::-1, ::-1]  # upper, H = ut @ ut.T
    return _inv_upper(ut)


@partial(jax.jit, static_argnames=("spec", "block"))
def gptq_quantize(w: jax.Array, h: jax.Array, spec: QuantSpec,
                  *, damp: float = 0.01, block: int = 128):
    """w: (d_in, d_out); h: (d_in, d_in) (already includes token scaling).

    Returns dict with:
      ``w_deq``  (d_in, d_out) dequantized weight (same dtype as w)
      ``q``      (d_in, d_out) int32 codes
      ``scale``/``zero`` (n_groups, d_out)
      ``err``    scalar proxy loss sum_i ||(w_i - q_i)/U_ii||^2
    """
    d_in, d_out = w.shape
    block = min(block, d_in)
    assert d_in % block == 0, (d_in, block)
    gs = d_in if spec.group_size == -1 else spec.group_size
    # group boundaries must align with block boundaries; groups larger than a
    # block are only supported as the single global group (group_size == -1)
    assert (gs <= block and block % gs == 0) or spec.group_size == -1, \
        (gs, block)
    rows_per_group = min(gs, block)
    n_blocks = d_in // block

    hf = prepare_hessian(h, damp)
    u = hinv_cholesky(hf)  # (d_in, d_in) upper

    w0 = w.astype(jnp.float32)
    groups_per_block = block // rows_per_group if gs <= block else 0

    def block_step(carry, b):
        wc = carry
        wb = jax.lax.dynamic_slice(wc, (b * block, 0), (block, d_out))
        ub = jax.lax.dynamic_slice(u, (b * block, b * block), (block, block))

        def row_step(i, state):
            wb, qb, deqb, errb, scaleb, zerob = state
            row = jax.lax.dynamic_slice(wb, (i, 0), (1, d_out))[0]
            if gs <= block:
                # entering a new group? -> (re)compute params from the
                # *current* (already-compensated) rows of this group
                grp = jax.lax.dynamic_slice(
                    wb, ((i // rows_per_group) * rows_per_group, 0),
                    (rows_per_group, d_out))
                s_new, z_new = find_params(grp, spec)
                at_boundary = (i % rows_per_group) == 0
                g_idx = i // rows_per_group
                s_cur = jnp.where(at_boundary, s_new, scaleb[g_idx])
                z_cur = jnp.where(at_boundary, z_new, zerob[g_idx])
                scaleb = scaleb.at[g_idx].set(s_cur)
                zerob = zerob.at[g_idx].set(z_cur)
            else:
                # group spans multiple blocks: params fixed from the global
                # precomputed scale (see below); scaleb holds a single row
                s_cur, z_cur = scaleb[0], zerob[0]
            qrow = quantize_rtn(row, s_cur, z_cur, spec)
            deq = dequantize(qrow, s_cur, z_cur)
            d_ii = ub[i, i]
            err = (row - deq) / d_ii
            # compensate the remaining rows of this block (j > i)
            mask = (jnp.arange(block) > i).astype(jnp.float32)
            wb = wb - (mask * ub[i])[:, None] * err[None, :]
            qb = qb.at[i].set(qrow)
            deqb = deqb.at[i].set(deq)
            errb = errb.at[i].set(err)
            return wb, qb, deqb, errb, scaleb, zerob

        qb0 = jnp.zeros((block, d_out), jnp.int32)
        deqb0 = jnp.zeros((block, d_out), jnp.float32)
        errb0 = jnp.zeros((block, d_out), jnp.float32)
        if gs <= block:
            sb0 = jnp.zeros((groups_per_block, d_out), jnp.float32)
            zb0 = jnp.zeros((groups_per_block, d_out), jnp.float32)
        else:
            # one global group: compute once from the original weight
            s_all, z_all = find_params(w0, spec)
            sb0, zb0 = s_all[None], z_all[None]
        wb, qb, deqb, errb, sb, zb = jax.lax.fori_loop(
            0, block, row_step, (wb, qb0, deqb0, errb0, sb0, zb0))

        # deferred compensation of all rows after this block (one matmul)
        u_rows = jax.lax.dynamic_slice(u, (b * block, 0), (block, d_in))
        col_mask = (jnp.arange(d_in) >= (b + 1) * block).astype(jnp.float32)
        wc = wc - _mm((u_rows * col_mask[None, :]).T, errb)
        # write the final (dequantized) rows back
        wc = jax.lax.dynamic_update_slice(wc, deqb, (b * block, 0))
        return wc, (qb, deqb, sb, zb, jnp.sum(errb * errb))

    wc, (qs, deqs, ss, zs, errs) = jax.lax.scan(
        block_step, w0, jnp.arange(n_blocks))
    w_deq = deqs.reshape(d_in, d_out).astype(w.dtype)
    q = qs.reshape(d_in, d_out)
    if gs <= block:
        scale = ss.reshape(-1, d_out)
        zero = zs.reshape(-1, d_out)
    else:
        scale, zero = ss[0], zs[0]
    return {"w_deq": w_deq, "q": q, "scale": scale, "zero": zero,
            "err": jnp.sum(errs)}


def gptq_quantize_ref(w, h, spec: QuantSpec, damp: float = 0.01):
    """Naive OBC recursion (explicit H^-1 downdating) in float64 — the
    oracle the blocked solver is tested against.  An O(d_in) python loop
    of O(d_in²) steps: rows and columns < i are already eliminated (zero),
    so step i updates only the trailing block, which keeps a 4096-wide
    solve to about a minute on a host."""
    import numpy as np

    d_in, d_out = w.shape
    hf = np.asarray(prepare_hessian(jnp.asarray(h), damp), np.float64)
    hinv = np.linalg.inv(hf)
    wf = np.asarray(w, np.float64).copy()
    gs = d_in if spec.group_size == -1 else spec.group_size
    q = np.zeros((d_in, d_out), np.int32)
    deq = np.zeros((d_in, d_out), np.float64)
    scale = np.zeros((d_in // gs, d_out))
    zero = np.zeros((d_in // gs, d_out))
    if spec.group_size == -1:
        s, z = find_params(jnp.asarray(wf, jnp.float32), spec)
        scale[0], zero[0] = np.asarray(s), np.asarray(z)
    for i in range(d_in):
        g = i // gs
        if spec.group_size != -1 and i % gs == 0:
            s, z = find_params(jnp.asarray(wf[i : i + gs], jnp.float32), spec)
            scale[g], zero[g] = np.asarray(s), np.asarray(z)
        qi = np.asarray(quantize_rtn(jnp.asarray(wf[i], jnp.float32),
                                     jnp.asarray(scale[g], jnp.float32),
                                     jnp.asarray(zero[g], jnp.float32), spec))
        di = scale[g] * (qi - zero[g])
        err = (wf[i] - di) / hinv[i, i]
        wf[i:] -= np.outer(hinv[i:, i], err)
        # downdate H^-1 (remove row/col i)
        hinv[i:, i:] -= np.outer(hinv[i:, i], hinv[i, i:]) / hinv[i, i]
        hinv[i, :] = 0.0
        hinv[:, i] = 0.0
        hinv[i, i] = 1.0
        q[i], deq[i] = qi, di
        wf[i] = di
    return {"w_deq": deq.astype(np.float32), "q": q,
            "scale": scale.astype(np.float32), "zero": zero.astype(np.float32)}
