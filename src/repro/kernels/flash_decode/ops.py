"""Public wrapper: flash decode against a quantized KV cache.

Dispatch policy (shared with ``quant_matmul``): the Pallas kernel on TPU,
the tile-matched jnp ref elsewhere; the ``REPRO_FD_KERNEL`` env var
overrides the default (``1`` forces the kernel — interpret mode off-TPU,
a correctness/CI tool; ``0`` forces the ref); an explicit ``use_kernel=``
argument beats both.

Split-KV sharding: when the caller is on a live mesh (``mesh``/``axis``
from the model's ``ParallelCtx``), the KV sequence axis of the cache is
already model-axis-sharded (``launch.specs.cache_shardings`` — context
parallelism), and :func:`flash_decode` runs the kernel *per shard* under
``shard_map``: each device computes flash-decode partials over its local
sequence slice, then the shards merge with a max/sum-shifted partial
softmax.  The only collective is one ``all_gather`` of the concatenated
``(acc, m, l)`` triple — a few hundred bytes per (batch, head) — and
*zero* cache collectives: the codes never move, which is the whole point
of sharding a long cache.  When the local tile can't align (sequence not
divisible by the axis, a 2-bit scale chunk straddling shards) the call
falls back to the GSPMD-partitionable scan ref — an opaque Pallas custom
call under GSPMD would make XLA all-gather the cache, exactly the
quant_matmul mesh policy.  Every such demotion of a call that wanted the
kernel is counted in ``kernels.demotions``, and the CI mesh leg asserts
zero ref calls, so a silently demoted serving config fails the bench.

Tile selection: ``_s_tile`` picks the largest sequence tile <= 512 that
divides S, holds whole scale chunks and keeps the scale block legal for
Mosaic.  ``models.lm`` rounds quantized
cache lengths up to a ``cfg.kv_chunk`` multiple at allocation, so a
healthy serving config always tiles at >= 64 rows.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import demotions
from repro.kernels.flash_decode.kernel import (
    flash_decode_pallas, mla_flash_decode_pallas,
    paged_flash_decode_pallas, paged_flash_extend_pallas,
    paged_mla_flash_decode_pallas, paged_mla_flash_extend_pallas)
from repro.kernels.flash_decode.ref import (
    flash_decode_ref, mla_flash_decode_ref, paged_flash_decode_ref,
    paged_flash_extend_ref, paged_mla_flash_decode_ref,
    paged_mla_flash_extend_ref)


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _kernel_default() -> bool:
    """Backend kernel policy with the ``REPRO_FD_KERNEL`` env override."""
    env = os.environ.get("REPRO_FD_KERNEL")
    if env is not None:
        return env.strip().lower() not in ("", "0", "false", "off")
    return jax.default_backend() == "tpu"


def _smap(fn, mesh, in_specs, out_specs):
    """shard_map with replication checking off: the Pallas custom call has
    no replication rule for the checker to consult."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _s_tile(s: int, chunk: int) -> int:
    """Largest sequence tile <= 512 that divides s, holds whole scale
    chunks and gives Mosaic a legal (tile // chunk, KV) scale block — a
    multiple of the 8-row sublane tile, or the whole axis (0 when none
    exists — caller takes the ref)."""
    best, t = 0, chunk
    lim = min(s, 512)
    while t <= lim:
        if s % t == 0 and ((t // chunk) % 8 == 0 or t == s):
            best = t
        t += chunk
    return best


def _finalize(acc, l):
    return acc / jnp.maximum(l, 1e-30)


def _merge_partials(parts, dv: int):
    """Merge per-shard (acc | m | l) partials gathered on a leading axis:
    shift every shard's unnormalized accumulator/denominator to the global
    max and sum — the distributed-softmax identity."""
    accs, ms, ls = parts[..., :dv], parts[..., dv:dv + 1], parts[..., dv + 1:]
    m_g = jnp.max(ms, axis=0)
    w = jnp.exp(ms - m_g)
    return jnp.sum(w * accs, axis=0) / jnp.maximum(jnp.sum(w * ls, axis=0),
                                                   1e-30)


def _pos2d(pos) -> jax.Array:
    return jnp.reshape(jnp.asarray(pos).astype(jnp.int32), (-1,))[:1][None]


# ------------------------------------------------------------------- GQA


def _split_kv_gqa(q, kq, ks, vq, vs, px, *, mesh, axis, dp, kv_bits, chunk,
                  dh, dv):
    """Split-KV shard_map route; None when the local tile can't align."""
    axis_size = mesh.shape[axis]
    s = kq.shape[1]
    if axis_size < 2 or s % axis_size or ks.shape[1] % axis_size:
        return None
    s_loc = s // axis_size
    if chunk > 1 and s_loc % chunk:
        return None
    s_blk = _s_tile(s_loc, chunk)
    if not s_blk:
        return None

    def local(qx, kqx, ksx, vqx, vsx, pxx):
        # local positions: this shard holds rows [rank*s_loc, ...)
        p_loc = pxx - jax.lax.axis_index(axis) * s_loc
        acc, m, l = flash_decode_pallas(
            qx, kqx, ksx, vqx, vsx, p_loc, kv_bits=kv_bits, chunk=chunk,
            dh=dh, dv=dv, s_blk=s_blk, interpret=_interpret())
        # the ONE collective: tiny (acc, m, l) partials, zero cache bytes
        parts = jax.lax.all_gather(jnp.concatenate([acc, m, l], axis=-1),
                                   axis)
        return _merge_partials(parts, dv)

    qspec, cspec = P(dp), P(dp, axis)
    return _smap(local, mesh,
                 in_specs=(qspec, cspec, cspec, cspec, cspec, P()),
                 out_specs=qspec)(q, kq, ks, vq, vs, px)


def flash_decode(q, kq, ks, vq, vs, pos, *, kv_bits: int, chunk: int,
                 dv: int | None = None, mesh=None, axis=None, dp=None,
                 use_kernel: bool | None = None):
    """Single-token GQA attention directly on quantized KV.

    q: (B, KV, G, Dh) f32 query groups with the attention scale folded in;
    kq/ks/vq/vs: codes + scales as stored in the cache (``models.lm``);
    pos: () int32 — last valid cache row.  Returns (B, KV, G, Dv) f32
    normalized attention output.  ``mesh``/``axis``/``dp`` (from the
    model's ParallelCtx) enable the split-KV shard_map route."""
    dh = q.shape[-1]
    if dv is None:
        assert kv_bits == 8, "dv is required for packed 2-bit codes"
        dv = vq.shape[-1]
    if use_kernel is None:
        use_kernel = _kernel_default()
    px = _pos2d(pos)
    if mesh is not None and axis is not None and use_kernel:
        out = _split_kv_gqa(q, kq, ks, vq, vs, px, mesh=mesh, axis=axis,
                            dp=dp, kv_bits=kv_bits, chunk=chunk, dh=dh,
                            dv=dv)
        if out is not None:
            return out
    s = kq.shape[1]
    s_blk = _s_tile(s, chunk)
    if mesh is None and use_kernel and s_blk:
        acc, _, l = flash_decode_pallas(
            q, kq, ks, vq, vs, px, kv_bits=kv_bits, chunk=chunk, dh=dh,
            dv=dv, s_blk=s_blk, interpret=_interpret())
        return _finalize(acc, l)
    # meshless non-kernel path, or on-mesh misalignment (GSPMD partitions
    # the scan ref; it must never see the opaque kernel custom call)
    if use_kernel:
        demotions.record("flash_decode", f"untileable (S={s}, "
                         f"chunk={chunk}, mesh={mesh is not None})")
    acc, _, l = flash_decode_ref(
        q, kq, ks, vq, vs, px, kv_bits=kv_bits, chunk=chunk, dh=dh, dv=dv,
        s_blk=s_blk or min(s, 512))
    return _finalize(acc, l)


# ------------------------------------------------------------------- MLA


def _split_kv_mla(ql, qr, cq, cs, rq, rs, px, *, mesh, axis, dp, kv_bits,
                  chunk, dl, dr):
    axis_size = mesh.shape[axis]
    s = cq.shape[1]
    if axis_size < 2 or s % axis_size or cs.shape[1] % axis_size:
        return None
    s_loc = s // axis_size
    if chunk > 1 and s_loc % chunk:
        return None
    s_blk = _s_tile(s_loc, chunk)
    if not s_blk:
        return None

    def local(qlx, qrx, cqx, csx, rqx, rsx, pxx):
        p_loc = pxx - jax.lax.axis_index(axis) * s_loc
        acc, m, l = mla_flash_decode_pallas(
            qlx, qrx, cqx, csx, rqx, rsx, p_loc, kv_bits=kv_bits,
            chunk=chunk, dl=dl, dr=dr, s_blk=s_blk, interpret=_interpret())
        parts = jax.lax.all_gather(jnp.concatenate([acc, m, l], axis=-1),
                                   axis)
        return _merge_partials(parts, dl)

    qspec, cspec = P(dp), P(dp, axis)
    return _smap(local, mesh,
                 in_specs=(qspec, qspec, cspec, cspec, cspec, cspec, P()),
                 out_specs=qspec)(ql, qr, cq, cs, rq, rs, px)


def mla_flash_decode(ql, qr, cq, cs, rq, rs, pos, *, kv_bits: int,
                     chunk: int, dl: int, dr: int, mesh=None, axis=None,
                     dp=None, use_kernel: bool | None = None):
    """Single-token MLA latent attention directly on quantized c/r codes.

    ql: (B, H, dl), qr: (B, H, dr) — absorbed queries with the attention
    scale folded in; values are the latents (v = c).  Returns (B, H, dl)
    f32 normalized latent context."""
    if use_kernel is None:
        use_kernel = _kernel_default()
    px = _pos2d(pos)
    if mesh is not None and axis is not None and use_kernel:
        out = _split_kv_mla(ql, qr, cq, cs, rq, rs, px, mesh=mesh,
                            axis=axis, dp=dp, kv_bits=kv_bits, chunk=chunk,
                            dl=dl, dr=dr)
        if out is not None:
            return out
    s = cq.shape[1]
    s_blk = _s_tile(s, chunk)
    if mesh is None and use_kernel and s_blk:
        acc, _, l = mla_flash_decode_pallas(
            ql, qr, cq, cs, rq, rs, px, kv_bits=kv_bits, chunk=chunk,
            dl=dl, dr=dr, s_blk=s_blk, interpret=_interpret())
        return _finalize(acc, l)
    if use_kernel:
        demotions.record("mla_flash_decode", f"untileable (S={s}, "
                         f"chunk={chunk}, mesh={mesh is not None})")
    acc, _, l = mla_flash_decode_ref(
        ql, qr, cq, cs, rq, rs, px, kv_bits=kv_bits, chunk=chunk, dl=dl,
        dr=dr, s_blk=s_blk or min(s, 512))
    return _finalize(acc, l)


# ----------------------------------------------------------------- paged


def paged_flash_decode(tbl, pos, q, kq, ks, vq, vs, *, kv_bits: int,
                       chunk: int, dv: int, page: int,
                       use_kernel: bool | None = None):
    """Single-token GQA attention over a block-paged quantized pool.

    tbl: (B, n_tiles) int32 per-request page table (pad slots with the
    trash page 0); pos: (B,) int32 per-request last valid position;
    q: (B, KV, G, Dh) f32 scaled queries; kq/vq: (n_pages, page, KV, w·)
    code pools; ks/vs: (n_pages, page // chunk, KV) scale pools.  Returns
    (B, KV, G, Dv) f32.

    Serving engines are meshless by design (the engine owns the batch
    axis); there is deliberately no shard_map route here — the split-KV
    policy of :func:`flash_decode` does not apply to paged pools, whose
    sequence axis is virtual (the page table).
    """
    dh = q.shape[-1]
    if use_kernel is None:
        use_kernel = _kernel_default()
    px = jnp.reshape(jnp.asarray(pos).astype(jnp.int32), (q.shape[0], 1))
    if use_kernel:
        acc, _, l = paged_flash_decode_pallas(
            tbl, px, q, kq, ks, vq, vs, kv_bits=kv_bits, chunk=chunk,
            dh=dh, dv=dv, page=page, interpret=_interpret())
    else:
        acc, _, l = paged_flash_decode_ref(
            tbl, px, q, kq, ks, vq, vs, kv_bits=kv_bits, chunk=chunk,
            dh=dh, dv=dv, page=page)
    return _finalize(acc, l)


def paged_mla_flash_decode(tbl, pos, ql, qr, cq, cs, rq, rs, *,
                           kv_bits: int, chunk: int, dl: int, dr: int,
                           page: int, use_kernel: bool | None = None):
    """Single-token MLA latent attention over block-paged latent pools.

    tbl: (B, n_tiles) int32; pos: (B,) int32; ql/qr: (B, H, dl|dr) scaled
    absorbed queries; cq/rq: (n_pages, page, w·) code pools; cs/rs:
    (n_pages, page // chunk) scale pools.  Returns (B, H, dl) f32.
    Meshless, like :func:`paged_flash_decode`."""
    if use_kernel is None:
        use_kernel = _kernel_default()
    px = jnp.reshape(jnp.asarray(pos).astype(jnp.int32), (ql.shape[0], 1))
    if use_kernel:
        acc, _, l = paged_mla_flash_decode_pallas(
            tbl, px, ql, qr, cq, cs, rq, rs, kv_bits=kv_bits, chunk=chunk,
            dl=dl, dr=dr, page=page, interpret=_interpret())
    else:
        acc, _, l = paged_mla_flash_decode_ref(
            tbl, px, ql, qr, cq, cs, rq, rs, kv_bits=kv_bits, chunk=chunk,
            dl=dl, dr=dr, page=page)
    return _finalize(acc, l)


# ---------------------------------------------- paged (chunked-prefill) extend


def paged_flash_extend(tbl, q, k_new, v_new, kq, ks, vq, vs, start, *,
                       kv_bits: int, chunk: int, dh: int, dv: int,
                       page: int, use_kernel: bool | None = None):
    """Chunked-prefill GQA attention over a block-paged quantized pool.

    An L-token query chunk attends to its own request's quantized past
    pages (``tbl``: (n_past,) int32 — chunk boundaries are page-aligned so
    every past page is full) plus its fp within-chunk keys/values
    (causal).  q: (1, L, H, Dh) *unscaled*; k_new/v_new: (1, L, KV, ·) fp;
    start = n_past * page.  Returns (1, L, H, Dv) f32.  Meshless, like
    :func:`paged_flash_decode` (the engine owns the batch axis)."""
    if use_kernel is None:
        use_kernel = _kernel_default()
    if use_kernel:
        return paged_flash_extend_pallas(
            tbl, q, k_new, v_new, kq, ks, vq, vs, start, kv_bits=kv_bits,
            chunk=chunk, dh=dh, dv=dv, page=page, interpret=_interpret())
    return paged_flash_extend_ref(
        tbl, q, k_new, v_new, kq, ks, vq, vs, start, kv_bits=kv_bits,
        chunk=chunk, dh=dh, dv=dv, page=page)


def paged_mla_flash_extend(tbl, ql, qr, c_new, r_new, cq, cs, rq, rs, start,
                           *, kv_bits: int, chunk: int, dl: int, dr: int,
                           page: int, use_kernel: bool | None = None):
    """Chunked-prefill MLA latent attention over block-paged latent pools.

    ql/qr: (L, H, dl|dr) *scaled* absorbed queries; c_new/r_new:
    (L, dl|dr) fp latents of this chunk; values are the latents (v = c).
    Returns (L, H, dl) f32 latent context.  Meshless."""
    if use_kernel is None:
        use_kernel = _kernel_default()
    if use_kernel:
        return paged_mla_flash_extend_pallas(
            tbl, ql, qr, c_new, r_new, cq, cs, rq, rs, start,
            kv_bits=kv_bits, chunk=chunk, dl=dl, dr=dr, page=page,
            interpret=_interpret())
    return paged_mla_flash_extend_ref(
        tbl, ql, qr, c_new, r_new, cq, cs, rq, rs, start, kv_bits=kv_bits,
        chunk=chunk, dl=dl, dr=dr, page=page)
