"""Quantized-KV flash decode: single-token attention against int8 / 2-bit
log-quantized KV tiles, dequantized in-register.

Long-context decode is bound by KV-cache HBM traffic: the whole cache is
read once per generated token per layer.  Storing the cache as quant
codes + scales cuts that traffic to ~bits/16 of a bf16 cache — but only
if attention consumes the codes *directly*.  These kernels stream
(s_blk, d) KV tiles into VMEM still packed, unpack + dequantize them on
the VPU, and feed the MXU — the cache is never materialized in fp, and a
running max/sum-shifted ``(m, l, acc)`` triple (flash-decode softmax)
carries the result across tiles via output-ref accumulation over the
"arbitrary" grid axis, the same pattern as ``quant_matmul``'s o_ref.

Two kernels share the tile dequant + streaming update:

  * :func:`flash_decode_pallas`     — GQA-aware: one grid step per
    (batch, kv_tile) over every KV head of the tile, each head's (G, Dh)
    query group contracted against its *un-repeated* cache slice
    (head-repeating the cache is exactly the memory blowup this path
    exists to avoid).
  * :func:`mla_flash_decode_pallas` — MLA's absorbed decode is 1-kv-head
    attention in latent space: scores are q_lat·c + q_rope·r over the
    compressed cache, values are the latents themselves.  Taking the
    c and r codes as separate operands avoids materializing a concat of
    cache codes per step.

Both return raw partials ``(acc, m, l)`` (acc unnormalized) so the same
kernel serves the local path and the split-KV ``shard_map`` path (ops.py
merges shard partials with one tiny collective and normalizes once).

Quantized formats (produced by ``models.attention``):

  * ``kv_bits=8`` — int8 codes, per-(token, head) bf16 scales
    (``kv_quantize``; ``chunk=1`` here).
  * ``kv_bits=2`` — LogQuant-style log-distributed codes
    value = scale * [-1, -0.25, +0.25, +1][code], packed 16 codes per
    uint32 along the feature axis, one bf16 scale per (chunk, head)
    group of tokens (``kv_log_encode``; ``chunk=cfg.kv_chunk``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _dequant_kv(codes, scale, *, kv_bits: int, chunk: int, d: int):
    """Dequantize one (rows, d) KV tile in-register.

    ``codes``: (rows, d) int8 or (rows, ceil(d/16)) uint32 2-bit packed;
    ``scale``: (rows // chunk,) or (rows // chunk, 1) bf16, broadcast to
    per-row.  Shift/mask unpack on the VPU (same idiom as
    ``quant_matmul._dequant_tile``), fp32 result."""
    scale = scale.reshape(-1, 1).astype(jnp.float32)
    if scale.shape[0] == 1:
        # one scale for the whole tile (page == chunk): a scalar splat —
        # Mosaic cannot broadcast a (1, 1) vector over both axes
        scale = scale[0, 0]
    elif chunk > 1:
        scale = jnp.repeat(scale, chunk, axis=0)
    if kv_bits == 8:
        # kv_quantize folds the /127 into the stored scale
        return codes.astype(jnp.float32) * scale
    shifts = (jnp.arange(16, dtype=jnp.uint32) * 2)[None, None, :]
    c = ((codes[:, :, None] >> shifts) & jnp.uint32(3)).astype(jnp.int32)
    c = c.reshape(codes.shape[0], -1)[:, :d]
    # log levels scale*[-1, -0.25, +0.25, +1] for codes 0..3, branch-free
    mag = jnp.where((c == 1) | (c == 2), 0.25, 1.0).astype(jnp.float32)
    sgn = jnp.where(c >= 2, 1.0, -1.0).astype(jnp.float32)
    return sgn * mag * scale


def _tile_update(scores, v, valid, m_prev, l_prev, acc_prev):
    """One tile's streaming-softmax update of the (m, l, acc) triple.

    ``scores``: (rows_q, s_blk) raw (unmasked) scores; ``v``: (s_blk, dv)
    dequantized values; ``valid``: (1, s_blk) position mask.  Shared
    verbatim by the Pallas kernels and the grouped-einsum refs — the
    bit-parity contract between them holds by construction."""
    s = jnp.where(valid, scores, NEG_INF)
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    # In decode the masked region is the *tail* (pos < S), so without the
    # explicit zero exp(NEG_INF - NEG_INF) = 1 garbage would survive — no
    # later tile's alpha ever rescales the final tiles away.
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
    acc_new = alpha * acc_prev + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    return m_new, l_new, acc_new


def _gqa_heads_update(q_ref, kq_ref, ks_ref, vq_ref, vs_ref, acc_ref,
                      m_ref, l_ref, valid, *, kv_bits: int, chunk: int,
                      dh: int, dv: int, heads=None):
    """Streaming update of KV heads over one whole-KV-axis cache tile.

    The code blocks are ``(1, rows, KV, w)`` and the scale blocks
    ``(1, rows // chunk, KV)``: their last two dims are the cache's own,
    the only blocking Mosaic accepts for a per-head slice (128 int8 or 8
    uint32 words) narrower than its (8, 128) tile.  Heads are a static
    unroll — each contracts its (G, Dh) query group against its own
    un-repeated (rows, Dh) slice, the per-head math of the refs.
    ``heads`` lists (cache head, query/partials index) pairs; the default
    is every head of a tile whose query block also holds every head."""
    if heads is None:
        heads = [(j, j) for j in range(q_ref.shape[1])]
    for jc, jq in heads:
        k = _dequant_kv(kq_ref[0, :, jc], ks_ref[0, :, jc:jc + 1],
                        kv_bits=kv_bits, chunk=chunk, d=dh)  # (rows, Dh)
        v = _dequant_kv(vq_ref[0, :, jc], vs_ref[0, :, jc:jc + 1],
                        kv_bits=kv_bits, chunk=chunk, d=dv)  # (rows, Dv)
        scores = jax.lax.dot_general(
            q_ref[0, jq].astype(jnp.float32), k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # (G, rows)
        m_new, l_new, acc_new = _tile_update(
            scores, v, valid, m_ref[0, jq], l_ref[0, jq], acc_ref[0, jq])
        m_ref[0, jq] = m_new
        l_ref[0, jq] = l_new
        acc_ref[0, jq] = acc_new


def _init_partials(acc_ref, m_ref, l_ref):
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)


def _fd_kernel(pos_ref, q_ref, kq_ref, ks_ref, vq_ref, vs_ref,
               acc_ref, m_ref, l_ref, *, kv_bits: int, chunk: int,
               dh: int, dv: int, s_blk: int):
    kk = pl.program_id(1)
    pl.when(kk == 0)(lambda: _init_partials(acc_ref, m_ref, l_ref))
    idx = kk * s_blk + jax.lax.broadcasted_iota(jnp.int32, (1, s_blk), 1)
    _gqa_heads_update(q_ref, kq_ref, ks_ref, vq_ref, vs_ref, acc_ref, m_ref,
                      l_ref, idx <= pos_ref[0], kv_bits=kv_bits,
                      chunk=chunk, dh=dh, dv=dv)


def _partials_out(lead, n_rows, dv):
    """Shapes of an f32 ``(acc, m, l)`` triple of ``n_rows`` query rows."""
    return [jax.ShapeDtypeStruct(lead + (n_rows, d), jnp.float32)
            for d in (dv, 1, 1)]


@functools.partial(jax.jit, static_argnames=(
    "kv_bits", "chunk", "dh", "dv", "s_blk", "interpret"))
def flash_decode_pallas(q, kq, ks, vq, vs, pos, *, kv_bits: int, chunk: int,
                        dh: int, dv: int, s_blk: int,
                        interpret: bool = True):
    """GQA flash decode over a quantized cache -> raw partials.

    q: (B, KV, G, Dh) — query groups, attention scale already folded in;
    kq/vq: (B, S, KV, Dh) int8 or (B, S, KV, ceil(D/16)) uint32;
    ks/vs: (B, S // chunk, KV) bf16; pos: (1, 1) int32.  One grid step
    per (batch, kv tile) streams every KV head of the tile
    (:func:`_gqa_heads_update`).  Returns f32 ``(acc, m, l)``:
    (B, KV, G, Dv) unnormalized accumulator plus (B, KV, G, 1) running
    max / denominator."""
    b, kv, g, _ = q.shape
    s = kq.shape[1]
    assert s % s_blk == 0 and s_blk % chunk == 0, (s, s_blk, chunk)
    rows_c = s_blk // chunk
    wk, wv = kq.shape[-1], vq.shape[-1]
    kernel = functools.partial(_fd_kernel, kv_bits=kv_bits, chunk=chunk,
                               dh=dh, dv=dv, s_blk=s_blk)
    per_b = lambda i, kk, pos: (i, 0, 0, 0)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, s // s_blk),
        in_specs=[
            pl.BlockSpec((1, kv, g, q.shape[-1]), per_b),
            pl.BlockSpec((1, s_blk, kv, wk), lambda i, kk, pos: (i, kk, 0, 0)),
            pl.BlockSpec((1, rows_c, kv), lambda i, kk, pos: (i, kk, 0)),
            pl.BlockSpec((1, s_blk, kv, wv), lambda i, kk, pos: (i, kk, 0, 0)),
            pl.BlockSpec((1, rows_c, kv), lambda i, kk, pos: (i, kk, 0)),
        ],
        out_specs=[pl.BlockSpec((1, kv, g, d), per_b) for d in (dv, 1, 1)],
    )
    acc, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=_partials_out((b, kv), g, dv),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.reshape(pos, (1,)).astype(jnp.int32), q, kq, ks, vq, vs)
    return acc, m, l


# --------------------------------------------------- paged (page-table) GQA


def _paged_fd_kernel(tbl_ref, pos_ref, q_ref, kq_ref, ks_ref, vq_ref, vs_ref,
                     acc_ref, m_ref, l_ref, *, kv_bits: int, chunk: int,
                     dh: int, dv: int, page: int):
    i, kk = pl.program_id(0), pl.program_id(1)
    pl.when(kk == 0)(lambda: _init_partials(acc_ref, m_ref, l_ref))
    # logical position of tile kk's rows is kk*page regardless of which
    # physical page the table routed here — fully masked (trailing) tiles
    # are exact no-ops of _tile_update, so stale/trash table entries past
    # a request's pos never perturb the result
    idx = kk * page + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
    _gqa_heads_update(q_ref, kq_ref, ks_ref, vq_ref, vs_ref, acc_ref, m_ref,
                      l_ref, idx <= pos_ref[i, 0], kv_bits=kv_bits,
                      chunk=chunk, dh=dh, dv=dv)


@functools.partial(jax.jit, static_argnames=(
    "kv_bits", "chunk", "dh", "dv", "page", "interpret"))
def paged_flash_decode_pallas(tbl, pos, q, kq, ks, vq, vs, *, kv_bits: int,
                              chunk: int, dh: int, dv: int, page: int,
                              interpret: bool = True):
    """GQA flash decode over a block-paged quantized cache -> raw partials.

    The sequence axis is indirected through a per-request page table: tile
    ``kk`` of request ``i`` streams physical page ``tbl[i, kk]`` (every KV
    head of it) from the shared pools.  ``tbl``/``pos`` ride in as
    scalar-prefetch operands (SMEM) so the page id is available to the
    BlockSpec index_map — the kernel walks the table, it never sees a
    contiguous sequence axis.

    tbl: (B, n_tiles) int32; pos: (B, 1) int32 per-request last valid row;
    q: (B, KV, G, Dh) with the attention scale folded in;
    kq/vq: (n_pages, page, KV, wk|wv) code pools; ks/vs:
    (n_pages, page // chunk, KV) scale pools.  Returns the same f32
    ``(acc, m, l)`` triple as :func:`flash_decode_pallas` — with identical
    tile math, so paged == flat holds bitwise at a matched tile size."""
    b, kv, g, _ = q.shape
    n_tiles = tbl.shape[1]
    assert page % chunk == 0, (page, chunk)
    rows_c = page // chunk
    wk, wv = kq.shape[-1], vq.shape[-1]
    kernel = functools.partial(_paged_fd_kernel, kv_bits=kv_bits,
                               chunk=chunk, dh=dh, dv=dv, page=page)
    per_b = lambda i, kk, tbl, pos: (i, 0, 0, 0)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, n_tiles),
        in_specs=[
            pl.BlockSpec((1, kv, g, q.shape[-1]), per_b),
            pl.BlockSpec((1, page, kv, wk),
                         lambda i, kk, tbl, pos: (tbl[i, kk], 0, 0, 0)),
            pl.BlockSpec((1, rows_c, kv),
                         lambda i, kk, tbl, pos: (tbl[i, kk], 0, 0)),
            pl.BlockSpec((1, page, kv, wv),
                         lambda i, kk, tbl, pos: (tbl[i, kk], 0, 0, 0)),
            pl.BlockSpec((1, rows_c, kv),
                         lambda i, kk, tbl, pos: (tbl[i, kk], 0, 0)),
        ],
        out_specs=[pl.BlockSpec((1, kv, g, d), per_b) for d in (dv, 1, 1)],
    )
    acc, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=_partials_out((b, kv), g, dv),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(tbl, pos, q, kq, ks, vq, vs)
    return acc, m, l


# ------------------------------------------- chunked-prefill (extend) GQA


def _paged_fe_kernel(tbl_ref, q_ref, kf_ref, vf_ref, kq_ref, ks_ref, vq_ref,
                     vs_ref, acc_ref, m_ref, l_ref, *, kv_bits: int,
                     chunk: int, dh: int, dv: int, page: int, n_past: int,
                     g: int):
    i, kk = pl.program_id(0), pl.program_id(1)
    pl.when(kk == 0)(lambda: _init_partials(acc_ref, m_ref, l_ref))
    valid = jnp.ones((1, page), bool)  # past pages are full
    # the page blocks carry every KV head (see _gqa_heads_update) and
    # Mosaic indexes that axis only statically: one branch per head
    for j in range(kq_ref.shape[2]):
        pl.when((kk < n_past) & (i == j))(functools.partial(
            _gqa_heads_update, q_ref, kq_ref, ks_ref, vq_ref, vs_ref,
            acc_ref, m_ref, l_ref, valid, kv_bits=kv_bits, chunk=chunk,
            dh=dh, dv=dv, heads=[(j, 0)]))

    @pl.when(kk == n_past)
    def _chunk_tile():
        q = q_ref[0, 0]  # (L*g, Dh) f32, scale pre-folded
        kf = kf_ref[0]  # (Lp, Dh) f32 — this chunk's fresh keys (padded)
        vf = vf_ref[0]  # (Lp, Dv)
        rows, cols = q.shape[0], kf.shape[0]
        scores = jax.lax.dot_general(
            q, kf, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # (L*g, Lp)
        # within-chunk causal: query row i is token i // g of the chunk,
        # key column j is token j — the page-aligned ``start`` offsets
        # both sides identically and cancels; padded key rows (j >= L)
        # exceed every query token and mask out for free
        causal = (jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0) // g
                  >= jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1))
        m_new, l_new, acc_new = _tile_update(
            scores, vf, causal, m_ref[0, 0], l_ref[0, 0], acc_ref[0, 0])
        m_ref[0, 0] = m_new
        l_ref[0, 0] = l_new
        acc_ref[0, 0] = acc_new


@functools.partial(jax.jit, static_argnames=(
    "kv_bits", "chunk", "dh", "dv", "page", "interpret"))
def paged_flash_extend_pallas(tbl, q, k_new, v_new, kq, ks, vq, vs, start, *,
                              kv_bits: int, chunk: int, dh: int, dv: int,
                              page: int, interpret: bool = True):
    """Chunked-prefill GQA extend over a block-paged quantized cache.

    Same contract as ``paged_flash_extend_ref`` (bit-identical at
    tile = page, pinned in tests): an L-token chunk attends to the
    request's quantized past pages (``tbl``: (n_past,) int32, every page
    full because chunk boundaries are page-aligned) plus its own fp
    keys/values with a within-chunk causal mask.  The grid walks
    (kv_head, past pages + 1 fp tile); past pages dequantize in-register
    exactly like :func:`paged_flash_decode_pallas`, and each head's step
    reads the whole-KV page block (the one blocking Mosaic accepts), so
    past pages are read once per head.  q: (1, L, H, Dh) *unscaled*;
    k_new/v_new: (1, L, KV, Dh|Dv) fp.  Returns (1, L, H, Dv) f32
    normalized output."""
    _, L, h, _ = q.shape
    kv = k_new.shape[2]
    g = h // kv
    n_past = tbl.shape[0]
    assert page % chunk == 0, (page, chunk)
    rows_c = page // chunk
    wk, wv = kq.shape[-1], vq.shape[-1]
    qf = (q.astype(jnp.float32) * (dh ** -0.5))[0]          # (L, H, Dh)
    qf = jnp.moveaxis(qf.reshape(L, kv, g, dh), 1, 0)       # (KV, L, g, Dh)
    qf = qf.reshape(1, kv, L * g, dh)                       # rows = (l, g)
    kf = jnp.moveaxis(k_new[0].astype(jnp.float32), 1, 0)   # (KV, L, Dh)
    vf = jnp.moveaxis(v_new[0].astype(jnp.float32), 1, 0)   # (KV, L, Dv)
    # pad the fp tile to a sublane multiple: tiny L would hand XLA a
    # degenerate contraction it rewrites (fma) differently per context,
    # breaking kernel == ref bit-parity; padded rows mask out causally
    # and are exact no-ops of _tile_update
    Lp = -(-L // 8) * 8
    if Lp != L:
        kf = jnp.pad(kf, ((0, 0), (0, Lp - L), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, Lp - L), (0, 0)))
    del start  # page-aligned: cancels from the causal mask
    # the fp tile's grid step still maps a (never-read) page block; clamp
    # its table lookup in range, with a trash entry when there is no past
    tbl_x = tbl if n_past else jnp.zeros((1,), jnp.int32)

    def _pg(kk, tbl):
        return tbl[jnp.maximum(jnp.minimum(kk, n_past - 1), 0)]

    kernel = functools.partial(_paged_fe_kernel, kv_bits=kv_bits,
                               chunk=chunk, dh=dh, dv=dv, page=page,
                               n_past=n_past, g=g)
    # q and the (acc, m, l) outputs keep a unit lead axis so the head
    # axis (blocked at 1) sits third-from-last, the whole-KV page blocks'
    # layout in _gqa_heads_update
    per_head = lambda i, kk, tbl: (0, i, 0, 0)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(kv, n_past + 1),
        in_specs=[
            pl.BlockSpec((1, 1, L * g, dh), per_head),
            pl.BlockSpec((1, Lp, dh), lambda i, kk, tbl: (i, 0, 0)),
            pl.BlockSpec((1, Lp, dv), lambda i, kk, tbl: (i, 0, 0)),
            pl.BlockSpec((1, page, kv, wk),
                         lambda i, kk, tbl: (_pg(kk, tbl), 0, 0, 0)),
            pl.BlockSpec((1, rows_c, kv),
                         lambda i, kk, tbl: (_pg(kk, tbl), 0, 0)),
            pl.BlockSpec((1, page, kv, wv),
                         lambda i, kk, tbl: (_pg(kk, tbl), 0, 0, 0)),
            pl.BlockSpec((1, rows_c, kv),
                         lambda i, kk, tbl: (_pg(kk, tbl), 0, 0)),
        ],
        out_specs=[pl.BlockSpec((1, 1, L * g, d), per_head)
                   for d in (dv, 1, 1)],
    )
    acc, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=_partials_out((1, kv), L * g, dv),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(tbl_x, qf, kf, vf, kq, ks, vq, vs)
    out = acc[0] / jnp.maximum(l[0], 1e-30)                 # (KV, L*g, Dv)
    out = jnp.moveaxis(out.reshape(kv, L, g, dv), 0, 1)     # (L, KV, g, Dv)
    return out.reshape(L, h, dv)[None]


def _mla_fd_kernel(pos_ref, ql_ref, qr_ref, cq_ref, cs_ref, rq_ref, rs_ref,
                   acc_ref, m_ref, l_ref, *, kv_bits: int, chunk: int,
                   dl: int, dr: int, s_blk: int):
    kk = pl.program_id(1)
    pl.when(kk == 0)(lambda: _init_partials(acc_ref, m_ref, l_ref))
    idx = kk * s_blk + jax.lax.broadcasted_iota(jnp.int32, (1, s_blk), 1)
    _mla_update(ql_ref[0], qr_ref[0], cq_ref, cs_ref, rq_ref, rs_ref,
                acc_ref, m_ref, l_ref, idx <= pos_ref[0], kv_bits=kv_bits,
                chunk=chunk, dl=dl, dr=dr)


def _mla_update(ql, qr, cq_ref, cs_ref, rq_ref, rs_ref, acc_ref, m_ref,
                l_ref, valid, *, kv_bits: int, chunk: int, dl: int, dr: int):
    """One latent tile's streaming update: scores q_lat·c + q_rope·r over
    the (rows, ·) code blocks, values the latents themselves.  Scale
    blocks are (1, rows // chunk, 1) — the wrappers give the 2-D scale
    arrays a unit lane axis, so the block's last two dims are legal for
    Mosaic whatever the batch or pool size."""
    ql = ql.astype(jnp.float32)  # (rows_q, dl), scale pre-folded
    qr = qr.astype(jnp.float32)  # (rows_q, dr)
    c = _dequant_kv(cq_ref[0], cs_ref[0], kv_bits=kv_bits, chunk=chunk,
                    d=dl)        # (rows, dl) — keys *and* values
    r = _dequant_kv(rq_ref[0], rs_ref[0], kv_bits=kv_bits, chunk=chunk,
                    d=dr)        # (rows, dr)
    scores = (jax.lax.dot_general(ql, c, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
              + jax.lax.dot_general(qr, r, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32))
    lead = 0 if acc_ref.ndim == 3 else ...  # per-batch block or whole
    m_new, l_new, acc_new = _tile_update(
        scores, c, valid, m_ref[lead], l_ref[lead], acc_ref[lead])
    m_ref[lead] = m_new
    l_ref[lead] = l_new
    acc_ref[lead] = acc_new


@functools.partial(jax.jit, static_argnames=(
    "kv_bits", "chunk", "dl", "dr", "s_blk", "interpret"))
def mla_flash_decode_pallas(ql, qr, cq, cs, rq, rs, pos, *, kv_bits: int,
                            chunk: int, dl: int, dr: int, s_blk: int,
                            interpret: bool = True):
    """MLA (absorbed, latent-space) flash decode -> raw partials.

    ql: (B, H, dl) latent queries, qr: (B, H, dr) rope queries — the
    (dn + dr)^-0.5 attention scale already folded in; cq: (B, S, dl) int8
    or (B, S, ceil(dl/16)) uint32 latent codes; cs: (B, S // chunk) bf16;
    rq/rs likewise for the shared rope key.  Values are the latents
    themselves (v = c).  Returns f32 ``(acc, m, l)``: (B, H, dl) + 2x
    (B, H, 1)."""
    b, h, _ = ql.shape
    s = cq.shape[1]
    assert s % s_blk == 0 and s_blk % chunk == 0, (s, s_blk, chunk)
    rows_c = s_blk // chunk
    kernel = functools.partial(_mla_fd_kernel, kv_bits=kv_bits, chunk=chunk,
                               dl=dl, dr=dr, s_blk=s_blk)
    per_b = lambda i, kk, pos: (i, 0, 0)  # noqa: E731
    tile = lambda i, kk, pos: (i, kk, 0)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, s // s_blk),
        in_specs=[
            pl.BlockSpec((1, h, ql.shape[-1]), per_b),
            pl.BlockSpec((1, h, qr.shape[-1]), per_b),
            pl.BlockSpec((1, s_blk, cq.shape[-1]), tile),
            pl.BlockSpec((1, rows_c, 1), tile),
            pl.BlockSpec((1, s_blk, rq.shape[-1]), tile),
            pl.BlockSpec((1, rows_c, 1), tile),
        ],
        out_specs=[pl.BlockSpec((1, h, d), per_b) for d in (dl, 1, 1)],
    )
    acc, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=_partials_out((b,), h, dl),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.reshape(pos, (1,)).astype(jnp.int32), ql, qr, cq, cs[..., None],
      rq, rs[..., None])
    return acc, m, l


# --------------------------------------------------- paged (page-table) MLA


def _paged_mla_fd_kernel(tbl_ref, pos_ref, ql_ref, qr_ref, cq_ref, cs_ref,
                         rq_ref, rs_ref, acc_ref, m_ref, l_ref, *,
                         kv_bits: int, chunk: int, dl: int, dr: int,
                         page: int):
    i, kk = pl.program_id(0), pl.program_id(1)
    pl.when(kk == 0)(lambda: _init_partials(acc_ref, m_ref, l_ref))
    idx = kk * page + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
    _mla_update(ql_ref[0], qr_ref[0], cq_ref, cs_ref, rq_ref, rs_ref,
                acc_ref, m_ref, l_ref, idx <= pos_ref[i, 0],
                kv_bits=kv_bits, chunk=chunk, dl=dl, dr=dr)


@functools.partial(jax.jit, static_argnames=(
    "kv_bits", "chunk", "dl", "dr", "page", "interpret"))
def paged_mla_flash_decode_pallas(tbl, pos, ql, qr, cq, cs, rq, rs, *,
                                  kv_bits: int, chunk: int, dl: int,
                                  dr: int, page: int,
                                  interpret: bool = True):
    """MLA (absorbed, latent-space) flash decode over block-paged pools.

    tbl: (B, n_tiles) int32; pos: (B, 1) int32; ql/qr: (B, H, dl|dr) with
    the attention scale folded in; cq/rq: (n_pages, page, wc|wr) latent /
    rope code pools; cs/rs: (n_pages, page // chunk) scale pools.  Same
    tile math as :func:`mla_flash_decode_pallas` (paged == flat bitwise at
    a matched tile); values are the latents (v = c).  Returns f32
    ``(acc, m, l)``: (B, H, dl) + 2x (B, H, 1)."""
    b, h, _ = ql.shape
    n_tiles = tbl.shape[1]
    assert page % chunk == 0, (page, chunk)
    rows_c = page // chunk
    kernel = functools.partial(_paged_mla_fd_kernel, kv_bits=kv_bits,
                               chunk=chunk, dl=dl, dr=dr, page=page)
    per_b = lambda i, kk, tbl, pos: (i, 0, 0)  # noqa: E731
    pg = lambda i, kk, tbl, pos: (tbl[i, kk], 0, 0)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, n_tiles),
        in_specs=[
            pl.BlockSpec((1, h, ql.shape[-1]), per_b),
            pl.BlockSpec((1, h, qr.shape[-1]), per_b),
            pl.BlockSpec((1, page, cq.shape[-1]), pg),
            pl.BlockSpec((1, rows_c, 1), pg),
            pl.BlockSpec((1, page, rq.shape[-1]), pg),
            pl.BlockSpec((1, rows_c, 1), pg),
        ],
        out_specs=[pl.BlockSpec((1, h, d), per_b) for d in (dl, 1, 1)],
    )
    acc, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=_partials_out((b,), h, dl),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(tbl, pos, ql, qr, cq, cs[..., None], rq, rs[..., None])
    return acc, m, l


# ------------------------------------------- chunked-prefill (extend) MLA


def _paged_mla_fe_kernel(tbl_ref, ql_ref, qr_ref, cf_ref, rf_ref, cq_ref,
                         cs_ref, rq_ref, rs_ref, acc_ref, m_ref, l_ref, *,
                         kv_bits: int, chunk: int, dl: int, dr: int,
                         page: int, n_past: int, h: int):
    kk = pl.program_id(0)
    pl.when(kk == 0)(lambda: _init_partials(acc_ref, m_ref, l_ref))
    ql = ql_ref[...]  # (L*h, dl) f32, scale pre-folded
    qr = qr_ref[...]  # (L*h, dr)
    valid = jnp.ones((1, cq_ref.shape[1]), bool)  # past pages are full
    pl.when(kk < n_past)(functools.partial(
        _mla_update, ql, qr, cq_ref, cs_ref, rq_ref, rs_ref, acc_ref, m_ref,
        l_ref, valid, kv_bits=kv_bits, chunk=chunk, dl=dl, dr=dr))

    @pl.when(kk == n_past)
    def _chunk_tile():
        cf = cf_ref[...]  # (Lp, dl) f32 — this chunk's latents (padded)
        rf = rf_ref[...]  # (Lp, dr)
        rows, cols = ql.shape[0], cf.shape[0]
        scores = (jax.lax.dot_general(ql, cf, (((1,), (1,)), ((), ())),
                                      preferred_element_type=jnp.float32)
                  + jax.lax.dot_general(qr, rf, (((1,), (1,)), ((), ())),
                                        preferred_element_type=jnp.float32))
        # query row i is chunk token i // h, key column j is chunk token
        # j — the page-aligned ``start`` cancels from both sides; padded
        # key rows (j >= L) exceed every query token and mask out free
        causal = (jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0) // h
                  >= jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1))
        m_new, l_new, acc_new = _tile_update(
            scores, cf, causal, m_ref[...], l_ref[...], acc_ref[...])
        m_ref[...] = m_new
        l_ref[...] = l_new
        acc_ref[...] = acc_new


@functools.partial(jax.jit, static_argnames=(
    "kv_bits", "chunk", "dl", "dr", "page", "interpret"))
def paged_mla_flash_extend_pallas(tbl, ql, qr, c_new, r_new, cq, cs, rq, rs,
                                  start, *, kv_bits: int, chunk: int,
                                  dl: int, dr: int, page: int,
                                  interpret: bool = True):
    """Chunked-prefill MLA latent extend over block-paged latent pools.

    Same contract as ``paged_mla_flash_extend_ref`` (bit-identical at
    tile = page, pinned in tests): an L-token chunk's absorbed queries
    attend to the request's quantized latent pages plus the fp
    within-chunk latents (causal); values are the latents (v = c).
    ql/qr: (L, H, dl|dr) *scaled* queries; c_new/r_new: (L, dl|dr) fp.
    Returns (L, H, dl) f32 latent context."""
    L, h, _ = ql.shape
    n_past = tbl.shape[0]
    assert page % chunk == 0, (page, chunk)
    rows_c = page // chunk
    wc, wr = cq.shape[-1], rq.shape[-1]
    qlf = ql.astype(jnp.float32).reshape(L * h, dl)
    qrf = qr.astype(jnp.float32).reshape(L * h, dr)
    cf = c_new.astype(jnp.float32)                          # (L, dl)
    rf = r_new.astype(jnp.float32)                          # (L, dr)
    # pad the fp tile to a sublane multiple (see the GQA extend wrapper)
    Lp = -(-L // 8) * 8
    if Lp != L:
        cf = jnp.pad(cf, ((0, Lp - L), (0, 0)))
        rf = jnp.pad(rf, ((0, Lp - L), (0, 0)))
    del start  # page-aligned: cancels from the causal mask
    tbl_x = tbl if n_past else jnp.zeros((1,), jnp.int32)

    def _pg(kk, tbl):
        return (tbl[jnp.maximum(jnp.minimum(kk, n_past - 1), 0)], 0, 0)

    whole = lambda kk, tbl: (0, 0)  # noqa: E731
    kernel = functools.partial(_paged_mla_fe_kernel, kv_bits=kv_bits,
                               chunk=chunk, dl=dl, dr=dr, page=page,
                               n_past=n_past, h=h)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_past + 1,),
        in_specs=[
            pl.BlockSpec((L * h, dl), whole),
            pl.BlockSpec((L * h, dr), whole),
            pl.BlockSpec((Lp, dl), whole),
            pl.BlockSpec((Lp, dr), whole),
            pl.BlockSpec((1, page, wc), _pg),
            pl.BlockSpec((1, rows_c, 1), _pg),
            pl.BlockSpec((1, page, wr), _pg),
            pl.BlockSpec((1, rows_c, 1), _pg),
        ],
        out_specs=[pl.BlockSpec((L * h, d), whole) for d in (dl, 1, 1)],
    )
    acc, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=_partials_out((), L * h, dl),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(tbl_x, qlf, qrf, cf, rf, cq, cs[..., None], rq, rs[..., None])
    out = acc / jnp.maximum(l, 1e-30)
    return out.reshape(L, h, dl)
