"""Attention: chunked (flash-style) prefill/train attention, decode attention,
GQA / MLA / cross-attention projections, and attention-concentration capture
for RSQ's AttnCon importance.

The chunked attention never materializes the (T, T) score matrix: it scans
over KV chunks with a running (max, denominator, accumulator) triple — the
TPU-native analogue of FlashAttention.  The scan body is checkpointed so the
backward pass recomputes per-chunk scores instead of storing them.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.quantizer import dequantize_packed
from repro.kernels.flash_decode.ops import (flash_decode, mla_flash_decode,
                                            paged_flash_decode,
                                            paged_flash_extend,
                                            paged_mla_flash_decode,
                                            paged_mla_flash_extend)
from repro.kernels.quant_matmul.ops import (is_packed, mla_latent_weights,
                                            quant_matmul, quant_matmul_t)
from repro.models.layers import apply_rope, dense_init, linear, rms_norm

NEG_INF = -1e30


def _materialize(w):
    """fp view of a projection weight, for math that contracts the weight
    per-head instead of as a plain GEMM (MLA's absorbed decode).

    Historical note: this used to dequantize a packed ``wkv_b`` inside the
    jitted decode step — the one transient fp materialization left in the
    packed decode path.  Absorbed decode now contracts packed codes
    directly through the latent-layout kernels
    (``quant_matmul_t``/``quant_matmul`` over ``mla_latent_weights``
    views), so a ``PackedWeight`` reaching here means a dispatch bug: the
    zero-dequant serving contract (tests/test_serve_packed.py) counts any
    ``dequantize_packed`` call during generate as a failure."""
    if is_packed(w):  # pragma: no cover - guarded against by mla_decode
        assert w.w_packed.ndim == 2, w.w_packed.shape
        return dequantize_packed(w.w_packed, w.scale, w.zero,
                                 bits=w.bits, d_in=w.d_in)
    return w


def _repeat_kv(x: jax.Array, n_rep: int) -> jax.Array:
    """(B, T, KV, Dh) -> (B, T, KV*n_rep, Dh)."""
    if n_rep == 1:
        return x
    b, t, kv, dh = x.shape
    x = jnp.broadcast_to(x[:, :, :, None, :], (b, t, kv, n_rep, dh))
    return x.reshape(b, t, kv * n_rep, dh)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    kv_chunk: int = 512,
    q_offset: int = 0,
    colsum: bool = False,
):
    """Chunked attention.

    q: (B, Tq, H, Dh); k: (B, Tk, KV, Dh); v: (B, Tk, KV, Dv), H % KV == 0.
    Returns (B, Tq, H, Dv) and, when ``colsum`` is set, the per-token
    attention-concentration scores sum_{h,i} A[h, i, j] of shape (B, Tk)
    (the AttnCon importance of the paper, computed streamingly).
    """
    b, tq, h, dh = q.shape
    _, tk, kv_heads, _ = k.shape
    dv = v.shape[-1]
    n_rep = h // kv_heads
    kv_chunk = min(kv_chunk, tk)
    valid_tk = tk
    pad = (-tk) % kv_chunk
    if pad:  # ragged KV length (media/cross): pad + mask
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        tk = tk + pad
    n_chunks = tk // kv_chunk

    qf = q.astype(jnp.float32) * (dh ** -0.5)
    q_pos = q_offset + jnp.arange(tq)

    def body(carry, xs):
        m, l, acc = carry
        k_c, v_c, off = xs
        k_r = _repeat_kv(k_c, n_rep).astype(jnp.float32)
        v_r = _repeat_kv(v_c, n_rep).astype(jnp.float32)
        s = jnp.einsum("bthd,bchd->bthc", qf, k_r)  # (B, Tq, H, c)
        kv_pos = off + jnp.arange(kv_chunk)
        if causal:
            mask = (q_pos[:, None] >= kv_pos[None, :]) & (
                kv_pos < valid_tk)[None, :]  # (Tq, c)
            s = jnp.where(mask[None, :, None, :], s, NEG_INF)
        elif pad:
            s = jnp.where((kv_pos < valid_tk)[None, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum("bthc,bchd->bthd", p, v_r)
        # Column sums of the *normalized* probabilities require the final
        # (m, l); accumulate unnormalized stats + the per-chunk max instead.
        return (m_new, l_new, acc_new), (m_new, p if colsum else None)

    m0 = jnp.full((b, tq, h), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, tq, h), jnp.float32)
    a0 = jnp.zeros((b, tq, h, dv), jnp.float32)
    ks = k.reshape(b, n_chunks, kv_chunk, kv_heads, dh).swapaxes(0, 1)
    vs = v.reshape(b, n_chunks, kv_chunk, kv_heads, dv).swapaxes(0, 1)
    offs = jnp.arange(n_chunks) * kv_chunk

    if not colsum:
        (m, l, acc), _ = jax.lax.scan(
            jax.checkpoint(lambda c, x: body(c, x)), (m0, l0, a0), (ks, vs, offs)
        )
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return out.astype(q.dtype)

    # Capture path (calibration only; tiny models) — keeps per-chunk p.
    (m, l, acc), (ms, ps) = jax.lax.scan(body, (m0, l0, a0), (ks, vs, offs))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    # renormalize each chunk's p by exp(m_chunk - m_final)/l_final and
    # column-sum over (query, head): ps: (nc, B, Tq, H, c)
    scale = jnp.exp(ms - m[None]) / jnp.maximum(l[None], 1e-30)  # (nc,B,Tq,H)
    col = jnp.einsum("nbthc,nbth->nbc", ps, scale)  # (nc, B, c)
    col = col.swapaxes(0, 1).reshape(b, tk)[:, :valid_tk]
    return out.astype(q.dtype), col


def decode_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    pos: jax.Array,
) -> jax.Array:
    """Single-token attention against a (B, S, KV, Dh) cache; positions > pos
    are masked.  q: (B, 1, H, Dh) -> (B, 1, H, Dv).

    GQA-aware: the query is reshaped to (KV, G) groups and contracted
    against the cache directly — materializing a head-repeated (B, S, H, Dh)
    cache view (16x the cache for KV=8 -> H=128!) is exactly what makes
    long-context decode memory/collective-bound, and it breaks sequence
    sharding of the cache under SPMD."""
    import os
    b, _, h, dh = q.shape
    _, s, kv_heads, _ = k_cache.shape
    if os.environ.get("REPRO_BASELINE"):  # pre-optimization path (§Perf)
        k_r = _repeat_kv(k_cache, h // kv_heads)
        v_r = _repeat_kv(v_cache, h // kv_heads)
        qf = q.astype(jnp.float32) * (dh ** -0.5)
        scores = jnp.einsum("bthd,bshd->bths", qf, k_r.astype(jnp.float32))
        valid = jnp.arange(s)[None, None, None, :] <= pos
        scores = jnp.where(valid, scores, NEG_INF)
        p = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bths,bshd->bthd", p, v_r.astype(jnp.float32))
        return out.astype(q.dtype)
    g = h // kv_heads
    qf = (q.astype(jnp.float32) * (dh ** -0.5)).reshape(b, kv_heads, g, dh)
    # bf16 operands + fp32 accumulation (MXU-native): casting the cache to
    # f32 would write a 2x-sized copy of the entire KV cache per layer per
    # token — 3x the fundamental decode HBM traffic
    scores = jnp.einsum("bkgd,bskd->bkgs", qf.astype(k_cache.dtype), k_cache,
                        preferred_element_type=jnp.float32)
    valid = jnp.arange(s)[None, None, None, :] <= pos
    scores = jnp.where(valid, scores, NEG_INF)
    # streaming-stable softmax over the (possibly sequence-sharded) S axis
    m = jnp.max(scores, axis=-1, keepdims=True)
    p = jnp.exp(scores - m)
    denom = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bkgs,bskd->bkgd", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    out = out / jnp.maximum(denom, 1e-30)
    return out.reshape(b, 1, h, v_cache.shape[-1]).astype(q.dtype)


# ------------------------------------------------------- quantized KV cache


def kv_quantize(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(B, T, KV, Dh) -> int8 codes + per-(token, head) scales.

    Halves (vs bf16) the fundamental long-context decode HBM traffic — the
    whole cache is read per generated token (KVQuant/KIVI-style, symmetric
    per-token-per-head)."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, scale[..., 0].astype(jnp.bfloat16)


def kv_dequantize(q: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    """Full-tensor fp materialization of an int8 cache.

    Debug/test-only: the serving decode path consumes codes directly
    (``decode_attention_quantized``); the zero-dequant guard counts any
    call to this during generate as a failure."""
    return (q.astype(jnp.bfloat16) * scale[..., None].astype(jnp.bfloat16)
            ).astype(dtype)


# 2-bit log-distributed codes (LogQuant-style): value = scale * LEVELS[code].
# Codes 0..3 are sign x {outer, inner} log levels; one bf16 scale per
# (chunk-of-tokens, head) group; 16 codes packed per uint32 word along the
# feature axis.
KV_LOG_LEVELS = (-1.0, -0.25, 0.25, 1.0)


def kv_pack(codes: jax.Array) -> jax.Array:
    """Pack (..., D) 2-bit codes into (..., ceil(D/16)) uint32 words
    (code j of a word at bits [2j, 2j+2); ragged D zero-padded)."""
    d = codes.shape[-1]
    pad = (-d) % 16
    if pad:
        widths = [(0, 0)] * (codes.ndim - 1) + [(0, pad)]
        codes = jnp.pad(codes, widths)
    c = codes.astype(jnp.uint32).reshape(*codes.shape[:-1], -1, 16)
    shifts = jnp.arange(16, dtype=jnp.uint32) * 2
    return jnp.sum(c << shifts, axis=-1).astype(jnp.uint32)


def kv_unpack(words: jax.Array, d: int) -> jax.Array:
    """(..., ceil(D/16)) uint32 -> (..., D) int32 codes in 0..3."""
    shifts = jnp.arange(16, dtype=jnp.uint32) * 2
    c = ((words[..., None] >> shifts) & jnp.uint32(3)).astype(jnp.int32)
    return c.reshape(*words.shape[:-1], -1)[..., :d]


def kv_log_scales(x: jax.Array, chunk: int) -> jax.Array:
    """Per-(chunk, head) log scales: amax of |x| over each ``chunk``-token
    group and the feature axis.  x: (B, T, ..., D) -> (B, ceil(T/chunk), ...)
    bf16 (ragged T zero-padded — padded rows never decode)."""
    xf = jnp.abs(x.astype(jnp.float32))
    b, t = x.shape[:2]
    pad = (-t) % chunk
    if pad:
        widths = [(0, 0), (0, pad)] + [(0, 0)] * (xf.ndim - 2)
        xf = jnp.pad(xf, widths)
    xf = xf.reshape(b, -1, chunk, *x.shape[2:])
    amax = jnp.max(xf, axis=(2, -1))
    return jnp.maximum(amax, 1e-8).astype(jnp.bfloat16)


def _kv_log_codes(xf: jax.Array, scale: jax.Array) -> jax.Array:
    """Encode f32 values against a per-(token, head) scale (shape
    ``xf.shape[:-1]``): |x|/scale > 0.5 picks the outer level, sign picks
    the half — code = 2 + magcode for x >= 0, 1 - magcode otherwise.
    Values beyond the scale clip to the outer level (the chunk-leader
    rule: decode-appended tokens reuse their chunk's first-token scale)."""
    s = jnp.maximum(scale.astype(jnp.float32), 1e-8)[..., None]
    magcode = (jnp.abs(xf) / s > 0.5).astype(jnp.int32)
    return jnp.where(xf >= 0, 2 + magcode, 1 - magcode)


def kv_log_encode(x: jax.Array, scales: jax.Array, chunk: int) -> jax.Array:
    """x: (B, T, ..., D) + per-chunk scales -> (B, T, ..., ceil(D/16))
    packed uint32 codes."""
    t = x.shape[1]
    s_tok = jnp.repeat(scales, chunk, axis=1)[:, :t]
    return kv_pack(_kv_log_codes(x.astype(jnp.float32), s_tok))


def kv_log_decode(packed: jax.Array, scales: jax.Array, *, d: int,
                  chunk: int, dtype=jnp.float32) -> jax.Array:
    """Full-tensor fp materialization of a 2-bit cache — debug/test-only,
    same guard contract as ``kv_dequantize``."""
    c = kv_unpack(packed, d)
    t = packed.shape[1]
    s_tok = jnp.repeat(scales.astype(jnp.float32), chunk, axis=1)[:, :t]
    lut = jnp.array(KV_LOG_LEVELS, jnp.float32)
    return (lut[c] * s_tok[..., None]).astype(dtype)


# --------------------------------------------------------------- KV codecs
#
# One protocol for every KV-cache representation.  A codec owns three
# things so the flat (B, S, ...) cache, the paged (n_pages, page, ...)
# pools and the kernels can never drift on layout or rounding:
#
#   * ``encode``       — prefill-length tensor -> (codes, scales)
#   * ``encode_token`` / ``append`` — one-token quantize (+ the kv2
#     chunk-leader scale rule) and its flat-cache write
#   * layout           — ``round_len`` (cache-length alignment, the old
#     ``models.lm._cache_len``), ``code_cols``/``code_dtype``/
#     ``scale_rows``/``scale_dtype`` (allocation shapes) and
#     ``page_tokens`` (the paged-cache page size: one ``align`` group of
#     tokens, so 2-bit scale groups never straddle pages)
#
# The legacy free functions (``kv_cache_quantize`` / ``kv_cache_update``)
# survive as thin wrappers — call sites and tests keep working — but the
# logic lives here once.


@dataclasses.dataclass(frozen=True)
class FpCodec:
    """KV cache held in the activation dtype — no codes, no scales."""

    kv_bits: int = 0
    chunk: int = 1  # tokens per scale row (no scales: nominal)
    align: int = 1  # cache-length alignment unit
    quantized: bool = False

    def round_len(self, s: int) -> int:
        return s

    def scale_rows(self, s: int) -> int:
        return 0


@dataclasses.dataclass(frozen=True)
class Kv8Codec:
    """int8 codes + per-(token, head) bf16 scales (``kv_quantize``)."""

    align: int  # cfg.kv_chunk — tile/page alignment even though chunk=1
    kv_bits: int = 8
    chunk: int = 1
    quantized: bool = True
    code_dtype = jnp.int8
    scale_dtype = jnp.bfloat16

    def round_len(self, s: int) -> int:
        return -(-s // self.align) * self.align

    def scale_rows(self, s: int) -> int:
        return s // self.chunk

    def code_cols(self, d: int) -> int:
        return d

    @property
    def page_tokens(self) -> int:
        return self.align

    def encode(self, x):
        return kv_quantize(x)

    def encode_token(self, x, pos, cur_scale):
        """One token (B, 1, ..., D) -> (codes, scale row); ``pos`` and the
        current scale are irrelevant at per-token granularity."""
        del pos, cur_scale
        return kv_quantize(x)

    def append(self, codes, scales, x, pos):
        q, sc = self.encode_token(x, pos, None)
        codes = jax.lax.dynamic_update_slice_in_dim(codes, q, pos, 1)
        scales = jax.lax.dynamic_update_slice_in_dim(scales, sc, pos, 1)
        return codes, scales


@dataclasses.dataclass(frozen=True)
class Kv2Codec:
    """Packed LogQuant-style 2-bit codes + per-(chunk, head) log scales.

    Chunk-leader rule: the token at a chunk boundary stamps the chunk's
    scale from its own amax; later tokens in the chunk reuse it (their
    overflow clips to the outer log level).  Revisiting the scale would
    re-code earlier tokens — a full-cache rewrite per step, exactly the
    traffic this cache layout removes."""

    align: int  # cfg.kv_chunk == scale-group size == page size
    kv_bits: int = 2
    quantized: bool = True
    code_dtype = jnp.uint32
    scale_dtype = jnp.bfloat16

    @property
    def chunk(self) -> int:
        return self.align

    def round_len(self, s: int) -> int:
        return -(-s // self.align) * self.align

    def scale_rows(self, s: int) -> int:
        return s // self.chunk

    def code_cols(self, d: int) -> int:
        return -(-d // 16)

    @property
    def page_tokens(self) -> int:
        return self.align

    def encode(self, x):
        scales = kv_log_scales(x, self.chunk)
        return kv_log_encode(x, scales, self.chunk), scales

    def encode_token(self, x, pos, cur_scale):
        """One token (B, 1, ..., D) against the current scale of its chunk
        (shape (B, 1, ...)); ``pos`` may be a scalar (flat cache, shared
        across the batch) or per-slot (B,) (paged cache)."""
        xf = x.astype(jnp.float32)
        amax = jnp.max(jnp.abs(xf), axis=-1)
        lead = jnp.maximum(amax, 1e-8).astype(cur_scale.dtype)
        stamp = jnp.reshape(pos % self.chunk == 0,
                            (-1,) + (1,) * (cur_scale.ndim - 1))
        sc = jnp.where(stamp, lead, cur_scale)
        return kv_pack(_kv_log_codes(xf, sc)), sc

    def append(self, codes, scales, x, pos):
        ci = pos // self.chunk
        cur = jax.lax.dynamic_slice_in_dim(scales, ci, 1, 1)
        tok, sc = self.encode_token(x, pos, cur)
        codes = jax.lax.dynamic_update_slice_in_dim(codes, tok, pos, 1)
        scales = jax.lax.dynamic_update_slice_in_dim(scales, sc, ci, 1)
        return codes, scales


@functools.lru_cache(maxsize=None)
def kv_codec(kv_bits: int = 0, kv_chunk: int = 64):
    """The codec for a (kv_bits, kv_chunk) cache config — cached so every
    call site shares one instance per config."""
    if kv_bits == 0:
        return FpCodec()
    if kv_bits == 8:
        return Kv8Codec(align=kv_chunk)
    if kv_bits == 2:
        return Kv2Codec(align=kv_chunk)
    raise ValueError(
        f"kv_bits={kv_bits} is not supported — use 0 (KV cache in the "
        "activation dtype), 8 (int8 codes + per-token-head scales) or 2 "
        "(packed log codes + per-chunk scales)")


def kv_cache_quantize(x: jax.Array, *, kv_bits: int,
                      chunk: int = 1) -> tuple[jax.Array, jax.Array]:
    """Quantize a prefill-length KV tensor into (codes, scales) as stored
    in the cache — thin wrapper over :func:`kv_codec`'s ``encode``."""
    return kv_codec(kv_bits, chunk if kv_bits == 2 else 64).encode(x)


def kv_cache_update(codes: jax.Array, scales: jax.Array, x: jax.Array,
                    pos: jax.Array, *, kv_bits: int,
                    chunk: int = 1) -> tuple[jax.Array, jax.Array]:
    """Quantize one new token x: (B, 1, ..., D) and write it into the
    (codes, scales) cache at ``pos`` — thin wrapper over :func:`kv_codec`'s
    ``append``; the decode append never leaves the quantized domain."""
    return kv_codec(kv_bits, chunk if kv_bits == 2 else 64).append(
        codes, scales, x, pos)


def _fd_mesh_args(ctx, batch: int) -> dict:
    """ParallelCtx -> flash_decode mesh kwargs: split the cache sequence
    axis over the model axis; include the data axes in the specs only when
    the batch actually divides over them (else GSPMD would have to
    re-gather the dp-sharded cache batch into the shard_map)."""
    if ctx is None or not getattr(ctx, "enabled", False) or ctx.tp is None:
        return {"mesh": None, "axis": None, "dp": None}
    dp = None
    if ctx.dp and ctx.axis_size("dp") > 1 and batch % ctx.axis_size("dp") == 0:
        dp = ctx.dp if len(ctx.dp) != 1 else ctx.dp[0]
    return {"mesh": ctx.mesh, "axis": ctx.tp, "dp": dp}


def decode_attention_quantized(q: jax.Array, k_codes: jax.Array,
                               k_scales: jax.Array, v_codes: jax.Array,
                               v_scales: jax.Array, pos: jax.Array, *,
                               kv_bits: int, chunk: int = 1,
                               ctx=None) -> jax.Array:
    """Single-token attention directly against the quantized cache.

    q: (B, 1, H, Dh); codes/scales as stored by ``kv_cache_update``.
    Same GQA contraction discipline as ``decode_attention`` ((KV, G)
    groups, never a head-repeated cache) but the cache stays codes all the
    way into the kernel tile — no fp copy of any size S tensor."""
    b, _, h, dh = q.shape
    kv_heads = k_codes.shape[2]
    g = h // kv_heads
    qf = (q.astype(jnp.float32) * (dh ** -0.5)).reshape(b, kv_heads, g, dh)
    out = flash_decode(qf, k_codes, k_scales, v_codes, v_scales, pos,
                       kv_bits=kv_bits, chunk=chunk, dv=dh,
                       **_fd_mesh_args(ctx, b))
    return out.reshape(b, 1, h, dh).astype(q.dtype)


def paged_decode_attention_quantized(q: jax.Array, k_pool: jax.Array,
                                     ks_pool: jax.Array, v_pool: jax.Array,
                                     vs_pool: jax.Array, page_tbl: jax.Array,
                                     pos: jax.Array, *, kv_bits: int,
                                     chunk: int = 1) -> jax.Array:
    """Single-token GQA attention against block-paged quantized pools.

    q: (B, 1, H, Dh) — one slot per engine request; k_pool/v_pool:
    (n_pages, page, KV, w·) code pools, ks_pool/vs_pool:
    (n_pages, page // chunk, KV) scale pools; page_tbl: (B, n_tiles) i32
    per-slot page table (trash page 0 in unused entries); pos: (B,) i32
    per-slot positions, -1 for a dead slot (its row comes back 0).  Same
    scale folding and (KV, G) grouping as
    :func:`decode_attention_quantized`, so paged == flat stays bitwise at
    a matched tile size (tile = page).  The kernel walks only the live
    (slot, page) pairs — tiles ``0 .. pos // page`` of each live slot and
    one empty item per dead slot — in a work list of static length
    ``min(B * n_tiles, n_pages + B)``, which holds every item as long as
    no page is shared and dead slots pass -1."""
    b, _, h, dh = q.shape
    kv_heads = k_pool.shape[2]
    g = h // kv_heads
    qf = (q.astype(jnp.float32) * (dh ** -0.5)).reshape(b, kv_heads, g, dh)
    out = paged_flash_decode(page_tbl, pos, qf, k_pool, ks_pool, v_pool,
                             vs_pool, kv_bits=kv_bits, chunk=chunk, dv=dh,
                             page=k_pool.shape[1])
    return out.reshape(b, 1, h, dh).astype(q.dtype)


def kv_paged_append(codec, c_pool, s_pool, x, page_ids, pos, active):
    """Quantize one new token per slot and scatter it into paged pools.

    x: (B, 1, ..., D) new cache entries (time axis 1, same layout as the
    flat ``append``); page_ids: (B,) i32 — the physical page holding each
    slot's current tile; pos: (B,) i32 global positions; active: (B,)
    bool.  Inactive slots are routed to the reserved trash page 0, so a
    fixed-shape scatter needs no masking and never perturbs live pages.
    The per-token quantization math is the codec's ``encode_token`` — the
    same routine the flat cache appends with — so paged and flat caches
    hold bit-identical codes for the same token stream."""
    page = c_pool.shape[1]
    row = (pos % page).astype(jnp.int32)
    srow = row // codec.chunk
    pid = jnp.where(active, page_ids, 0).astype(jnp.int32)
    cur = s_pool[pid, srow][:, None]          # (B, 1, ...) current scales
    tok, sc = codec.encode_token(x, pos, cur)
    c_pool = c_pool.at[pid, row].set(tok[:, 0])
    s_pool = s_pool.at[pid, srow].set(sc[:, 0])
    return c_pool, s_pool


# ------------------------------------------------------------------ GQA block


def init_gqa(key, cfg, dtype):
    d, h, kvh, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    keys = jax.random.split(key, 4)
    p = {
        "wq": dense_init(keys[0], d, h * dh, dtype),
        "wk": dense_init(keys[1], d, kvh * dh, dtype),
        "wv": dense_init(keys[2], d, kvh * dh, dtype),
        "wo": dense_init(keys[3], h * dh, d, dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * dh,), dtype)
        p["bk"] = jnp.zeros((kvh * dh,), dtype)
        p["bv"] = jnp.zeros((kvh * dh,), dtype)
    return p


def gqa_qkv(p, cfg, x, positions, *, rope: bool = True):
    b, t, _ = x.shape
    h, kvh, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = linear(x, p["wq"]) + (p["bq"] if "bq" in p else 0.0)
    k = linear(x, p["wk"]) + (p["bk"] if "bk" in p else 0.0)
    v = linear(x, p["wv"]) + (p["bv"] if "bv" in p else 0.0)
    q = q.reshape(b, t, h, dh)
    k = k.reshape(b, t, kvh, dh)
    v = v.reshape(b, t, kvh, dh)
    if rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def apply_gqa(p, cfg, x, positions, *, causal=True, kv_chunk=512, colsum=False):
    b, t, _ = x.shape
    q, k, v = gqa_qkv(p, cfg, x, positions)
    res = flash_attention(q, k, v, causal=causal, kv_chunk=min(kv_chunk, t),
                          colsum=colsum)
    if colsum:
        out, col = res
    else:
        out, col = res, None
    y = linear(out.reshape(b, t, -1), p["wo"])
    return (y, col) if colsum else y


# ------------------------------------------------------------------ MLA block


def init_mla(key, cfg, dtype):
    d = cfg.d_model
    h = cfg.n_heads
    qr, kvr = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    keys = jax.random.split(key, 6)
    p = {}
    if qr:
        p["wq_a"] = dense_init(keys[0], d, qr, dtype)
        p["q_norm"] = jnp.ones((qr,), dtype)
        p["wq_b"] = dense_init(keys[1], qr, h * (dn + dr), dtype)
    else:
        p["wq"] = dense_init(keys[1], d, h * (dn + dr), dtype)
    p["wkv_a"] = dense_init(keys[2], d, kvr + dr, dtype)
    p["kv_norm"] = jnp.ones((kvr,), dtype)
    p["wkv_b"] = dense_init(keys[3], kvr, h * (dn + dv), dtype)
    p["wo"] = dense_init(keys[4], h * dv, d, dtype)
    return p


def mla_qkv(p, cfg, x, positions):
    """Returns expanded per-head q, k, v plus the latent cache entries."""
    b, t, _ = x.shape
    h = cfg.n_heads
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    kvr = cfg.kv_lora_rank
    if "wq_a" in p:
        ql = rms_norm(linear(x, p["wq_a"]), p["q_norm"], cfg.norm_eps)
        q = linear(ql, p["wq_b"]).reshape(b, t, h, dn + dr)
    else:
        q = linear(x, p["wq"]).reshape(b, t, h, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)

    kv = linear(x, p["wkv_a"])  # (B, T, kvr + dr)
    c_kv = rms_norm(kv[..., :kvr], p["kv_norm"], cfg.norm_eps)
    k_rope = apply_rope(kv[..., None, kvr:], positions, cfg.rope_theta)  # 1 head
    kvb = linear(c_kv, p["wkv_b"]).reshape(b, t, h, dn + dv)
    k_nope, v = kvb[..., :dn], kvb[..., dn:]
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (b, t, h, dr))], axis=-1
    )
    return q, k, v, c_kv, k_rope[..., 0, :]


def apply_mla(p, cfg, x, positions, *, causal=True, kv_chunk=512, colsum=False):
    b, t, _ = x.shape
    q, k, v, _, _ = mla_qkv(p, cfg, x, positions)
    res = flash_attention(q, k, v, causal=causal, kv_chunk=min(kv_chunk, t),
                          colsum=colsum)
    if colsum:
        out, col = res
    else:
        out, col = res, None
    y = linear(out.reshape(b, t, -1), p["wo"])
    return (y, col) if colsum else y


def _mla_q_and_expand(p, cfg, x, positions):
    """Absorbed-MLA query projection shared by the flat and paged decode
    paths: latent/rope queries plus the W_v expansion closure.

    Pure code motion out of :func:`mla_decode` — both paths run the exact
    same ops here, so per-request results stay bitwise identical between
    the flat cache and the paged engine.  ``positions`` is whatever
    ``apply_rope`` broadcasts against (..., T, ...): ``pos[None]`` on the
    flat path, per-slot ``pos[:, None]`` on the paged path, a chunk's
    ``start + arange(L)`` on the extend path (T = L rows)."""
    b, t, _ = x.shape
    h = cfg.n_heads
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    kvr = cfg.kv_lora_rank
    if "wq_a" in p:
        ql = rms_norm(linear(x, p["wq_a"]), p["q_norm"], cfg.norm_eps)
        q = linear(ql, p["wq_b"]).reshape(b, t, h, dn + dr)
    else:
        q = linear(x, p["wq"]).reshape(b, t, h, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    if is_packed(p["wkv_b"]):
        pw_k, pw_v = mla_latent_weights(p["wkv_b"], h, dn, dv)

        def absorb_k(qn):  # (B, T, H, dn) -> (B, T, H, kvr)
            bt = qn.shape[0] * qn.shape[1]
            qh = qn.astype(jnp.float32).reshape(bt, h, dn)
            qh = qh.transpose(1, 0, 2)  # (H, B*T, dn)
            lat = jax.vmap(quant_matmul_t)(qh, pw_k)  # (H, B*T, kvr)
            return lat.transpose(1, 0, 2).reshape(qn.shape[0], qn.shape[1],
                                                  h, kvr)

        def expand_v(cl):  # (B, T, H, kvr) -> (B, T, H, dv)
            b_, t_ = cl.shape[0], cl.shape[1]
            ch = cl.reshape(b_ * t_, h, kvr).transpose(1, 0, 2)
            out = jax.vmap(functools.partial(quant_matmul, shard=False))(
                ch, pw_v)
            return out.transpose(1, 0, 2).reshape(b_, t_, h, dv)
    else:
        wkv_b = _materialize(p["wkv_b"]).reshape(kvr, h, dn + dv)
        w_k, w_v = wkv_b[..., :dn], wkv_b[..., dn:]

        def absorb_k(qn):  # (B,1,H,dn) x (kvr,H,dn) -> (B,1,H,kvr)
            return jnp.einsum("bthd,khd->bthk", qn.astype(jnp.float32),
                              w_k.astype(jnp.float32))

        def expand_v(cl):
            return jnp.einsum("bthk,khd->bthd", cl, w_v.astype(jnp.float32))

    return absorb_k(q_nope), q_rope, expand_v


def mla_decode(p, cfg, x, c_cache, rope_cache, pos, *, c_scale=None,
               r_scale=None, kv_bits: int = 0, chunk: int = 1, ctx=None):
    """Latent-space ("absorbed") MLA decode: the KV cache stores only the
    compressed c_kv (kvr) + shared rope key (dr) per token.

    x: (B, 1, D); c_cache: (B, S, kvr); rope_cache: (B, S, dr).

    With ``kv_bits`` in {8, 2} the caches are codes (+ ``c_scale`` /
    ``r_scale``) and the latent attention runs through
    ``mla_flash_decode`` — MLA's absorbed decode is 1-kv-head attention in
    latent space (scores q_lat.c + q_rope.r, values the latents), so the
    quantized path consumes the c and r codes as separate operands and
    never materializes the latent cache (or a concat of it) in fp.

    The absorbed trick contracts ``wkv_b`` per-head (two contractions
    against the latent cache) rather than as one GEMM.  A packed
    ``wkv_b`` stays packed even here: the per-head column views
    (``mla_latent_weights`` — exact slices of the codes, packing runs
    along kvr) feed ``quant_matmul_t`` (absorb W_k into q: contraction
    over dn, output in latent space) and ``quant_matmul`` (expand the
    latent attention output through W_v), so the last per-step fp weight
    materialization of the decode path is gone — no
    ``dequantize_packed`` anywhere in the step trace; the q and output
    projections stay on ``quant_matmul`` via ``linear`` as before."""
    b, _, _ = x.shape
    h = cfg.n_heads
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    kvr = cfg.kv_lora_rank
    q_lat, q_rope, expand_v = _mla_q_and_expand(p, cfg, x, pos[None])
    scale = (dn + dr) ** -0.5
    if kv_bits in (8, 2):
        # quantized latent cache: fold the scale into the queries, attend
        # on codes, normalize once in the wrapper
        ql = (q_lat.astype(jnp.float32) * scale)[:, 0]
        qr = (q_rope.astype(jnp.float32) * scale)[:, 0]
        ctx_lat = mla_flash_decode(
            ql, qr, c_cache, c_scale, rope_cache, r_scale, pos,
            kv_bits=kv_bits, chunk=chunk, dl=kvr, dr=dr,
            **_fd_mesh_args(ctx, b))[:, None]  # (B, 1, H, kvr)
        y = linear(expand_v(ctx_lat).reshape(b, 1, h * dv).astype(x.dtype),
                   p["wo"])
        return y
    s_lat = jnp.einsum("bthk,bsk->bths", q_lat, c_cache.astype(jnp.float32))
    s_rope = jnp.einsum("bthd,bsd->bths", q_rope.astype(jnp.float32),
                        rope_cache.astype(jnp.float32))
    scores = (s_lat + s_rope) * scale
    s = c_cache.shape[1]
    valid = jnp.arange(s)[None, None, None, :] <= pos
    scores = jnp.where(valid, scores, NEG_INF)
    prob = jax.nn.softmax(scores, axis=-1)
    ctx_lat = jnp.einsum("bths,bsk->bthk", prob, c_cache.astype(jnp.float32))
    ctx = expand_v(ctx_lat)
    y = linear(ctx.reshape(b, 1, h * dv).astype(x.dtype), p["wo"])
    return y


def mla_decode_paged(p, cfg, x, c_pool, cs_pool, r_pool, rs_pool, page_tbl,
                     pos, *, kv_bits: int, chunk: int):
    """Absorbed MLA decode against block-paged quantized latent pools.

    x: (B, 1, D) — one slot per engine request; c_pool/r_pool:
    (n_pages, page, w·) latent/rope code pools, cs_pool/rs_pool:
    (n_pages, page // chunk) scale pools; page_tbl: (B, n_tiles) i32;
    pos: (B,) i32 per-slot positions.  Query math is shared with
    :func:`mla_decode` via :func:`_mla_q_and_expand` and the tile loop
    with :func:`paged_mla_flash_decode`, so paged == flat per request."""
    b, _, _ = x.shape
    h = cfg.n_heads
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    kvr = cfg.kv_lora_rank
    q_lat, q_rope, expand_v = _mla_q_and_expand(p, cfg, x, pos[:, None])
    scale = (dn + dr) ** -0.5
    ql = (q_lat.astype(jnp.float32) * scale)[:, 0]
    qr = (q_rope.astype(jnp.float32) * scale)[:, 0]
    ctx_lat = paged_mla_flash_decode(
        page_tbl, pos, ql, qr, c_pool, cs_pool, r_pool, rs_pool,
        kv_bits=kv_bits, chunk=chunk, dl=kvr, dr=dr,
        page=c_pool.shape[1])[:, None]          # (B, 1, H, kvr)
    return linear(expand_v(ctx_lat).reshape(b, 1, h * dv).astype(x.dtype),
                  p["wo"])


def paged_extend_attention_quantized(q, k_new, v_new, k_pool, ks_pool,
                                     v_pool, vs_pool, tbl, start, *,
                                     kv_bits: int, chunk: int):
    """One prompt chunk's GQA attention against the request's own quantized
    pages plus the fp within-chunk rows (opt-in "paged" chunked prefill).

    q: (1, L, H, Dh); k_new/v_new: (1, L, KV, Dh) this chunk's fp keys and
    values; tbl: (n_past,) i32 — the pages holding the already-ingested
    chunks (earlier rows are read back as codes, dequantized in-register by
    the extend kernel, so this route is HBM-cheap but *lossy* versus the
    flat prefill); start: () i32 page-aligned chunk offset."""
    out = paged_flash_extend(tbl, q, k_new, v_new, k_pool, ks_pool, v_pool,
                             vs_pool, start, kv_bits=kv_bits, chunk=chunk,
                             dh=q.shape[-1], dv=v_new.shape[-1],
                             page=k_pool.shape[1])
    return out.astype(q.dtype)


def mla_extend_paged(p, cfg, x, c_new, r_new, pools, tbl, start, positions, *,
                     kv_bits: int, chunk: int):
    """One prompt chunk's absorbed MLA attention against quantized latent
    pages plus the chunk's fp latents (opt-in "paged" chunked prefill).

    x: (1, L, D) chunk rows; c_new/r_new: (1, L, kvr)/(1, L, dr) this
    chunk's fp latent/rope cache rows; tbl: (n_past,) i32 pages of the
    already-ingested chunks.  Queries come through the same
    :func:`_mla_q_and_expand` absorption as decode (generalized to L rows),
    so the chunk attends in latent space end to end."""
    b, t, _ = x.shape
    h = cfg.n_heads
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    kvr = cfg.kv_lora_rank
    q_lat, q_rope, expand_v = _mla_q_and_expand(p, cfg, x, positions)
    scale = (dn + dr) ** -0.5
    ql = (q_lat.astype(jnp.float32) * scale)[0]    # (L, H, kvr)
    qr = (q_rope.astype(jnp.float32) * scale)[0]   # (L, H, dr)
    ctx_lat = paged_mla_flash_extend(
        tbl, ql, qr, c_new[0].astype(jnp.float32),
        r_new[0].astype(jnp.float32), pools["c"], pools["cs"], pools["r"],
        pools["rs"], start, kv_bits=kv_bits, chunk=chunk, dl=kvr, dr=dr,
        page=pools["c"].shape[1])[None]             # (1, L, H, kvr)
    return linear(expand_v(ctx_lat).reshape(b, t, h * dv).astype(x.dtype),
                  p["wo"])


# ------------------------------------------------------------- cross-attention


def init_cross_attn(key, cfg, dtype):
    """Cross-attention (VLM media layers / enc-dec): queries from the decoder
    stream, keys/values from (stub) media or encoder output at d_model."""
    d, h, kvh, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    keys = jax.random.split(key, 4)
    return {
        "wq": dense_init(keys[0], d, h * dh, dtype),
        "wk": dense_init(keys[1], d, kvh * dh, dtype),
        "wv": dense_init(keys[2], d, kvh * dh, dtype),
        "wo": dense_init(keys[3], h * dh, d, dtype),
    }


def cross_kv(p, cfg, media):
    b, tm, _ = media.shape
    kvh, dh = cfg.n_kv_heads, cfg.head_dim
    k = linear(media, p["wk"]).reshape(b, tm, kvh, dh)
    v = linear(media, p["wv"]).reshape(b, tm, kvh, dh)
    return k, v


def apply_cross_attn(p, cfg, x, media=None, kv=None, kv_chunk=512):
    """media: (B, Tm, D) stub embeddings; or precomputed kv (decode path)."""
    b, t, _ = x.shape
    h, dh = cfg.n_heads, cfg.head_dim
    q = linear(x, p["wq"]).reshape(b, t, h, dh)
    if kv is None:
        kv = cross_kv(p, cfg, media)
    k, v = kv
    out = flash_attention(q, k, v, causal=False,
                          kv_chunk=min(kv_chunk, k.shape[1]))
    return linear(out.reshape(b, t, -1), p["wo"])
