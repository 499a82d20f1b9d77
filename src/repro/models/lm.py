"""Unified decoder-LM assembly for every assigned architecture family.

A model is a stack of *blocks*; each block = mixer (attn | MLA | Mamba |
cross-attn) + optional cross-attention sub-layer (enc-dec decoders) +
optional FFN (dense | MoE).  Layers are scanned in *groups* of
``cfg.scan_period`` blocks so heterogeneous patterns (Jamba 1:7, VLM every
5th cross) still lower to one compact ``lax.scan`` — O(1) HLO in depth.

Three entry points per model, matching the dry-run cells:
  * ``loss``         — training forward + chunked CE (train_4k)
  * ``prefill``      — forward returning last-token logits + KV/state cache
  * ``decode_step``  — one token against the cache (decode_32k / long_500k)
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as att
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models.layers import (
    apply_dense_ffn,
    embed_lookup,
    capture_dense_ffn,
    cross_entropy_chunked,
    dense_init,
    init_dense_ffn,
    init_embedding,
    linear,
    rms_norm,
)
from repro.runtime.sharding import LOCAL, ParallelCtx, param_specs

from jax.sharding import PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class BlockMeta:
    mixer: str  # "attn" | "mla" | "mamba" | "cross"
    ffn: str  # "dense" | "moe" | "none"
    has_cross: bool = False  # enc-dec decoder blocks
    causal: bool = True


def decoder_metas(cfg: ModelConfig) -> tuple[BlockMeta, ...]:
    metas = []
    for kind, ffn in zip(cfg.layer_kinds(), cfg.ffn_kinds()):
        mixer = kind
        if kind == "attn" and cfg.attn_kind == "mla":
            mixer = "mla"
        metas.append(
            BlockMeta(mixer=mixer, ffn=ffn, has_cross=(cfg.family == "encdec"))
        )
    return tuple(metas)


def encoder_metas(cfg: ModelConfig) -> tuple[BlockMeta, ...]:
    return tuple(
        BlockMeta(mixer="attn", ffn="dense", causal=False)
        for _ in range(cfg.n_encoder_layers)
    )


# ------------------------------------------------------------------- blocks


def init_block(key, cfg: ModelConfig, meta: BlockMeta, dtype):
    keys = jax.random.split(key, 4)
    d = cfg.d_model
    p: dict[str, Any] = {"mixer_norm": jnp.ones((d,), dtype)}
    if meta.mixer == "attn":
        p["mixer"] = att.init_gqa(keys[0], cfg, dtype)
    elif meta.mixer == "mla":
        p["mixer"] = att.init_mla(keys[0], cfg, dtype)
    elif meta.mixer == "mamba":
        p["mixer"] = ssm_lib.init_mamba(keys[0], cfg, dtype)
    elif meta.mixer == "cross":
        p["mixer"] = att.init_cross_attn(keys[0], cfg, dtype)
    else:
        raise ValueError(meta.mixer)
    if meta.has_cross:
        p["cross_norm"] = jnp.ones((d,), dtype)
        p["cross"] = att.init_cross_attn(keys[1], cfg, dtype)
    if meta.ffn == "dense":
        p["ffn_norm"] = jnp.ones((d,), dtype)
        p["ffn"] = init_dense_ffn(keys[2], cfg.d_model, cfg.d_ff, dtype)
    elif meta.ffn == "moe":
        p["ffn_norm"] = jnp.ones((d,), dtype)
        p["ffn"] = moe_lib.init_moe(keys[2], cfg, dtype)
    return p


def _routed_moe(p_ffn, cfg, h, ctx: ParallelCtx):
    """Routed-expert part, through shard_map EP when enabled."""
    routed = {"router": p_ffn["router"], "experts": p_ffn["experts"]}
    if ctx.enabled and ctx.ep and ctx.tp is not None:
        import math
        dp_size = math.prod(ctx.mesh.shape[a] for a in ctx.dp) if ctx.dp else 1
        # decode with tiny batches: tokens replicated over the data axes
        dp_ok = ctx.dp and h.shape[0] % dp_size == 0
        dp_entry = (ctx.dp if len(ctx.dp) != 1 else ctx.dp[0]) if dp_ok else None
        pspecs = {
            "router": P(None, None),
            "experts": {
                "wi": P(ctx.tp, None, None),
                "wu": P(ctx.tp, None, None),
                "wd": P(ctx.tp, None, None),
            },
        }
        act = P(dp_entry, None, None)

        def fn(pm, xx):
            y, aux = moe_lib.apply_moe(pm, cfg, xx, axis=ctx.tp)
            return y, jax.lax.pmean(aux, ctx.dp)

        y, aux = jax.shard_map(
            fn,
            mesh=ctx.mesh,
            in_specs=(pspecs, act),
            out_specs=(act, P()),
            check_vma=False,
        )(routed, h)
        return y, aux
    return moe_lib.apply_moe(routed, cfg, h, axis=None)


def _gather_seq(x, ctx: ParallelCtx):
    """Gather the sequence-sharded residual to full T once per sub-layer.

    §Perf iteration (train cells): with a seq-sharded x entering the
    matmuls, GSPMD replicates the *weights* over every mesh axis (full
    1.6 GB f32 all-gathers per use at command-r-plus scale); gathering the
    (much smaller) activation instead lets weights gather over the data
    axes only — the standard Megatron sequence-parallel schedule."""
    import os
    if os.environ.get("REPRO_BASELINE") or not ctx.enabled:
        return x
    if x.shape[1] == 1:  # decode: handled by constrain_act
        return x
    return ctx.constrain(x, "dp", None, None)


def apply_block(p, cfg, meta: BlockMeta, x, *, positions=None, media=None,
                ctx: ParallelCtx = LOCAL):
    """Full-sequence forward (train / prefill). Returns (x, aux, cache).

    ``positions=None`` defaults to ``arange(T)`` — lets callers that jit
    over varying sequence lengths (the calibration engine's per-meta trace
    cache) derive positions inside the trace instead of threading them."""
    if positions is None:
        positions = jnp.arange(x.shape[1])
    aux = jnp.zeros((), jnp.float32)
    # attention input stays sequence-sharded (QKV weights are the small
    # ones); only the FFN gathers full-T activations — see _gather_seq
    h = rms_norm(x, p["mixer_norm"], cfg.norm_eps)
    cache = {}
    codec = att.kv_codec(cfg.kv_bits, cfg.kv_chunk)
    if meta.mixer == "attn":
        b, t, _ = h.shape
        q, k, v = att.gqa_qkv(p["mixer"], cfg, h, positions)
        out = att.flash_attention(q, k, v, causal=meta.causal,
                                  kv_chunk=min(512, t))
        mix = linear(out.reshape(b, t, -1), p["mixer"]["wo"])
        if codec.quantized:
            # prefill writes the cache already quantized — decode appends
            # stay quantized too, so codes+scales is the *only* cache
            # representation end-to-end (training/calib forwards discard
            # the cache and XLA dead-code-eliminates the quantize)
            kq, ks = codec.encode(k)
            vq, vs = codec.encode(v)
            cache = {"k": kq, "ks": ks, "v": vq, "vs": vs}
        else:
            cache = {"k": k, "v": v}
    elif meta.mixer == "mla":
        b, t, _ = h.shape
        q, k, v, c_kv, k_rope = att.mla_qkv(p["mixer"], cfg, h, positions)
        out = att.flash_attention(q, k, v, causal=meta.causal,
                                  kv_chunk=min(512, t))
        mix = linear(out.reshape(b, t, -1), p["mixer"]["wo"])
        if codec.quantized:
            cq, cs = codec.encode(c_kv)
            rq, rs = codec.encode(k_rope)
            cache = {"c": cq, "cs": cs, "r": rq, "rs": rs}
        else:
            cache = {"c": c_kv, "r": k_rope}
    elif meta.mixer == "mamba":
        mix, (conv_s, ssm_s) = ssm_lib.apply_mamba(p["mixer"], cfg, h,
                                                   return_state=True)
        cache = {"conv": conv_s, "ssm": ssm_s}
    elif meta.mixer == "cross":
        mix = att.apply_cross_attn(p["mixer"], cfg, h, media=media)
        cache = {"kv": att.cross_kv(p["mixer"], cfg, media)}
    x = ctx.constrain_act(x + mix)

    if meta.has_cross:
        h = rms_norm(_gather_seq(x, ctx), p["cross_norm"], cfg.norm_eps)
        x = x + att.apply_cross_attn(p["cross"], cfg, h, media=media)
        cache["cross_kv"] = att.cross_kv(p["cross"], cfg, media)

    if meta.ffn != "none":
        h = rms_norm(_gather_seq(x, ctx), p["ffn_norm"], cfg.norm_eps)
        if meta.ffn == "dense":
            y = apply_dense_ffn(p["ffn"], h)
        else:
            y, aux = _routed_moe(p["ffn"], cfg, h, ctx)
            if "shared" in p["ffn"]:
                b, t, d = h.shape
                y = y + apply_dense_ffn(p["ffn"]["shared"],
                                        h.reshape(b * t, d)).reshape(b, t, d)
        x = ctx.constrain_act(x + y)
    return x, aux, cache


def decode_block(p, cfg, meta: BlockMeta, x, cache, pos,
                 ctx: ParallelCtx = LOCAL):
    """One-token step. x: (B, 1, D). Returns (x, new_cache)."""
    b = x.shape[0]
    codec = att.kv_codec(cfg.kv_bits, cfg.kv_chunk)
    h = rms_norm(x, p["mixer_norm"], cfg.norm_eps)
    new_cache = dict(cache)
    if meta.mixer == "attn":
        q, k, v = att.gqa_qkv(p["mixer"], cfg, h, pos[None])
        if codec.quantized:
            # quantized cache: append the new token's codes+scales and
            # attend directly on the codes (flash_decode dequantizes tile
            # by tile in-register) — no fp copy of the cache, ever; the
            # old path's per-step full-cache kv_dequantize was 3x the
            # fundamental decode HBM traffic per layer per token
            kc, ks = codec.append(cache["k"], cache["ks"], k, pos)
            vc, vs = codec.append(cache["v"], cache["vs"], v, pos)
            new_cache.update(k=kc, ks=ks, v=vc, vs=vs)
            out = att.decode_attention_quantized(
                q, kc, ks, vc, vs, pos, kv_bits=codec.kv_bits,
                chunk=codec.chunk, ctx=ctx)
        else:
            k_cache = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, pos, 1)
            v_cache = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, pos, 1)
            out = att.decode_attention(q, k_cache, v_cache, pos)
            new_cache.update(k=k_cache, v=v_cache)
        mix = linear(out.reshape(b, 1, -1), p["mixer"]["wo"])
    elif meta.mixer == "mla":
        _, _, _, c_kv, k_rope = att.mla_qkv(p["mixer"], cfg, h, pos[None])
        if codec.quantized:
            cc, cs = codec.append(cache["c"], cache["cs"], c_kv, pos)
            rc, rs = codec.append(cache["r"], cache["rs"], k_rope, pos)
            mix = att.mla_decode(p["mixer"], cfg, h, cc, rc, pos, c_scale=cs,
                                 r_scale=rs, kv_bits=codec.kv_bits,
                                 chunk=codec.chunk, ctx=ctx)
            new_cache.update(c=cc, cs=cs, r=rc, rs=rs)
        else:
            c_cache = jax.lax.dynamic_update_slice_in_dim(cache["c"], c_kv,
                                                          pos, 1)
            r_cache = jax.lax.dynamic_update_slice_in_dim(cache["r"], k_rope,
                                                          pos, 1)
            mix = att.mla_decode(p["mixer"], cfg, h, c_cache, r_cache, pos)
            new_cache.update(c=c_cache, r=r_cache)
    elif meta.mixer == "mamba":
        mix, (conv_s, ssm_s) = ssm_lib.mamba_decode(
            p["mixer"], cfg, h, cache["conv"], cache["ssm"])
        new_cache.update(conv=conv_s, ssm=ssm_s)
    elif meta.mixer == "cross":
        mix = att.apply_cross_attn(p["mixer"], cfg, h, kv=cache["kv"])
    x = x + mix
    if meta.has_cross:
        h = rms_norm(x, p["cross_norm"], cfg.norm_eps)
        x = x + att.apply_cross_attn(p["cross"], cfg, h, kv=cache["cross_kv"])
    if meta.ffn != "none":
        h = rms_norm(x, p["ffn_norm"], cfg.norm_eps)
        if meta.ffn == "dense":
            y = apply_dense_ffn(p["ffn"], h)
        else:
            y, _ = _routed_moe(p["ffn"], cfg, h, ctx)
            if "shared" in p["ffn"]:
                t = h.shape[1]
                y = y + apply_dense_ffn(
                    p["ffn"]["shared"], h.reshape(b * t, -1)
                ).reshape(b, t, -1)
        x = x + y
    return x, new_cache


def paged_decode_block(p, cfg, meta: BlockMeta, x, pools, page_tbl, pos,
                       active):
    """One-token step against block-paged quantized pools (serving engine).

    x: (B, 1, D) — one engine slot per row; pools: this block's shared
    code/scale pools (no batch axis — pages are the unit of allocation);
    page_tbl: (B, n_tiles) i32; pos: (B,) i32 per-slot positions; active:
    (B,) bool.  Per-slot rope positions and the per-slot position mask in
    the paged kernels are the only differences from :func:`decode_block` —
    the projection/append/attention math is shared, so a slot's output is
    bitwise the flat B=1 step at the same position.  Meshless by design
    (the engine owns the batch axis)."""
    b = x.shape[0]
    codec = att.kv_codec(cfg.kv_bits, cfg.kv_chunk)
    h = rms_norm(x, p["mixer_norm"], cfg.norm_eps)
    new_pools = dict(pools)
    pid = page_tbl[jnp.arange(b), (pos // codec.page_tokens).astype(jnp.int32)]
    if meta.mixer == "attn":
        q, k, v = att.gqa_qkv(p["mixer"], cfg, h, pos[:, None])
        kc, ks = att.kv_paged_append(codec, pools["k"], pools["ks"], k, pid,
                                     pos, active)
        vc, vs = att.kv_paged_append(codec, pools["v"], pools["vs"], v, pid,
                                     pos, active)
        new_pools.update(k=kc, ks=ks, v=vc, vs=vs)
        # a retired slot keeps its stale pos and table, whose pages may
        # belong to another request by now: -1 takes it off the kernel's
        # work list (one empty item, output 0)
        out = att.paged_decode_attention_quantized(
            q, kc, ks, vc, vs, page_tbl, jnp.where(active, pos, -1),
            kv_bits=codec.kv_bits, chunk=codec.chunk)
        mix = linear(out.reshape(b, 1, -1), p["mixer"]["wo"])
    elif meta.mixer == "mla":
        _, _, _, c_kv, k_rope = att.mla_qkv(p["mixer"], cfg, h, pos[:, None])
        cc, cs = att.kv_paged_append(codec, pools["c"], pools["cs"], c_kv,
                                     pid, pos, active)
        rc, rs = att.kv_paged_append(codec, pools["r"], pools["rs"], k_rope,
                                     pid, pos, active)
        new_pools.update(c=cc, cs=cs, r=rc, rs=rs)
        mix = att.mla_decode_paged(p["mixer"], cfg, h, cc, cs, rc, rs,
                                   page_tbl, pos, kv_bits=codec.kv_bits,
                                   chunk=codec.chunk)
    else:
        raise NotImplementedError(
            f"paged decode supports attn/mla mixers, got {meta.mixer!r} — "
            "ssm/cross state is per-slot, not per-page; serve such models "
            "through the flat generate() path")
    x = x + mix
    if meta.ffn != "none":
        h = rms_norm(x, p["ffn_norm"], cfg.norm_eps)
        if meta.ffn == "dense":
            y = apply_dense_ffn(p["ffn"], h)
        else:
            y, _ = _routed_moe(p["ffn"], cfg, h, LOCAL)
            if "shared" in p["ffn"]:
                t = h.shape[1]
                y = y + apply_dense_ffn(
                    p["ffn"]["shared"], h.reshape(b * t, -1)
                ).reshape(b, t, -1)
        x = x + y
    return x, new_pools


def pad_cache_entry(c, codec, s: int):
    """Zero-pad one block's sequence-indexed cache entries to length ``s``
    (codes) / ``scale_rows(s)`` (scales); everything else passes through.
    Shared by whole-prompt prefill (s = rounded prompt length) and chunked
    prefill (s = rounded chunk length) — zero rows match what the paged
    kernels mask out, and codes are padded *after* encoding real rows (a
    zero kv2 row would encode to code 2, not 0)."""
    def f(key, a):
        if key in ("k", "v", "c", "r"):
            tgt = s
        elif key in ("ks", "vs", "cs", "rs"):
            tgt = codec.scale_rows(s)
        else:
            return a
        pad = [(0, 0)] * a.ndim
        pad[1] = (0, tgt - a.shape[1])
        return jnp.pad(a, pad)
    return {k: (f(k, v) if not isinstance(v, (dict, tuple)) else v)
            for k, v in c.items()}


def ingest_block(p, cfg, meta: BlockMeta, x, buf, start, positions,
                 t_total: int):
    """One prompt chunk through one block against fp prefix buffers (exact
    chunked prefill).

    x: (1, L, D) chunk rows; buf: fp K/V buffers of full prompt length
    ``t_total`` (GQA: post-rope K/V; MLA: the *expanded* per-head K/V —
    flash_attention's operands); start: () i32 page-aligned chunk offset.
    The chunk's rows are sliced into the buffers, then flash_attention runs
    with ``q_offset=start`` and ``kv_chunk=min(512, t_total)`` — the same
    kv tiles, in the same order, under the same causal mask as the flat
    prefill's pass over the whole prompt, and every other op here is
    row-wise.  Hidden rows, cache codes and the final chunk's logits are
    therefore bitwise the whole-prompt prefill's.  Returns
    (x, new_buf, chunk_cache) with chunk_cache holding codes for the L
    chunk rows only."""
    codec = att.kv_codec(cfg.kv_bits, cfg.kv_chunk)
    h = rms_norm(x, p["mixer_norm"], cfg.norm_eps)
    b, t, _ = h.shape
    if meta.mixer == "attn":
        q, k, v = att.gqa_qkv(p["mixer"], cfg, h, positions)
        k_buf = jax.lax.dynamic_update_slice_in_dim(buf["k"], k, start, 1)
        v_buf = jax.lax.dynamic_update_slice_in_dim(buf["v"], v, start, 1)
        out = att.flash_attention(q, k_buf, v_buf, causal=True,
                                  kv_chunk=min(512, t_total), q_offset=start)
        mix = linear(out.reshape(b, t, -1), p["mixer"]["wo"])
        if codec.quantized:
            kq, ks = codec.encode(k)
            vq, vs = codec.encode(v)
            cache = {"k": kq, "ks": ks, "v": vq, "vs": vs}
        else:
            cache = {"k": k, "v": v}
        new_buf = {"k": k_buf, "v": v_buf}
    elif meta.mixer == "mla":
        q, k, v, c_kv, k_rope = att.mla_qkv(p["mixer"], cfg, h, positions)
        k_buf = jax.lax.dynamic_update_slice_in_dim(buf["k"], k, start, 1)
        v_buf = jax.lax.dynamic_update_slice_in_dim(buf["v"], v, start, 1)
        out = att.flash_attention(q, k_buf, v_buf, causal=True,
                                  kv_chunk=min(512, t_total), q_offset=start)
        mix = linear(out.reshape(b, t, -1), p["mixer"]["wo"])
        if codec.quantized:
            cq, cs = codec.encode(c_kv)
            rq, rs = codec.encode(k_rope)
            cache = {"c": cq, "cs": cs, "r": rq, "rs": rs}
        else:
            cache = {"c": c_kv, "r": k_rope}
        new_buf = {"k": k_buf, "v": v_buf}
    else:
        raise NotImplementedError(
            f"chunked prefill supports attn/mla mixers, got {meta.mixer!r} — "
            "ssm/cross state is sequential, not per-page; serve such models "
            "through the flat generate() path")
    x = x + mix
    if meta.ffn != "none":
        h = rms_norm(x, p["ffn_norm"], cfg.norm_eps)
        if meta.ffn == "dense":
            y = apply_dense_ffn(p["ffn"], h)
        else:
            y, _ = _routed_moe(p["ffn"], cfg, h, LOCAL)
            if "shared" in p["ffn"]:
                t = h.shape[1]
                y = y + apply_dense_ffn(
                    p["ffn"]["shared"], h.reshape(b * t, -1)
                ).reshape(b, t, -1)
        x = x + y
    return x, new_buf, cache


def paged_extend_block(p, cfg, meta: BlockMeta, x, pools, tbl, start,
                       positions):
    """One prompt chunk through one block against the request's quantized
    pages (opt-in "paged" chunked prefill).

    No fp prefix buffer exists: earlier chunks are read back as codes
    through the paged extend kernels (in-register dequant, same tile math
    as paged decode), the chunk's own rows attend causally in fp.
    HBM-cheap — the only per-request state is the pages themselves — but
    *lossy* versus the flat prefill, since past keys have already been
    through the codec.  tbl: (n_past,) i32 pages of the already-ingested
    chunks.  Returns (x, chunk_cache)."""
    codec = att.kv_codec(cfg.kv_bits, cfg.kv_chunk)
    h = rms_norm(x, p["mixer_norm"], cfg.norm_eps)
    b, t, _ = h.shape
    if meta.mixer == "attn":
        q, k, v = att.gqa_qkv(p["mixer"], cfg, h, positions)
        out = att.paged_extend_attention_quantized(
            q, k, v, pools["k"], pools["ks"], pools["v"], pools["vs"], tbl,
            start, kv_bits=codec.kv_bits, chunk=codec.chunk)
        mix = linear(out.reshape(b, t, -1), p["mixer"]["wo"])
        kq, ks = codec.encode(k)
        vq, vs = codec.encode(v)
        cache = {"k": kq, "ks": ks, "v": vq, "vs": vs}
    elif meta.mixer == "mla":
        _, _, _, c_kv, k_rope = att.mla_qkv(p["mixer"], cfg, h, positions)
        mix = att.mla_extend_paged(
            p["mixer"], cfg, h, c_kv, k_rope, pools, tbl, start, positions,
            kv_bits=codec.kv_bits, chunk=codec.chunk)
        cq, cs = codec.encode(c_kv)
        rq, rs = codec.encode(k_rope)
        cache = {"c": cq, "cs": cs, "r": rq, "rs": rs}
    else:
        raise NotImplementedError(
            f"chunked prefill supports attn/mla mixers, got {meta.mixer!r} — "
            "ssm/cross state is sequential, not per-page; serve such models "
            "through the flat generate() path")
    x = x + mix
    if meta.ffn != "none":
        h = rms_norm(x, p["ffn_norm"], cfg.norm_eps)
        if meta.ffn == "dense":
            y = apply_dense_ffn(p["ffn"], h)
        else:
            y, _ = _routed_moe(p["ffn"], cfg, h, LOCAL)
            if "shared" in p["ffn"]:
                t = h.shape[1]
                y = y + apply_dense_ffn(
                    p["ffn"]["shared"], h.reshape(b * t, -1)
                ).reshape(b, t, -1)
        x = x + y
    return x, cache


def capture_block(p, cfg, meta: BlockMeta, x, *, positions=None, media=None):
    """Calibration forward of one block for the RSQ pipeline.

    Returns (y, caps, domains, colsum):
      caps     — weight path -> input matrix X (stream shapes (B, T, d_in);
                 expert entries (E, C, d))
      domains  — weight path -> "stream" | "media" | "expert" | "hidden"
                 ("stream"/"hidden" rows are token-aligned and get scaled by
                 R; "media" rows are media tokens; "expert" buffers carry
                 their own slot->token map in caps["__moe_slot_token"])
      colsum   — (B, T) attention-concentration scores or None
    """
    if positions is None:
        positions = jnp.arange(x.shape[1])
    caps: dict[str, Any] = {}
    dom: dict[str, str] = {}
    colsum = None
    b, t, _ = x.shape
    h = rms_norm(x, p["mixer_norm"], cfg.norm_eps)
    if meta.mixer == "attn":
        q, k, v = att.gqa_qkv(p["mixer"], cfg, h, positions)
        out, colsum = att.flash_attention(q, k, v, causal=meta.causal,
                                          kv_chunk=min(512, t), colsum=True)
        attn_out = out.reshape(b, t, -1)
        mix = linear(attn_out, p["mixer"]["wo"])
        caps.update({"mixer/wq": h, "mixer/wk": h, "mixer/wv": h,
                     "mixer/wo": attn_out})
        dom.update({k_: "stream" for k_ in
                    ("mixer/wq", "mixer/wk", "mixer/wv", "mixer/wo")})
    elif meta.mixer == "mla":
        pm = p["mixer"]
        dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
        kvr = cfg.kv_lora_rank
        if "wq_a" in pm:
            ql = rms_norm(linear(h, pm["wq_a"]), pm["q_norm"], cfg.norm_eps)
            q = linear(ql, pm["wq_b"]).reshape(b, t, cfg.n_heads, dn + dr)
            caps.update({"mixer/wq_a": h, "mixer/wq_b": ql})
            dom.update({"mixer/wq_a": "stream", "mixer/wq_b": "stream"})
        else:
            q = linear(h, pm["wq"]).reshape(b, t, cfg.n_heads, dn + dr)
            caps["mixer/wq"] = h
            dom["mixer/wq"] = "stream"
        from repro.models.layers import apply_rope
        q_nope, q_rope = q[..., :dn], q[..., dn:]
        q = jnp.concatenate(
            [q_nope, apply_rope(q_rope, positions, cfg.rope_theta)], axis=-1)
        kv = linear(h, pm["wkv_a"])
        c_kv = rms_norm(kv[..., :kvr], pm["kv_norm"], cfg.norm_eps)
        k_rope = apply_rope(kv[..., None, kvr:], positions, cfg.rope_theta)
        kvb = linear(c_kv, pm["wkv_b"]).reshape(b, t, cfg.n_heads, dn + dv)
        k = jnp.concatenate(
            [kvb[..., :dn],
             jnp.broadcast_to(k_rope, (b, t, cfg.n_heads, dr))], axis=-1)
        out, colsum = att.flash_attention(q, k, kvb[..., dn:],
                                          causal=meta.causal,
                                          kv_chunk=min(512, t), colsum=True)
        ctx_out = out.reshape(b, t, -1)
        mix = linear(ctx_out, pm["wo"])
        caps.update({"mixer/wkv_a": h, "mixer/wkv_b": c_kv,
                     "mixer/wo": ctx_out})
        dom.update({"mixer/wkv_a": "stream", "mixer/wkv_b": "stream",
                    "mixer/wo": "stream"})
    elif meta.mixer == "mamba":
        mix, m_caps = ssm_lib.capture_mamba(p["mixer"], cfg, h)
        caps.update({f"mixer/{k_}": v_ for k_, v_ in m_caps.items()})
        dom.update({f"mixer/{k_}": "stream" for k_ in m_caps})
    elif meta.mixer == "cross":
        pm = p["mixer"]
        q = linear(h, pm["wq"]).reshape(b, t, cfg.n_heads, cfg.head_dim)
        kv = att.cross_kv(pm, cfg, media)
        out = att.flash_attention(q, *kv, causal=False,
                                  kv_chunk=min(512, kv[0].shape[1]))
        attn_out = out.reshape(b, t, -1)
        mix = linear(attn_out, pm["wo"])
        caps.update({"mixer/wq": h, "mixer/wk": media, "mixer/wv": media,
                     "mixer/wo": attn_out})
        dom.update({"mixer/wq": "stream", "mixer/wk": "media",
                    "mixer/wv": "media", "mixer/wo": "stream"})
    x = x + mix

    if meta.has_cross:
        h2 = rms_norm(x, p["cross_norm"], cfg.norm_eps)
        pc = p["cross"]
        q = linear(h2, pc["wq"]).reshape(b, t, cfg.n_heads, cfg.head_dim)
        kv = att.cross_kv(pc, cfg, media)
        out = att.flash_attention(q, *kv, causal=False,
                                  kv_chunk=min(512, kv[0].shape[1]))
        attn_out = out.reshape(b, t, -1)
        x = x + linear(attn_out, pc["wo"])
        caps.update({"cross/wq": h2, "cross/wk": media, "cross/wv": media,
                     "cross/wo": attn_out})
        dom.update({"cross/wq": "stream", "cross/wk": "media",
                    "cross/wv": "media", "cross/wo": "stream"})

    if meta.ffn != "none":
        hf = rms_norm(x, p["ffn_norm"], cfg.norm_eps)
        if meta.ffn == "dense":
            y, f_caps = capture_dense_ffn(p["ffn"], hf)
            caps.update({f"ffn/{k_}": v_ for k_, v_ in f_caps.items()})
            dom.update({f"ffn/{k_}": ("hidden" if k_ == "wd" else "stream")
                        for k_ in f_caps})
        else:
            y, _aux, m_caps = moe_lib.capture_moe(p["ffn"], cfg, hf)
            for k_, v_ in m_caps.items():
                if k_.startswith("experts/"):
                    caps[f"ffn/{k_}"] = v_
                    dom[f"ffn/{k_}"] = "expert"
                elif k_ == "__slot_token":
                    caps["ffn/__moe_slot_token"] = v_
                else:  # shared expert
                    caps[f"ffn/{k_}"] = v_
                    dom[f"ffn/{k_}"] = ("hidden" if k_.endswith("wd")
                                        else "stream")
        x = x + y
    return x, caps, dom, colsum


# --------------------------------------------------------------- full stacks


def _group_metas(metas, period):
    groups = [metas[i : i + period] for i in range(0, len(metas), period)]
    assert all(g == groups[0] for g in groups), "layer pattern must repeat"
    return groups[0]


class Model:
    """Functional model wrapper for one ModelConfig."""

    def __init__(self, cfg: ModelConfig, ctx: ParallelCtx = LOCAL):
        if cfg.kv_bits not in (0, 2, 8):
            raise ValueError(
                f"kv_bits={cfg.kv_bits} is not supported — use 0 (KV cache "
                "in the activation dtype), 8 (int8 codes + per-token-head "
                "scales) or 2 (packed log codes + per-chunk scales)")
        self.cfg = cfg
        self.ctx = ctx
        self.dtype = jnp.dtype(cfg.dtype)
        # one codec instance owns every rounding/layout decision — flat
        # cache, paged pools and serve-time capacity math all derive from
        # it, so they cannot drift
        self.codec = att.kv_codec(cfg.kv_bits, cfg.kv_chunk)
        metas = decoder_metas(cfg)
        self.prefix_metas = metas[: cfg.first_dense_layers]
        body = metas[cfg.first_dense_layers :]
        self.period = cfg.scan_period
        assert len(body) % self.period == 0, (len(body), self.period)
        self.n_groups = len(body) // self.period
        self.group_metas = _group_metas(body, self.period)
        self.enc_metas = encoder_metas(cfg) if cfg.family == "encdec" else ()

    # ------------------------------------------------------------------ init
    def init(self, key) -> dict:
        cfg, dtype = self.cfg, self.dtype
        keys = jax.random.split(key, 8)
        params: dict[str, Any] = {
            "embed": init_embedding(keys[0], cfg.vocab_size, cfg.d_model, dtype),
            "final_norm": jnp.ones((cfg.d_model,), dtype),
        }
        if not cfg.tie_embeddings:
            params["head"] = dense_init(keys[1], cfg.d_model, cfg.vocab_size,
                                        dtype)
        if self.prefix_metas:
            pkeys = jax.random.split(keys[2], len(self.prefix_metas))
            params["prefix"] = [
                init_block(k, cfg, m, dtype)
                for k, m in zip(pkeys, self.prefix_metas)
            ]

        def init_group(k):
            ks = jax.random.split(k, self.period)
            return {
                f"b{i}": init_block(ks[i], cfg, self.group_metas[i], dtype)
                for i in range(self.period)
            }

        gkeys = jax.random.split(keys[3], self.n_groups)
        params["groups"] = jax.vmap(init_group)(gkeys)

        if self.enc_metas:
            def init_enc_group(k):
                return {"b0": init_block(k, cfg, self.enc_metas[0], dtype)}

            ekeys = jax.random.split(keys[4], len(self.enc_metas))
            params["encoder"] = {
                "groups": jax.vmap(init_enc_group)(ekeys),
                "final_norm": jnp.ones((cfg.d_model,), dtype),
            }
        return params

    def param_shapes(self):
        return jax.eval_shape(self.init, jax.random.key(0))

    def param_specs(self):
        return param_specs(self.param_shapes(), self.ctx)

    # --------------------------------------------------------------- encoder
    def _encode(self, params, frames):
        cfg, ctx = self.cfg, self.ctx
        if "frame_proj" in params:
            # rotation folded into the (stubbed) conv frontend's output
            # projection — see core/rotation.rotate_model
            frames = frames @ params["frame_proj"].astype(frames.dtype)
        t = frames.shape[1]
        positions = jnp.arange(t)

        def body(x, gp):
            x, _, _ = apply_block(gp["b0"], cfg, self.enc_metas[0], x,
                                  positions=positions, ctx=ctx)
            return x, None

        x, _ = jax.lax.scan(jax.checkpoint(body), frames,
                            params["encoder"]["groups"])
        return rms_norm(x, params["encoder"]["final_norm"], cfg.norm_eps)

    # --------------------------------------------------------------- forward
    def hidden_states(self, params, tokens, *, media=None, frames=None):
        """(B, T) tokens -> (B, T, D) final hidden states (post final norm)."""
        cfg, ctx = self.cfg, self.ctx
        x = embed_lookup(params["embed"], tokens).astype(self.dtype)
        x = ctx.constrain_act(x)
        t = tokens.shape[1]
        positions = jnp.arange(t)
        if cfg.family == "encdec":
            media = self._encode(params, frames)
        aux = jnp.zeros((), jnp.float32)
        for p_blk, meta in zip(params.get("prefix", []), self.prefix_metas):
            x, a, _ = apply_block(p_blk, cfg, meta, x, positions=positions,
                                  media=media, ctx=ctx)
            aux = aux + a

        def body(carry, gp):
            x, aux = carry
            for i in range(self.period):
                x, a, _ = apply_block(gp[f"b{i}"], cfg, self.group_metas[i], x,
                                      positions=positions, media=media, ctx=ctx)
                aux = aux + a
            return (x, aux), None

        body_fn = jax.checkpoint(body) if cfg.remat != "none" else body
        (x, aux), _ = jax.lax.scan(body_fn, (x, aux), params["groups"])
        return rms_norm(x, params["final_norm"], cfg.norm_eps), aux

    def head_weight(self, params):
        # rotation/quantization may untie the head (norm-γ fusion breaks the
        # tie), in which case an explicit "head" entry takes precedence
        if "head" in params:
            return params["head"]
        return params["embed"].T

    def head_logits(self, params, x: jax.Array) -> jax.Array:
        """(..., D) hidden states -> (..., V) fp32 logits.

        Tied-embedding models contract the (V, D) table over D directly
        instead of going through ``head_weight``'s ``embed.T``: with the
        whole generation fused into one ``lax.scan`` device program
        (launch.serve), a materialized (D, V) transpose would sit *inside*
        the per-token loop body — at real vocab sizes that is a
        full-table-sized copy per generated token."""
        if "head" in params:
            return linear(x, params["head"]).astype(jnp.float32)
        return jax.lax.dot_general(
            x, params["embed"].astype(x.dtype),
            (((x.ndim - 1,), (1,)), ((), ()))).astype(jnp.float32)

    def loss(self, params, batch) -> jax.Array:
        """batch: {"tokens", "labels", opt "media"/"frames"} -> scalar loss."""
        x, aux = self.hidden_states(params, batch["tokens"],
                                    media=batch.get("media"),
                                    frames=batch.get("frames"))
        ce = cross_entropy_chunked(x, self.head_weight(params),
                                   batch["labels"])
        return ce + 0.01 * aux

    def logits(self, params, tokens, **kw) -> jax.Array:
        x, _ = self.hidden_states(params, tokens, **kw)
        return self.head_logits(params, x)

    def _cache_len(self, s: int) -> int:
        """Allocated cache length — the codec's ``round_len``: quantized
        caches round up to a ``kv_chunk`` multiple so flash_decode always
        has an aligned sequence tile (scale rows stay whole; the tail is
        position-masked).  Pages use the same rounding (page = kv_chunk),
        so flat-cache and page-capacity math share one source of truth."""
        return self.codec.round_len(s)

    # --------------------------------------------------------------- prefill
    def prefill(self, params, tokens, *, media=None, frames=None,
                cache_len: Optional[int] = None, logits: bool = True):
        """Returns (last_logits (B, V), cache). Cache length ``cache_len``
        (defaults to T; quantized caches round up to a kv_chunk multiple).

        ``logits=False`` is the resume-ingest entry for the serve engine's
        preemption path: re-admitting a preempted request replays the
        prompt through this exact prefill to rebuild its KV pages bitwise,
        but its token 0 was already drawn before preemption — skipping the
        head projection drops the one vocab-sized matmul the resume would
        otherwise waste (returns ``(None, cache)``)."""
        cfg, ctx = self.cfg, self.ctx
        b, t = tokens.shape
        s = self._cache_len(cache_len or t)
        x = embed_lookup(params["embed"], tokens).astype(self.dtype)
        x = ctx.constrain_act(x)
        positions = jnp.arange(t)
        if cfg.family == "encdec":
            media = self._encode(params, frames)

        def pad_entry(c):
            # only sequence-indexed entries (self-attn KV, MLA latents) grow;
            # quantized caches also carry scale rows — the codec's
            # ``scale_rows`` (s is already a chunk multiple)
            return pad_cache_entry(c, self.codec, s)

        caches_prefix = []
        for p_blk, meta in zip(params.get("prefix", []), self.prefix_metas):
            x, _, c = apply_block(p_blk, cfg, meta, x, positions=positions,
                                  media=media, ctx=ctx)
            caches_prefix.append(pad_entry(c))

        def body(x, gp):
            caches = {}
            for i in range(self.period):
                x, _, c = apply_block(gp[f"b{i}"], cfg, self.group_metas[i], x,
                                      positions=positions, media=media, ctx=ctx)
                caches[f"b{i}"] = pad_entry(c)
            return x, caches

        x, group_caches = jax.lax.scan(body, x, params["groups"])
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        out = self.head_logits(params, x[:, -1]) if logits else None
        cache = {"groups": group_caches}
        if caches_prefix:
            cache["prefix"] = caches_prefix
        if cfg.family == "encdec":
            cache["media"] = media
        return out, cache

    def init_cache(self, batch: int, cache_len: int, *, media=None):
        """Zero cache for pure-decode lowering (decode_32k / long_500k)."""
        cfg = self.cfg
        kvh, dh = cfg.n_kv_heads, cfg.head_dim
        dt = self.dtype
        cache_len = self._cache_len(cache_len)
        codec = self.codec

        def qkv_entry(d: int):
            """(codes, scales) zero pair for one quantized cache tensor of
            feature width d (head axes supplied by the caller) — widths,
            dtypes and scale-row counts all come from the codec layout."""
            return (((cache_len, codec.code_cols(d)), codec.code_dtype),
                    ((codec.scale_rows(cache_len),), codec.scale_dtype))

        def entry(meta: BlockMeta):
            c = {}
            if meta.mixer == "attn":
                if cfg.kv_bits in (8, 2):
                    (cd, cdt), (sd, sdt) = qkv_entry(dh)
                    codes = jnp.zeros((batch, cd[0], kvh) + cd[1:], cdt)
                    scales = jnp.zeros((batch, sd[0], kvh), sdt)
                    c = {"k": codes, "v": codes, "ks": scales, "vs": scales}
                else:
                    c = {"k": jnp.zeros((batch, cache_len, kvh, dh), dt),
                         "v": jnp.zeros((batch, cache_len, kvh, dh), dt)}
            elif meta.mixer == "mla":
                if cfg.kv_bits in (8, 2):
                    (cd, cdt), (sd, sdt) = qkv_entry(cfg.kv_lora_rank)
                    (rd, rdt), _ = qkv_entry(cfg.qk_rope_dim)
                    c = {"c": jnp.zeros((batch,) + cd, cdt),
                         "cs": jnp.zeros((batch,) + sd, sdt),
                         "r": jnp.zeros((batch,) + rd, rdt),
                         "rs": jnp.zeros((batch,) + sd, sdt)}
                else:
                    c = {"c": jnp.zeros((batch, cache_len, cfg.kv_lora_rank),
                                        dt),
                         "r": jnp.zeros((batch, cache_len, cfg.qk_rope_dim),
                                        dt)}
            elif meta.mixer == "mamba":
                c = {"conv": jnp.zeros(
                        (batch, cfg.ssm_conv_width - 1,
                         cfg.d_inner + 2 * cfg.ssm_d_state), dt),
                     "ssm": jnp.zeros(
                        (batch, cfg.ssm_n_heads, cfg.ssm_head_dim,
                         cfg.ssm_d_state), jnp.float32)}
            elif meta.mixer == "cross":
                tm = media.shape[1]
                c = {"kv": (jnp.zeros((batch, tm, kvh, dh), dt),
                            jnp.zeros((batch, tm, kvh, dh), dt))}
            if meta.has_cross:
                tm = media.shape[1]
                c["cross_kv"] = (jnp.zeros((batch, tm, kvh, dh), dt),
                                 jnp.zeros((batch, tm, kvh, dh), dt))
            return c

        def stack(e):
            return jax.tree.map(
                lambda a: jnp.broadcast_to(a, (self.n_groups,) + a.shape), e)

        cache = {"groups": {f"b{i}": stack(entry(self.group_metas[i]))
                            for i in range(self.period)}}
        if self.prefix_metas:
            cache["prefix"] = [entry(m) for m in self.prefix_metas]
        return cache

    # ---------------------------------------------------------------- decode
    def decode_step(self, params, cache, token, pos):
        """token: (B, 1) int32; pos: () int32. Returns (logits (B, V), cache)."""
        cfg, ctx = self.cfg, self.ctx
        x = embed_lookup(params["embed"], token).astype(self.dtype)
        x = ctx.constrain(x, "dp", None, None)
        new_cache = dict(cache)
        if "prefix" in cache:
            new_prefix = []
            for p_blk, meta, c in zip(params["prefix"], self.prefix_metas,
                                      cache["prefix"]):
                x, c2 = decode_block(p_blk, cfg, meta, x, c, pos, ctx=ctx)
                new_prefix.append(c2)
            new_cache["prefix"] = new_prefix

        def body(x, xs):
            gp, gc = xs
            new_gc = {}
            for i in range(self.period):
                x, c2 = decode_block(gp[f"b{i}"], cfg, self.group_metas[i], x,
                                     gc[f"b{i}"], pos, ctx=ctx)
                new_gc[f"b{i}"] = c2
            return x, new_gc

        x, new_groups = jax.lax.scan(body, x, (params["groups"],
                                               cache["groups"]))
        new_cache["groups"] = new_groups
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = self.head_logits(params, x[:, 0])
        return logits, new_cache

    def paged_decode_step(self, params, pools, page_tbl, token, pos, active):
        """One decode step for every engine slot against paged pools.

        token: (B, 1) int32; page_tbl: (B, n_tiles) int32; pos/active:
        (B,) per-slot positions and liveness.  Returns
        (logits (B, V), pools) — the same group-scan schedule as
        :func:`decode_step`, with the ONE page table shared by every
        layer (all layers of a request occupy the same logical tiles;
        each layer owns its pools)."""
        cfg = self.cfg
        x = embed_lookup(params["embed"], token).astype(self.dtype)
        new_pools = dict(pools)
        if "prefix" in pools:
            new_prefix = []
            for p_blk, meta, c in zip(params["prefix"], self.prefix_metas,
                                      pools["prefix"]):
                x, c2 = paged_decode_block(p_blk, cfg, meta, x, c, page_tbl,
                                           pos, active)
                new_prefix.append(c2)
            new_pools["prefix"] = new_prefix

        def body(x, xs):
            gp, gc = xs
            new_gc = {}
            for i in range(self.period):
                x, c2 = paged_decode_block(gp[f"b{i}"], cfg,
                                           self.group_metas[i], x,
                                           gc[f"b{i}"], page_tbl, pos, active)
                new_gc[f"b{i}"] = c2
            return x, new_gc

        x, new_groups = jax.lax.scan(body, x, (params["groups"],
                                               pools["groups"]))
        new_pools["groups"] = new_groups
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = self.head_logits(params, x[:, 0])
        return logits, new_pools

    # ------------------------------------------------------- chunked prefill
    def init_ingest(self, t_total: int):
        """Transient fp prefix buffers for exact chunked prefill of ONE
        request of prompt length ``t_total``.

        GQA blocks keep the post-rope K/V rows, MLA blocks the *expanded*
        per-head K/V (flash_attention's operands), so each chunk's
        attention replays the flat prefill bitwise — see
        :func:`ingest_block`.  The buffers live only while the request is
        ingesting; the steady-state cache representation stays quantized
        pages."""
        cfg = self.cfg
        dt = self.dtype
        kvh, dh = cfg.n_kv_heads, cfg.head_dim

        def entry(meta: BlockMeta):
            if meta.mixer == "attn":
                return {"k": jnp.zeros((1, t_total, kvh, dh), dt),
                        "v": jnp.zeros((1, t_total, kvh, dh), dt)}
            if meta.mixer == "mla":
                dq = cfg.qk_nope_dim + cfg.qk_rope_dim
                return {"k": jnp.zeros((1, t_total, cfg.n_heads, dq), dt),
                        "v": jnp.zeros((1, t_total, cfg.n_heads,
                                        cfg.v_head_dim), dt)}
            raise NotImplementedError(
                f"chunked prefill supports attn/mla mixers, got "
                f"{meta.mixer!r}")

        def stack(e):
            return jax.tree.map(
                lambda a: jnp.broadcast_to(a, (self.n_groups,) + a.shape), e)

        state = {"groups": {f"b{i}": stack(entry(self.group_metas[i]))
                            for i in range(self.period)}}
        if self.prefix_metas:
            state["prefix"] = [entry(m) for m in self.prefix_metas]
        return state

    def paged_extend_step(self, params, tokens, start, state, *,
                          t_total: int, last: bool, pools=None,
                          page_tbl=None):
        """Ingest one page-aligned prompt chunk of one request.

        tokens: (1, L) i32 chunk tokens; start: () i32 chunk offset
        (multiple of the page size); ``state``: fp prefix buffers from
        :meth:`init_ingest` (exact mode) — or None with ``pools`` +
        ``page_tbl`` (the request's already-written pages, (n_past,) i32)
        for the opt-in paged mode that attends earlier chunks' quantized
        pages through the extend kernels.  Returns
        (logits, new_state, chunk_cache): logits (1, V) when ``last`` else
        None — the same draw whole-prompt prefill would produce; and
        chunk_cache in prefill-cache layout, padded to a page multiple,
        ready for ``PagedPools.write_prefill`` on the chunk's pages."""
        cfg = self.cfg
        _, L = tokens.shape
        s_pad = self._cache_len(L)
        x = embed_lookup(params["embed"], tokens).astype(self.dtype)
        positions = start + jnp.arange(L)
        exact = state is not None

        caches_prefix = []
        new_prefix = []
        for p_blk, meta, c in zip(params.get("prefix", []),
                                  self.prefix_metas,
                                  (state or pools).get("prefix", [])):
            if exact:
                x, nb, cc = ingest_block(p_blk, cfg, meta, x, c, start,
                                         positions, t_total)
                new_prefix.append(nb)
            else:
                x, cc = paged_extend_block(p_blk, cfg, meta, x, c, page_tbl,
                                           start, positions)
            caches_prefix.append(pad_cache_entry(cc, self.codec, s_pad))

        if exact:
            def body(x, xs):
                gp, gb = xs
                new_gb, caches = {}, {}
                for i in range(self.period):
                    x, nb, cc = ingest_block(gp[f"b{i}"], cfg,
                                             self.group_metas[i], x,
                                             gb[f"b{i}"], start, positions,
                                             t_total)
                    new_gb[f"b{i}"] = nb
                    caches[f"b{i}"] = pad_cache_entry(cc, self.codec, s_pad)
                return x, (new_gb, caches)

            x, (new_groups, group_caches) = jax.lax.scan(
                body, x, (params["groups"], state["groups"]))
            new_state = {"groups": new_groups}
            if new_prefix:
                new_state["prefix"] = new_prefix
        else:
            def body(x, xs):
                gp, gpools = xs
                caches = {}
                for i in range(self.period):
                    x, cc = paged_extend_block(gp[f"b{i}"], cfg,
                                               self.group_metas[i], x,
                                               gpools[f"b{i}"], page_tbl,
                                               start, positions)
                    caches[f"b{i}"] = pad_cache_entry(cc, self.codec, s_pad)
                return x, caches

            x, group_caches = jax.lax.scan(
                body, x, (params["groups"], pools["groups"]))
            new_state = None

        chunk_cache = {"groups": group_caches}
        if caches_prefix:
            chunk_cache["prefix"] = caches_prefix
        logits = None
        if last:
            x = rms_norm(x, params["final_norm"], cfg.norm_eps)
            logits = self.head_logits(params, x[:, -1])
        return logits, new_state, chunk_cache


def build_model(cfg: ModelConfig, ctx: ParallelCtx = LOCAL) -> Model:
    return Model(cfg, ctx)
