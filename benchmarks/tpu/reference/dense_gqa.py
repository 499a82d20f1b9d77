"""Plain float32 reference of a dense decoder with grouped-query attention.

Written from the published description (RMSNorm, rotary embedding on
half-split head dimensions, causal softmax attention with query heads in
groups over shared KV heads, optional QKV bias, SwiGLU feed-forward, an
untied head) in straightforward ``jax.numpy`` at full float32 precision.
It imports nothing of the program: weights are regenerated from the seed
by ``benchmarks/tpu/weights.py``, layer by layer, so that the whole model
never has to fit beside itself.

Two users:

* serving cells: logits at given positions of token sequences, each
  sequence alone, padded at its end to a multiple of 1024 tokens;
* the quantizer cell: RSQ on the first layers of the cut model.
  AttnCon importance (per token, the attention probability mass it
  receives, summed over heads and queries; per sample mapped linearly
  into [r_min, r_max]), Hessians H = 2 (R X)^T (R X) per distinct input,
  GPTQ (damping ``damp`` x mean diagonal; rows in order; per group of
  ``group_size`` rows a symmetric scale from the already-compensated rows;
  error feedback through the upper Cholesky factor of H^-1), and the
  quantized layer's output as the next layer's input.

The control, one precision step below the bfloat16 activations that the
configurations state: ``act_dtype`` puts every projection's input through
that dtype first (per-row scaling to its largest finite value).
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.tpu import modelcfg, weights

HI = jax.lax.Precision.HIGHEST


def _mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def lower(x, act_dtype):
    """x through ``act_dtype`` with per-row absmax scaling (identity when
    ``act_dtype`` is None)."""
    if act_dtype is None:
        return x
    big = float(jnp.finfo(act_dtype).max)
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-30) / big
    return (x / s).astype(act_dtype).astype(jnp.float32) * s


def rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def rope(x, pos, theta):
    """x: (..., T, H, Dh); pos: (T,)."""
    dh = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = pos.astype(jnp.float32)[:, None] * inv
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    a, b = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([a * c - b * s, b * c + a * s], -1)


def attention(q, k, v, q_blk: int = 256, colsum: bool = False):
    """Causal attention.  q: (B, T, H, Dh); k, v: (B, T, KV, D).  Returns
    (B, T, H, Dv) and, with ``colsum``, (B, T) probability mass per key
    summed over heads and queries."""
    b, t, h, dh = q.shape
    g = h // k.shape[2]
    kr = jnp.repeat(k, g, axis=2)
    vr = jnp.repeat(v, g, axis=2)
    q_blk = min(q_blk, t)
    pos = jnp.arange(t)

    def blk(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * q_blk, q_blk, 1)
        pb = jax.lax.dynamic_slice_in_dim(pos, i * q_blk, q_blk, 0)
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, kr, precision=HI) * dh ** -0.5
        s = jnp.where((pb[:, None] >= pos[None, :])[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return (jnp.einsum("bhqk,bkhd->bqhd", p, vr, precision=HI),
                p.sum(axis=(1, 2)))

    outs, cols = jax.lax.map(blk, jnp.arange(t // q_blk))
    out = jnp.moveaxis(outs, 0, 1).reshape(b, t, h, vr.shape[-1])
    return (out, cols.sum(0)) if colsum else out


def block(w, x, d, act_dtype=None, colsum=False, caps=False):
    """One decoder layer on (B, T, D) float32.  ``caps`` also returns the
    input of each projection."""
    low = functools.partial(lower, act_dtype=act_dtype)
    h = rms(x, w["mixer_norm"], d["norm_eps"])
    q = _mm(low(h), w["mixer/wq"]) + w.get("mixer/bq", 0.0)
    k = _mm(low(h), w["mixer/wk"]) + w.get("mixer/bk", 0.0)
    v = _mm(low(h), w["mixer/wv"]) + w.get("mixer/bv", 0.0)
    b, t = x.shape[:2]
    hd, kv, dh = d["n_heads"], d["n_kv_heads"], d["head_dim"]
    pos = jnp.arange(t)
    q = rope(q.reshape(b, t, hd, dh), pos, d["rope_theta"])
    k = rope(k.reshape(b, t, kv, dh), pos, d["rope_theta"])
    res = attention(q, k, v.reshape(b, t, kv, dh), colsum=colsum)
    att, col = res if colsum else (res, None)
    att = att.reshape(b, t, hd * dh)
    x = x + _mm(low(att), w["mixer/wo"])
    h2 = rms(x, w["ffn_norm"], d["norm_eps"])
    hid = jax.nn.silu(_mm(low(h2), w["ffn/wi"])) * _mm(low(h2), w["ffn/wu"])
    x = x + _mm(low(hid), w["ffn/wd"])
    if caps:
        return x, col, {"h": h, "att": att, "h2": h2, "hid": hid}
    return x, col


# ------------------------------------------------------------------ serving

BUCKET = 1024


def serve_logits(config: dict, seed: int, packed, seqs, rows, *,
                 max_rows: int, act_dtype=None):
    """float32 logits at the positions ``rows[i]`` of each token sequence
    ``seqs[i]``, concatenated, of the model with ``packed=(bits,
    group_size)`` weights.  Each sequence runs alone, padded at its end to
    a multiple of ``BUCKET`` tokens (causal: the padding changes nothing
    before it), and its rows to ``max_rows``, so that the compiled programs
    are the same from run to run; layers outermost, so that each layer's
    weights are made once."""
    d = modelcfg.hf_dims(config)
    shapes = modelcfg.layer_shapes(config)

    @jax.jit
    def layer_w(li):
        return weights.layer_weights(seed, shapes, li, packed=packed)

    @jax.jit
    def layer(w, x):
        return block(w, x, d, act_dtype)[0]

    @jax.jit
    def embed(tok):
        e = weights.top_weight(seed, "embed", (d["vocab"], d["d_model"]))
        return e[tok][None]

    @jax.jit
    def head(x, r):
        g = weights.top_weight(seed, "final_norm", (d["d_model"],))
        hw = weights.top_weight(seed, "head", (d["d_model"], d["vocab"]))
        return _mm(lower(rms(x[0][r], g, d["norm_eps"]), act_dtype), hw)

    xs = []
    for s in seqs:
        pad = -len(s) % BUCKET
        xs.append(embed(jnp.asarray(np.pad(np.asarray(s, np.int32),
                                           (0, pad)))))
    t0 = time.perf_counter()
    for li in range(d["n_layers"]):
        w = layer_w(jnp.int32(li))
        xs = [layer(w, x) for x in xs]
        del w
        if li == 0:
            jax.block_until_ready(xs)
            t1 = time.perf_counter()
    jax.block_until_ready(xs)
    print(f"reference: layer 0 (compiles) {t1 - t0:.2f} s, layers 1+ "
          f"{time.perf_counter() - t1:.2f} s", flush=True)
    out = []
    for x, r in zip(xs, rows):
        rp = np.zeros(max_rows, np.int32)
        rp[:len(r)] = r
        out.append(head(x, jnp.asarray(rp))[:len(r)])
    return jnp.concatenate(out)


# ----------------------------------------------------------------- quantizer


def importance(col, r_min: float, r_max: float):
    """(B, T) column mass -> per-sample importance in [r_min, r_max]."""
    lo, hi = col.min(-1, keepdims=True), col.max(-1, keepdims=True)
    return r_min + (col - lo) / jnp.maximum(hi - lo, 1e-12) * (r_max - r_min)


def gptq(w, h, bits: int, group_size: int, damp: float, block: int = 128):
    """GPTQ (Frantar et al. 2023, lazy batch updates) of w (d_in, d_out)
    against the Hessian h (d_in, d_in), in float32.  Rows are quantized in
    order; each row's error, divided by the diagonal of the upper Cholesky
    factor U of H^-1, is fed back into the later rows of its block at
    once and into the rows after the block by one product per block.  A
    group's symmetric scale is taken from its rows as compensated when
    the group starts (groups lie inside blocks)."""
    d_in, d_out = w.shape
    maxq = 2 ** bits - 1
    mid = (maxq + 1) // 2
    diag = jnp.diag(h)
    dead = diag <= 0
    h = h + jnp.diag(jnp.where(dead, 1.0, 0.0))
    h = h + damp * jnp.mean(jnp.where(dead, 0.0, diag)) * jnp.eye(d_in)
    hinv = jnp.linalg.inv(h)
    u = jnp.linalg.cholesky(0.5 * (hinv + hinv.T)).T   # H^-1 = U^T U
    rows = jnp.arange(block)

    def block_step(w, b):
        i0 = b * block
        wb = jax.lax.dynamic_slice_in_dim(w, i0, block, 0)
        ub = jax.lax.dynamic_slice(u, (i0, i0), (block, block))

        def row(i, st):
            wb, qb, eb, scale = st
            g0 = (i // group_size) * group_size
            grp = jax.lax.dynamic_slice_in_dim(wb, g0, group_size, 0)
            new = jnp.maximum(jnp.max(jnp.abs(grp), 0) / (maxq / 2), 1e-9)
            scale = jnp.where(i % group_size == 0, new, scale)
            x = wb[i]
            q = scale * (jnp.clip(jnp.round(x / scale) + mid, 0, maxq) - mid)
            err = (x - q) / ub[i, i]
            wb = wb - jnp.where(rows > i, ub[i], 0.0)[:, None] * err[None]
            return wb, qb.at[i].set(q), eb.at[i].set(err), scale

        z = jnp.zeros((block, d_out), jnp.float32)
        _, qb, eb, _ = jax.lax.fori_loop(
            0, block, row, (wb, z, z, jnp.ones((d_out,), jnp.float32)))
        later = (jnp.arange(d_in) >= i0 + block)[:, None]
        u_rows = jax.lax.dynamic_slice_in_dim(u, i0, block, 0)
        w = w - jnp.where(later, _mm(u_rows.T, eb), 0.0)
        return jax.lax.dynamic_update_slice_in_dim(w, qb, i0, 0), None

    w, _ = jax.lax.scan(block_step, w.astype(jnp.float32),
                        jnp.arange(d_in // block))
    return w


def proxy_loss(w, q, h):
    """tr((W - Q)^T H (W - Q)), float32 at full precision."""
    e = (w - q).astype(jnp.float32)
    return jnp.sum(e * _mm(h, e))


INPUT_OF = {"mixer/wq": "h", "mixer/wk": "h", "mixer/wv": "h",
            "mixer/wo": "att", "ffn/wi": "h2", "ffn/wu": "h2",
            "ffn/wd": "hid"}


def rsq_layers(config: dict, seed: int, calib, n_check: int, *, bits: int,
               group_size: int, damp: float, r_min: float, r_max: float,
               batch: int, act_dtype=None):
    """RSQ on layers 0 .. n_check-1 of the dense model from ``seed``.

    calib: (N, T) int32 tokens.  Yields, per layer, ``(layer, {path: w},
    {path: h}, {path: q})``: the original weights, the reference Hessians
    and the reference's quantized weights."""
    d = modelcfg.hf_dims(config)
    shapes = modelcfg.layer_shapes(config)
    n = calib.shape[0]

    @jax.jit
    def embed(tok):
        e = weights.top_weight(seed, "embed", (d["vocab"], d["d_model"]))
        return e[tok]

    @jax.jit
    def capture(w, x, hs):
        x = lower(x, act_dtype)
        _, col, caps = block(w, x, d, act_dtype, colsum=True, caps=True)
        r = importance(col, r_min, r_max)[..., None]
        out = {}
        for name, a in caps.items():
            a = (lower(a, act_dtype) * r).reshape(-1, a.shape[-1])
            out[name] = hs[name] + 2.0 * jnp.einsum(
                "nd,ne->de", a, a, precision=HI)
        return out

    @jax.jit
    def apply(w, x):
        return block(w, lower(x, act_dtype), d, act_dtype)[0]

    solve = jax.jit(functools.partial(gptq, bits=bits, group_size=group_size,
                                      damp=damp))
    xs = [embed(calib[i:i + batch]) for i in range(0, n, batch)]
    dims = {"h": d["d_model"], "att": d["n_heads"] * d["head_dim"],
            "h2": d["d_model"], "hid": d["d_ff"]}
    for li in range(n_check):
        w = weights.layer_weights(seed, shapes, li)
        hs = {k: jnp.zeros((v, v), jnp.float32) for k, v in dims.items()}
        for x in xs:
            hs = capture(w, x, hs)
        q = {p: solve(w[p], hs[INPUT_OF[p]]) for p in INPUT_OF}
        yield li, {p: w[p] for p in INPUT_OF}, {p: hs[INPUT_OF[p]]
                                                for p in INPUT_OF}, q
        wq = dict(w, **q)
        xs = [apply(wq, x) for x in xs]
