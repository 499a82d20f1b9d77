"""Step 1 of RSQ: Rotate — randomized-Hadamard orthogonal transforms of the
residual stream (QuaRot / SliceGPT computational invariance).

Convention (see DESIGN.md §8): the stream is rotated ``x -> x @ Q``; weights
that *consume* the stream become ``Qᵀ W``; weights that *produce* it become
``W Q``; the embedding table becomes ``E Q`` (tied LM heads follow for free).
RMSNorm commutes with orthogonal Q only when its scale is 1, so ``fuse_norms``
must run first (it folds every norm's γ into the consuming weights).

Non-power-of-two dims use the Kronecker factorization H_{2^k} (x) Q_m with a
random orthogonal Q_m — keeping the fast-Hadamard structure on the 2^k part
(see kernels/hadamard for the TPU kernel).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig

# Folding Q into the weights runs in f32 at full precision: a TPU's
# default f32 dot takes one bf16 pass, which would perturb every weight
# by ~2^-9 before quantization ever sees it.
HIGHEST = jax.lax.Precision.HIGHEST
_mm = functools.partial(jnp.matmul, precision=HIGHEST)


def hadamard_matrix(n: int, dtype=jnp.float32) -> jax.Array:
    """Orthonormal Hadamard matrix, n must be a power of two."""
    assert n & (n - 1) == 0 and n > 0, f"{n} not a power of two"
    h = jnp.ones((1, 1), dtype)
    while h.shape[0] < n:
        h = jnp.block([[h, h], [h, -h]])
    return h / jnp.sqrt(jnp.asarray(n, dtype))


def _pow2_factor(n: int) -> tuple[int, int]:
    k = 1
    while n % (2 * k) == 0:
        k *= 2
    return k, n // k


def random_orthogonal(key, n: int, dtype=jnp.float32) -> jax.Array:
    a = jax.random.normal(key, (n, n), jnp.float32)
    q, r = jnp.linalg.qr(a)
    # sign-fix for a uniform (Haar) distribution
    q = q * jnp.sign(jnp.diag(r))[None, :]
    return q.astype(dtype)


def random_hadamard(key, n: int, dtype=jnp.float32) -> jax.Array:
    """Randomized (Hadamard (x) orthogonal) rotation with a random ±1
    diagonal: Q = diag(s) · (H_{2^k} (x) Q_m)."""
    k2, m = _pow2_factor(n)
    kd, km = jax.random.split(key)
    h = hadamard_matrix(k2, dtype)
    if m > 1:
        q_m = random_orthogonal(km, m, dtype)
        h = jnp.kron(h, q_m)
    s = jax.random.rademacher(kd, (n,), jnp.float32).astype(dtype)
    return s[:, None] * h


# ----------------------------------------------------------------- norm fuse


def _scale_in(w, g):
    """W' = diag(g) @ W for a stream-consuming weight (d_in, d_out)."""
    return (w.astype(jnp.float32) * g.astype(jnp.float32)[:, None]).astype(w.dtype)


# weights consuming the residual stream, per block sub-module
_MIXER_IN = ("wq", "wk", "wv", "wq_a", "wkv_a", "wzx", "wbc", "wdt")
_MIXER_IN_NOLORA = ("wq",)  # MLA without q_lora uses "wq" directly
_MIXER_OUT = ("wo", "out_proj")
_FFN_IN = ("wi", "wu")
_FFN_OUT = ("wd",)


def fuse_norms_block(p: dict, cfg: ModelConfig) -> dict:
    """Fold every RMSNorm γ of one block into its consuming weights."""
    p = jax.tree.map(lambda x: x, p)  # shallow-ish copy
    g = p["mixer_norm"].astype(jnp.float32)
    mixer = dict(p["mixer"])
    for name in _MIXER_IN:
        if name in mixer:
            mixer[name] = _scale_in(mixer[name], g)
    p["mixer"] = mixer
    p["mixer_norm"] = jnp.ones_like(p["mixer_norm"])
    if "cross_norm" in p:
        gc = p["cross_norm"].astype(jnp.float32)
        cross = dict(p["cross"])
        cross["wq"] = _scale_in(cross["wq"], gc)
        p["cross"] = cross
        p["cross_norm"] = jnp.ones_like(p["cross_norm"])
    if "ffn_norm" in p:
        gf = p["ffn_norm"].astype(jnp.float32)
        ffn = dict(p["ffn"])
        for name in _FFN_IN:
            if name in ffn:
                ffn[name] = _scale_in(ffn[name], gf)
        if "router" in ffn:
            ffn["router"] = _scale_in(ffn["router"], gf)
            experts = dict(ffn["experts"])
            for name in ("wi", "wu"):
                experts[name] = (experts[name].astype(jnp.float32)
                                 * gf[None, :, None]).astype(experts[name].dtype)
            ffn["experts"] = experts
            if "shared" in ffn:
                sh = dict(ffn["shared"])
                for name in _FFN_IN:
                    sh[name] = _scale_in(sh[name], gf)
                ffn["shared"] = sh
        p["ffn"] = ffn
        p["ffn_norm"] = jnp.ones_like(p["ffn_norm"])
    # MLA internal norms fold into the up-projections
    if "q_norm" in p.get("mixer", {}):
        mixer = dict(p["mixer"])
        mixer["wq_b"] = _scale_in(mixer["wq_b"], mixer["q_norm"])
        mixer["q_norm"] = jnp.ones_like(mixer["q_norm"])
        p["mixer"] = mixer
    if "kv_norm" in p.get("mixer", {}):
        mixer = dict(p["mixer"])
        mixer["wkv_b"] = _scale_in(mixer["wkv_b"], mixer["kv_norm"])
        mixer["kv_norm"] = jnp.ones_like(mixer["kv_norm"])
        p["mixer"] = mixer
    return p


def rotate_block(p: dict, cfg: ModelConfig, meta, q: jax.Array,
                 q_media: jax.Array | None = None) -> dict:
    """Apply the stream rotation to one block (norms must be fused first).

    ``meta``: the block's BlockMeta — cross-attention mixers consume the
    (unrotated or q_media-rotated) media stream on their K/V side, so only
    their wq/wo touch the residual rotation."""
    qf = q.astype(jnp.float32)
    p = jax.tree.map(lambda x: x, p)

    def rot_in(w):  # (d_model, d_out) -> Qᵀ W
        return _mm(qf.T, w.astype(jnp.float32)).astype(w.dtype)

    def rot_out(w):  # (d_in, d_model) -> W Q
        return _mm(w.astype(jnp.float32), qf).astype(w.dtype)

    def rot_cross(c):
        c = dict(c)
        c["wq"] = rot_in(c["wq"])
        c["wo"] = rot_out(c["wo"])
        if q_media is not None:
            qm = q_media.astype(jnp.float32)
            for name in ("wk", "wv"):
                c[name] = _mm(qm.T, c[name].astype(jnp.float32)
                              ).astype(c[name].dtype)
        return c

    if meta.mixer == "cross":
        p["mixer"] = rot_cross(p["mixer"])
    else:
        mixer = dict(p["mixer"])
        for name in _MIXER_IN:
            if name in mixer:
                mixer[name] = rot_in(mixer[name])
        for name in _MIXER_OUT:
            if name in mixer:
                mixer[name] = rot_out(mixer[name])
        p["mixer"] = mixer
    if "cross" in p:
        p["cross"] = rot_cross(p["cross"])

    if p.get("ffn") is not None:
        ffn = dict(p["ffn"])
        for name in _FFN_IN:
            if name in ffn:
                ffn[name] = rot_in(ffn[name])
        for name in _FFN_OUT:
            if name in ffn:
                ffn[name] = rot_out(ffn[name])
        if "router" in ffn:
            ffn["router"] = rot_in(ffn["router"])
            experts = dict(ffn["experts"])
            experts["wi"] = jnp.einsum(
                "de,aef->adf", qf.T, experts["wi"].astype(jnp.float32),
                precision=HIGHEST).astype(experts["wi"].dtype)
            experts["wu"] = jnp.einsum(
                "de,aef->adf", qf.T, experts["wu"].astype(jnp.float32),
                precision=HIGHEST).astype(experts["wu"].dtype)
            experts["wd"] = jnp.einsum(
                "afd,de->afe", experts["wd"].astype(jnp.float32), qf,
                precision=HIGHEST).astype(experts["wd"].dtype)
            ffn["experts"] = experts
            if "shared" in ffn:
                sh = dict(ffn["shared"])
                for name in _FFN_IN:
                    sh[name] = rot_in(sh[name])
                sh["wd"] = rot_out(sh["wd"])
                ffn["shared"] = sh
        p["ffn"] = ffn
    return p


def rotate_model(params: dict, cfg: ModelConfig, model, key) -> tuple[dict, dict]:
    """Fuse norms then rotate the whole model. Returns (params, rotations).

    Enc-dec models get separate rotations per stream (Q_dec, Q_enc); the
    decoder's cross-attention K/V side uses Q_enc as q_media.  VLM media is
    an external stub -> media side stays unrotated (q_media=None)."""
    kd, ke = jax.random.split(jax.random.fold_in(key, 7))
    q = random_hadamard(kd, cfg.d_model)
    q_enc = random_hadamard(ke, cfg.d_model) if cfg.family == "encdec" else None
    params = dict(params)

    # ---- fuse norms everywhere
    if "prefix" in params:
        params["prefix"] = [fuse_norms_block(b, cfg) for b in params["prefix"]]
    params["groups"] = jax.vmap(
        lambda g: {k: fuse_norms_block(g[k], cfg) for k in g})(params["groups"])
    head = params.get("head")
    fg = params["final_norm"].astype(jnp.float32)
    if head is not None:
        params["head"] = _scale_in(head, fg)
    else:
        # tied embeddings: head = embedᵀ -> fold γ into the embedding copy
        # is NOT output-preserving for the embedding side; keep a separate
        # head instead
        params["head"] = _scale_in(params["embed"].T, fg)
    params["final_norm"] = jnp.ones_like(params["final_norm"])
    if "encoder" in params:
        enc = dict(params["encoder"])
        enc["groups"] = jax.vmap(
            lambda g: {k: fuse_norms_block(g[k], cfg) for k in g})(enc["groups"])
        # the encoder final norm feeds every decoder cross-attn K/V: fold its
        # γ into those consumers so the encoder stream can be rotated
        ge = enc["final_norm"].astype(jnp.float32)

        def fold_cross(b):
            b = dict(b)
            cross = dict(b["cross"])
            cross["wk"] = _scale_in(cross["wk"], ge)
            cross["wv"] = _scale_in(cross["wv"], ge)
            b["cross"] = cross
            return b

        params["groups"] = jax.vmap(
            lambda g: {k: fold_cross(g[k]) for k in g})(params["groups"])
        enc["final_norm"] = jnp.ones_like(enc["final_norm"])
        params["encoder"] = enc

    # ---- rotate
    media_q = q_enc if cfg.family == "encdec" else None
    metas = model.group_metas
    if "prefix" in params:
        params["prefix"] = [
            rotate_block(b, cfg, m, q, media_q)
            for b, m in zip(params["prefix"], model.prefix_metas)]
    params["groups"] = jax.vmap(
        lambda g: {f"b{i}": rotate_block(g[f"b{i}"], cfg, metas[i], q, media_q)
                   for i in range(len(metas))})(params["groups"])
    params["embed"] = _mm(params["embed"].astype(jnp.float32),
                          q.astype(jnp.float32)).astype(params["embed"].dtype)
    params["head"] = _mm(q.astype(jnp.float32).T,
                         params["head"].astype(jnp.float32)
                         ).astype(params["head"].dtype)
    if "encoder" in params and q_enc is not None:
        enc = dict(params["encoder"])
        em = model.enc_metas[0]
        enc["groups"] = jax.vmap(
            lambda g: {"b0": rotate_block(g["b0"], cfg, em, q_enc)})(enc["groups"])
        params["encoder"] = enc
        # encoder input is a frontend stub: materialize the rotation the real
        # conv frontend's output projection would absorb
        params["frame_proj"] = q_enc.astype(params["embed"].dtype)
    rotations = {"q": q, "q_enc": q_enc}
    return params, rotations
