"""Seeded weights, made on the device in one jitted call.

Every weight is a function of ``(seed, leaf path, layer)`` alone, so the
plain references regenerate any layer by itself, in their own precision,
without taking anything from the program under test.

* ``dense_*``: bf16 weights of a model as the quantizer receives it
  (norm gains 1, as after norm fusion and rotation: a rotated random
  Gaussian weight is again a random Gaussian weight).
* ``packed_*``: the serving artifact's own format, as ``RSQPipeline``
  writes it for a symmetric quantizer: per weight (d_in, d_out), uint32
  words holding ``32 // bits`` codes along d_in (code ``i`` of word ``w``
  is row ``w * vpw + i``, in bits ``[i * bits, (i + 1) * bits)``),
  float32 scales per group of ``group_size`` rows and output column, and
  float32 zeros equal to ``2 ** (bits - 1)``.  The weight is
  ``scale * (code - zero)``.  Codes are uniform; scales are set so that a
  weight's standard deviation is ``d_in ** -0.5``.

Projections have standard deviation ``d_in ** -0.5``, except queries and
keys at ``QK_STD`` of that: attention scores then spread by about half a
unit.  With full-width random queries and keys, 40 layers of attention
over random tokens are chaotic, and a perturbation as small as bfloat16
rounding turns the last hidden state of some positions round (cosine
about -0.95 with the exact one); softer scores keep every precision's
logits within its rounding of the exact ones (see PERF.md).
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

EMBED_STD = 0.02
BIAS_STD = 0.1


def root_key(seed: int):
    """A JAX key from any non-negative whole number (the driver's seeds
    pass 32 bits)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def leaf_key(seed: int, path: str, layer: int = 0):
    k = jax.random.fold_in(root_key(seed), zlib.crc32(path.encode()))
    return jax.random.fold_in(k, layer)


def path_str(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)


def kind(path: str, ndim: int) -> str:
    """How a leaf of the model's parameter tree is made."""
    name = path.rsplit("/", 1)[-1]
    if name.endswith("norm"):
        return "ones"
    if name == "embed":
        return "embed"
    if name in ("bq", "bk", "bv"):
        return "bias"
    if path.startswith("groups/") and ndim == 3:
        return "linear"
    if name == "head":
        return "head"
    raise ValueError(f"no rule for parameter {path!r}")


QK_STD = 0.7


def std_factor(path: str) -> float:
    return QK_STD if path.rsplit("/", 1)[-1] in ("wq", "wk") else 1.0


def dense_leaf(key, k: str, shape, dtype=jnp.bfloat16, factor: float = 1.0):
    """One leaf (one layer's slice for group leaves)."""
    if k == "ones":
        return jnp.ones(shape, dtype)
    std = {"embed": EMBED_STD, "bias": BIAS_STD}.get(k, shape[0] ** -0.5)
    std *= factor
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def packed_leaf(key, d_in: int, d_out: int, bits: int, group_size: int,
                factor: float = 1.0):
    """(words, scale, zero) of one layer's weight."""
    vpw = 32 // bits
    kc, ks = jax.random.split(key)
    words = jax.random.bits(kc, (d_in // vpw, d_out), jnp.uint32)
    # uniform codes minus the zero: variance ((2^bits)^2 - 1) / 12
    code_std = float(np.sqrt(((2 ** bits) ** 2 - 1) / 12.0))
    scale = (jax.random.uniform(ks, (d_in // group_size, d_out), jnp.float32,
                                0.75, 1.25) * (factor * d_in ** -0.5
                                               / code_std))
    zero = jnp.full_like(scale, float(2 ** (bits - 1)))
    return words, scale, zero


def unpack(words, bits: int):
    """(n_words, d_out) uint32 -> (n_words * vpw, d_out) int32 codes."""
    vpw = 32 // bits
    shifts = (jnp.arange(vpw, dtype=jnp.uint32) * bits)[None, :, None]
    codes = (words[:, None, :] >> shifts) & jnp.uint32(2 ** bits - 1)
    return codes.reshape(-1, words.shape[-1]).astype(jnp.int32)


def dequant(words, scale, zero, bits: int, group_size: int):
    """float32 weight of one packed leaf."""
    codes = unpack(words, bits).astype(jnp.float32)
    g = scale.shape[0]
    w = (codes.reshape(g, group_size, -1) - zero[:, None]) * scale[:, None]
    return w.reshape(g * group_size, -1)


def _shapes(model):
    flat, treedef = jax.tree_util.tree_flatten_with_path(model.param_shapes())
    return [(path_str(p), s) for p, s in flat], treedef


def dense_params(model, seed: int):
    """The model's whole bf16 parameter tree, in one jitted call."""
    leaves, treedef = _shapes(model)

    def make():
        out = []
        for path, s in leaves:
            k = kind(path, len(s.shape))
            if path.startswith("groups/"):
                keys = jnp.stack([leaf_key(seed, path, i)
                                  for i in range(s.shape[0])])
                out.append(jax.vmap(lambda kk: dense_leaf(
                    kk, k, s.shape[1:], s.dtype, std_factor(path)))(keys))
            else:
                out.append(dense_leaf(leaf_key(seed, path), k, s.shape,
                                      s.dtype))
        return out

    return jax.tree_util.tree_unflatten(treedef, jax.jit(make)())


def packed_params(model, seed: int, bits: int, group_size: int, wrap):
    """Serving params: every stacked block linear as packed codes, the
    rest bf16, in one jitted call.  ``wrap(words, scale, zero, d_in)``
    builds the program's packed-weight node."""
    leaves, treedef = _shapes(model)

    def make():
        out = []
        for path, s in leaves:
            k = kind(path, len(s.shape))
            if k == "linear":
                n, d_in, d_out = s.shape
                keys = jnp.stack([leaf_key(seed, path, i) for i in range(n)])
                out.append(jax.vmap(lambda kk: packed_leaf(
                    kk, d_in, d_out, bits, group_size,
                    std_factor(path)))(keys))
            elif path.startswith("groups/"):
                keys = jnp.stack([leaf_key(seed, path, i)
                                  for i in range(s.shape[0])])
                out.append(jax.vmap(lambda kk: dense_leaf(
                    kk, k, s.shape[1:], s.dtype))(keys))
            else:
                out.append(dense_leaf(leaf_key(seed, path), k, s.shape,
                                      s.dtype))
        return out

    made = jax.jit(make)()
    out = []
    for (path, s), leaf in zip(leaves, made):
        out.append(wrap(*leaf, s.shape[-2]) if isinstance(leaf, tuple)
                   else leaf)
    return jax.tree_util.tree_unflatten(treedef, out)


# ------------------------------------------------ one layer, for references


def layer_weights(seed: int, names: dict, layer: int, *, packed=None):
    """float32 weights of decoder layer ``layer``, regenerated alone.

    ``names`` maps each parameter path below ``groups/b0/`` to its shape
    without the layer axis; ``packed=(bits, group_size)`` dequantizes the
    linear leaves from the artifact format."""
    out = {}
    for sub, shape in names.items():
        path = f"groups/b0/{sub}"
        k = kind(path, len(shape) + 1)
        key = leaf_key(seed, path, layer)
        if k == "linear" and packed is not None:
            bits, gs = packed
            w, s, z = packed_leaf(key, shape[0], shape[1], bits, gs,
                                  std_factor(path))
            out[sub] = dequant(w, s, z, bits, gs)
        else:
            out[sub] = dense_leaf(key, k, shape,
                                  factor=std_factor(path)).astype(jnp.float32)
    return out


def top_weight(seed: int, path: str, shape):
    """float32 top-level leaf (embed, head, final_norm)."""
    return dense_leaf(leaf_key(seed, path), kind(path, len(shape)),
                      shape).astype(jnp.float32)
