"""Public wrapper: weight-only quantized GEMM for serving.

``PackedWeight`` is the serving-side parameter type for a quantized dense
projection: a registered JAX pytree whose leaves are the packed uint32
codes plus the per-group ``(scale, zero)`` and whose aux data carries the
static quant geometry ``(bits, group_size, d_in)``.  Because it is a
pytree it drops into a param tree anywhere an fp ``(d_in, d_out)`` matrix
used to live — ``jax.jit``/``lax.scan``/``jax.vmap`` trace straight
through it (a stacked group of layers is simply a ``PackedWeight`` whose
leaves carry a leading layer axis, sliced by the scan like any other
param), and the model's ``linear`` dispatcher
(``models.layers.linear``) routes it through :func:`quant_matmul` instead
of ``x @ w``.

Use ``pack_weight`` once offline (after the RSQ pipeline), or build one
straight from a packed serving artifact with
``packed_weight_from_artifact``; then ``quant_matmul(x, packed)`` at
serving time.  Only power-of-two bit widths ride the packed kernel (int3
packing wastes 2 bits/word and breaks the k-tiling alignment; 3-bit
deployments dequantize via ref — documented in DESIGN.md).  Decode-shape
inputs (m = batch, not a sublane multiple of 8) are padded up to 8 inside
the wrapper and the output sliced back, so single-token decode stays on
the Pallas kernel instead of bouncing to the slow ref path.

Kernel policy (shared by every entry point here): the Pallas kernel on
TPU, the fused-XLA ref elsewhere — same as the gram kernel.  The
``REPRO_QMM_KERNEL`` env var overrides the default (``1`` forces the
kernel — interpret-mode off-TPU, a correctness/CI tool; ``0`` forces the
ref); an explicit ``use_kernel=`` argument beats both.

Mesh-sharded weights (``PackedWeight.mesh_sharded``, set by
``checkpoint.packed.load_packed_forward_params``) used to be ref-only:
the Pallas kernel is an opaque custom call GSPMD would service by
all-gathering the full codes per device.  They now run the fused kernel
through ``shard_map`` over the model axis instead (:func:`quant_matmul`
routes there automatically): the codes are d_out-sharded, so each shard
runs the kernel on its local (k, n/axis) tile with *zero* weight
collectives — no weight ever moves, which is the decode-serving
layout's whole point.  The GSPMD-partitionable ref remains
the fallback for meshless callers, non-TPU backends (unless the kernel is
forced), misaligned local tiles, 3-bit, and expert stacks under ``vmap``;
every such fallback taken where the policy asked for the kernel is
counted in ``kernels.demotions``.
"""
from __future__ import annotations

import dataclasses
import os

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.quantizer import QuantSpec, pack_codes
from repro.kernels import demotions
from repro.kernels.quant_matmul.kernel import (quant_matmul_pallas,
                                               quant_matmul_t_pallas)
from repro.kernels.quant_matmul.ref import (quant_matmul_ref,
                                            quant_matmul_t_ref)


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _kernel_default() -> bool:
    """Backend kernel policy with the ``REPRO_QMM_KERNEL`` env override."""
    env = os.environ.get("REPRO_QMM_KERNEL")
    if env is not None:
        return env.strip().lower() not in ("", "0", "false", "off")
    return jax.default_backend() == "tpu"


def _smap(fn, mesh, in_specs, out_specs):
    """shard_map with replication checking off: the Pallas custom call has
    no replication rule for the checker to consult."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


@jax.tree_util.register_pytree_with_keys_class
@dataclasses.dataclass
class PackedWeight:
    """Packed quantized projection: pytree leaves (w_packed, scale, zero),
    static aux (bits, group_size, d_in).

    ``w_packed``: (..., d_in // vpw, d_out) uint32; ``scale``/``zero``:
    (..., d_in // group_size, d_out).  Leading batch axes (stacked layer
    groups, expert stacks) are carried by the leaves and stay invisible to
    the static aux — exactly what lets a stacked ``PackedWeight`` ride a
    ``lax.scan`` over layers or a ``jax.vmap`` over experts."""

    w_packed: jax.Array  # (k // vpw, n) uint32
    scale: jax.Array  # (k // gs, n)
    zero: jax.Array
    bits: int
    group_size: int
    d_in: int
    # codes partitioned across a live mesh (set by
    # checkpoint.packed.load_packed_forward_params): ``mesh``/``mesh_axis``
    # name the d_out shard placement so quant_matmul can wrap the Pallas
    # kernel in shard_map over that axis (per-shard fused GEMMs, no code
    # all-gather); when the shard_map route can't run (no kernel, ragged
    # local tile, expert stacks under vmap) the flag keeps the codes on
    # the GSPMD-partitionable ref GEMM instead of the opaque custom call.
    mesh_sharded: bool = False
    mesh: Mesh | None = None
    mesh_axis: str | None = None

    def tree_flatten_with_keys(self):
        children = tuple(
            (jax.tree_util.GetAttrKey(f), getattr(self, f))
            for f in ("w_packed", "scale", "zero"))
        return children, (self.bits, self.group_size, self.d_in,
                          self.mesh_sharded, self.mesh, self.mesh_axis)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    @property
    def nbytes(self) -> int:
        """Resident HBM bytes of the packed representation."""
        return sum(int(a.size) * a.dtype.itemsize
                   for a in (self.w_packed, self.scale, self.zero))


def is_packed(w) -> bool:
    return isinstance(w, PackedWeight)


def pack_weight(q: jax.Array, scale: jax.Array, zero: jax.Array,
                spec: QuantSpec) -> PackedWeight:
    d_in = q.shape[-2]
    gs = d_in if spec.group_size == -1 else spec.group_size
    return PackedWeight(
        w_packed=pack_codes(q, spec.bits), scale=scale, zero=zero,
        bits=spec.bits, group_size=gs, d_in=d_in)


def packed_weight_from_artifact(entry: dict, em: dict,
                                spec: dict) -> PackedWeight:
    """Packed-artifact entry (``checkpoint.packed``) -> ``PackedWeight``.

    The codes move host->device still packed and ``quant_matmul`` consumes
    them directly — the serving path never unpacks on host.  ``entry`` is
    one ``load_packed_artifact`` entry, ``em``/``spec`` its per-entry and
    artifact-level metadata.  Expert stacks arrive with a leading (E,)
    axis on every leaf and dispatch through the vmapped kernel
    (``models.layers.linear``)."""
    codes = jnp.asarray(entry["codes"])
    assert codes.ndim in (2, 3), \
        f"dense (k/vpw, n) or expert-stacked (E, k/vpw, n) codes: {codes.shape}"
    return PackedWeight(
        w_packed=codes, scale=jnp.asarray(entry["scale"]),
        zero=jnp.asarray(entry["zero"]), bits=int(spec["bits"]),
        group_size=int(em["group_size"]), d_in=int(em["d_in"]))


def _k_tile(k: int, group_size: int) -> int:
    """Reduction tile of the packed GEMMs: divides k, holds whole quant
    groups, and makes the (tile // group_size, ·) scale/zero block legal
    for Mosaic — a multiple of the 8-row sublane tile, or the whole axis
    (at the default group 128 that means 1024-wide tiles, or k itself up
    to 512).  0 when no tile <= 2048 does all three (caller takes the
    ref)."""
    for t in (512, 1024, 2048, 256, 128):
        if (k % t == 0 and t % group_size == 0
                and ((t // group_size) % 8 == 0 or t == k)):
            return t
    return 0


def _shard_map_matmul(x: jax.Array, pw: PackedWeight) -> jax.Array | None:
    """Mesh-sharded fused route: the Pallas kernel per d_out shard.

    The codes (and the per-group scale/zero) are partitioned on their last
    axis over ``pw.mesh_axis``; ``shard_map`` hands each device its local
    (k/vpw, n_local) tile and the kernel runs on it exactly as in the
    unsharded case — no code all-gather (the very collective GSPMD would
    insert around the opaque custom call), no output collective (the
    result stays d_out-sharded, the decode activation layout).  The
    activation is the only replicated operand — a deliberate trade: row-
    sharding m over the data axes would save the dp-fold duplicate GEMM
    work at prefill, but XLA's GEMM accumulation order depends on m, so
    the per-row results stop being bit-identical to the GSPMD ref and
    greedy tokens drift off the dequantized reference (measured:
    ~4e-5 logit deltas, token flips within 8 steps).  Decode — the shape
    this kernel exists for — has a tiny m where replication is the right
    layout anyway; revisiting prefill row-sharding under a tolerance-
    based parity contract is a recorded ROADMAP item.  Returns None when
    the local tile can't align to the kernel (caller falls back to the
    ref GEMM, which partitions under GSPMD like any GEMM)."""
    mesh, axis = pw.mesh, pw.mesh_axis
    n = pw.w_packed.shape[1]
    axis_size = mesh.shape[axis]
    if n % axis_size:
        return None
    n_loc = n // axis_size
    m, k = x.shape
    vpw = 32 // pw.bits
    k_blk = _k_tile(k, pw.group_size)
    aligned = (pw.d_in % vpw == 0 and k % 128 == 0 and n_loc % 128 == 0
               and k_blk)
    if not aligned:
        return None
    m_pad = (-m) % 8
    if m_pad:
        x = jnp.concatenate([x, jnp.zeros((m_pad, k), x.dtype)], axis=0)
    m_blk = 128
    while x.shape[0] % m_blk:
        m_blk //= 2
    n_blk = 256
    while n_loc % n_blk:
        n_blk //= 2

    def local(xs, wq, sc, zr):
        return quant_matmul_pallas(
            xs, wq, sc, zr, bits=pw.bits, group_size=pw.group_size,
            m_blk=m_blk, n_blk=n_blk, k_blk=k_blk, interpret=_interpret())

    out = _smap(local, mesh,
                in_specs=(P(None, None), P(None, axis), P(None, axis),
                          P(None, axis)),
                out_specs=P(None, axis))(x, pw.w_packed, pw.scale, pw.zero)
    return out[:m] if m_pad else out


def quant_matmul(x: jax.Array, pw: PackedWeight, *,
                 use_kernel: bool | None = None,
                 shard: bool = True) -> jax.Array:
    """y = x @ dequant(pw).  x: (m, k) -> (m, n), fp32 accumulation.

    Decode shapes (m not a multiple of the 8-row sublane tile) are padded
    up to 8 and the output sliced back — a single generated token per
    sequence must not demote the GEMM to the unfused ref path, since the
    packed kernel's 16/bits HBM-traffic win is exactly what decode (a
    memory-bound shape) is serving for.

    ``use_kernel``: None (default) auto-selects the Pallas kernel on TPU
    and the jnp ref elsewhere — the same policy as the gram kernel
    (``RSQConfig.use_gram_kernel``); the ``REPRO_QMM_KERNEL`` env var
    overrides the default (interpret mode off-TPU is a correctness tool
    that would serialize the serving hot loop).  Mesh-sharded codes
    (``pw.mesh_sharded``) run the kernel *per shard* under shard_map over
    the model axis (see :func:`_shard_map_matmul`) — an opaque custom
    call must never reach GSPMD, which would all-gather the codes —
    falling back to the ref when the local tile is ragged.  ``shard=False``
    disables the shard_map route (the vmapped expert-stack dispatch sets
    it: shard_map can't nest under vmap).  The ref is a fused XLA
    unpack+dequant+matmul on the same packed codes — resident HBM stays
    packed either way."""
    m, k = x.shape
    vpw = 32 // pw.bits
    if use_kernel is None:
        use_kernel = _kernel_default()
    if pw.mesh_sharded:
        if (shard and use_kernel and pw.mesh is not None and pw.mesh_axis
                and pw.bits != 3 and 32 % pw.bits == 0
                and pw.w_packed.ndim == 2):
            out = _shard_map_matmul(x, pw)
            if out is not None:
                return out
        if use_kernel:
            demotions.record("quant_matmul", "mesh-sharded codes the "
                             "shard_map route cannot tile")
        return quant_matmul_ref(x, pw.w_packed, pw.scale, pw.zero,
                                bits=pw.bits, group_size=pw.group_size,
                                d_in=pw.d_in)
    aligned = (32 % pw.bits == 0 and pw.d_in % vpw == 0
               and k % 128 == 0 and pw.w_packed.shape[1] % 128 == 0)
    # the k tile must divide k, contain whole quant groups and give a legal
    # scale block (_k_tile); when no tile <= 2048 does (per-tensor groups
    # with a large d_in, non-power-of-two groups) the kernel can't tile
    # the reduction — serve via ref like the 3-bit case
    k_blk = _k_tile(k, pw.group_size)
    if not (aligned and use_kernel and k_blk) or pw.bits == 3:
        if use_kernel:
            demotions.record("quant_matmul", f"untileable (k={k}, "
                             f"n={pw.w_packed.shape[1]}, bits={pw.bits}, "
                             f"group={pw.group_size})")
        return quant_matmul_ref(x, pw.w_packed, pw.scale, pw.zero,
                                bits=pw.bits, group_size=pw.group_size,
                                d_in=pw.d_in)
    m_pad = (-m) % 8
    if m_pad:
        x = jnp.concatenate([x, jnp.zeros((m_pad, k), x.dtype)], axis=0)
    m_blk = 128
    while x.shape[0] % m_blk:
        m_blk //= 2
    n = pw.w_packed.shape[1]
    n_blk = 256
    while n % n_blk:
        n_blk //= 2
    out = quant_matmul_pallas(
        x, pw.w_packed, pw.scale, pw.zero, bits=pw.bits,
        group_size=pw.group_size, m_blk=m_blk, n_blk=n_blk, k_blk=k_blk,
        interpret=_interpret())
    return out[:m] if m_pad else out


def quant_matmul_t(x: jax.Array, pw: PackedWeight, *,
                   use_kernel: bool | None = None) -> jax.Array:
    """Latent-layout GEMM: y = x @ dequant(pw)ᵀ.  x: (m, d) -> (m, d_in).

    The contraction runs over the weight's *columns* while the codes stay
    packed along d_in (which becomes the output axis) — the layout MLA's
    absorbed decode needs to contract the per-head-reshaped ``wkv_b``
    against queries/attention outputs without ever materializing the fp
    weight (``models.attention.mla_decode``).  Kernel policy matches
    :func:`quant_matmul`; mesh-sharded codes take the GSPMD ref (the
    per-head latent contractions are tiny and column-sharded)."""
    m, d = x.shape
    vpw = 32 // pw.bits
    if use_kernel is None:
        use_kernel = _kernel_default()
    k_blk = _k_tile(pw.d_in, pw.group_size)
    aligned = (32 % pw.bits == 0 and pw.d_in % vpw == 0
               and pw.d_in % 128 == 0 and d % 128 == 0
               and pw.w_packed.shape[0] * vpw == pw.d_in
               and k_blk and k_blk % vpw == 0)
    if (not (aligned and use_kernel) or pw.bits == 3 or pw.mesh_sharded):
        if use_kernel:
            demotions.record("quant_matmul_t", f"untileable or mesh-sharded "
                             f"(d_in={pw.d_in}, d={d}, bits={pw.bits})")
        return quant_matmul_t_ref(x, pw.w_packed, pw.scale, pw.zero,
                                  bits=pw.bits, group_size=pw.group_size,
                                  d_in=pw.d_in)
    m_pad = (-m) % 8
    if m_pad:
        x = jnp.concatenate([x, jnp.zeros((m_pad, d), x.dtype)], axis=0)
    m_blk = 128
    while x.shape[0] % m_blk:
        m_blk //= 2
    d_blk = 512
    while d % d_blk:
        d_blk //= 2
    out = quant_matmul_t_pallas(
        x, pw.w_packed, pw.scale, pw.zero, bits=pw.bits,
        group_size=pw.group_size, m_blk=m_blk, k_blk=k_blk,
        d_blk=d_blk, interpret=_interpret())
    return out[:m] if m_pad else out


def mla_latent_weights(pw: PackedWeight, n_heads: int, dn: int, dv: int,
                       ) -> tuple[PackedWeight, PackedWeight]:
    """Per-head latent views of a packed MLA ``wkv_b``: (pw_k, pw_v).

    ``wkv_b`` is quantized as one (kvr, H*(dn+dv)) matrix; absorbed decode
    contracts its two halves per head.  Packing runs along d_in (=kvr), so
    slicing/reshaping the *column* axis is exact on the packed codes — no
    unpack, no dequant: both views share the original leaves' memory
    modulo the transpose.  Returns

      * ``pw_k`` — (H, kvr//vpw, dn) codes (+ per-head group params), fed
        to ``vmap(quant_matmul_t)``: absorb W_k into the per-head query,
        q_lat = q_nope @ W_kᵀ, output in latent space (kvr).
      * ``pw_v`` — (H, kvr//vpw, dv) codes, fed to ``vmap(quant_matmul)``:
        expand the per-head latent attention output, ctx = ctx_lat @ W_v.

    Mesh placement: the parent's last axis (H*(dn+dv)) shards over the
    model axis, so the per-head views inherit head-sharded leaves; they
    keep ``mesh_sharded`` (the vmapped ops stay on the GSPMD ref — the
    shard_map route doesn't nest under vmap)."""
    codes = pw.w_packed.reshape(pw.w_packed.shape[0], n_heads, dn + dv)
    scale = pw.scale.reshape(pw.scale.shape[0], n_heads, dn + dv)
    zero = pw.zero.reshape(pw.zero.shape[0], n_heads, dn + dv)

    def head_view(lo, hi):
        return (codes[:, :, lo:hi].transpose(1, 0, 2),
                scale[:, :, lo:hi].transpose(1, 0, 2),
                zero[:, :, lo:hi].transpose(1, 0, 2))

    def mk(leaves):
        return PackedWeight(
            w_packed=leaves[0], scale=leaves[1], zero=leaves[2],
            bits=pw.bits, group_size=pw.group_size, d_in=pw.d_in,
            mesh_sharded=pw.mesh_sharded, mesh=pw.mesh,
            mesh_axis=pw.mesh_axis)

    return mk(head_view(0, dn)), mk(head_view(dn, dn + dv))
