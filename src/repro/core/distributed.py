"""Distributed RSQ: how the calibration pipeline scales to a pod.

Two independent axes of parallelism, matching DESIGN.md §5:

  * data-parallel Hessians — calibration tokens shard over the data axes.
    The classic mode keeps H replicated: the (d, d) weighted gram update is
    a contraction over the sharded token dim, so GSPMD reduces it with one
    psum per *batch*.  The streaming mode (``streaming=True``) instead
    keeps the accumulator itself sharded — shape (S, d, d) with the shard
    axis on the data axes, each device adding only its local partial gram —
    and defers the cross-device reduction to a single solve-time
    ``reduce`` (a ring all-reduce, ``runtime.collectives.ring_psum``).
    Per-batch collective traffic drops to zero and no device ever holds an
    unsharded per-layer Hessian during accumulation, which is what lets
    calibration batches stream at pod scale.

  * grouped solves — GPTQ/LDLQ solves for different weights (all
    experts of a layer, or same-shaped weights across layers) are
    independent: ``gptq_quantize_batched`` maps the blocked solver over
    the group in one program (``lax.map``: one solve's memory), and
    ``ldlq_quantize_batched`` vmaps its solver so one pjit call can
    distribute the batch over the model axis.  These are the solvers the
    calibration engine's
    shape-grouped solves dispatch to (see pipeline.quantize_layer_weights):
    q/k/v-style same-shape weights and stacked (E, d_in, d_out) expert
    tensors arrive pre-stacked along the leading axis.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import hessian as hess
from repro.core.gptq import gptq_quantize
from repro.core.ldlq import ldlq_quantize
from repro.core.quantizer import QuantSpec
from repro.runtime.collectives import ring_psum
from repro.runtime.sharding import ParallelCtx


def make_shard_reducer(ctx: ParallelCtx):
    """Jitted ``(S, ...) -> (...)`` reduction of a streaming accumulator
    whose leading shard axis lives on ``ctx``'s data axes, via the explicit
    collective path: a local shard-sum followed by ONE exact all-reduce over
    the data axis (bandwidth-optimal ``ring_psum`` for a single data axis, a
    plain psum for pod x data meshes).  This is the reduce the calibration
    pipeline dispatches at solve time when a live mesh is present —
    replacing the GSPMD ``jnp.sum`` fallback — and the reduce
    ``make_sharded_hessian_fn(streaming=True)`` returns.

    Requires a mesh with a non-trivial data axis; callers without one keep
    ``hessian.reduce_shards``."""
    assert ctx.enabled and ctx.dp and ctx.axis_size("dp") > 1, \
        "make_shard_reducer needs a live mesh with a data axis"
    axes = ctx.dp if len(ctx.dp) > 1 else ctx.dp[0]

    def local_reduce(hs):
        # local shard-sum then ONE exact all-reduce over the data
        # axis — the only collective of the whole accumulation stream.
        # Single data axis: bandwidth-optimal ring, chunked over the
        # leading rows of the summed (d, d) / (E, d, d) partial;
        # multi-axis (pod x data) meshes: a plain psum over both.
        part = jnp.sum(hs, axis=0)
        if isinstance(axes, str):
            return ring_psum(part, axes)
        return jax.lax.psum(part, axes)

    def reduce_fn(h):
        spec = P(axes, *([None] * (h.ndim - 1)))
        out = P(*([None] * (h.ndim - 1)))
        # replication checking is off: chunks of the ring all-reduce are
        # each finalized on one owner device, so the output is
        # numerically identical everywhere but not provably "replicated"
        # to the tracer
        return jax.shard_map(local_reduce, mesh=ctx.mesh, in_specs=(spec,),
                             out_specs=out, check_vma=False)(h)

    return jax.jit(reduce_fn)


def make_sharded_hessian_fn(ctx: ParallelCtx, *, streaming: bool = False,
                            n_shards: int | None = None):
    """Sharded Hessian accumulation over ``ctx``'s data axes.

    ``streaming=False`` (classic): returns jitted ``f(h, x, r) -> h`` with X
    token-sharded and H replicated (one psum per batch).

    ``streaming=True``: returns ``(acc, reduce)``.  ``acc(h, x, r)``
    maintains a *sharded* (S, d, d) partial-sum accumulator (S = data-axis
    size unless ``n_shards`` overrides it; pass ``h=None`` to start) with no
    per-batch collectives; ``reduce(h) -> (d, d)`` performs the one
    solve-time reduction — a ring all-reduce over the data axis when the
    mesh is live, a plain shard-sum otherwise.
    """

    def acc_dense(h, x, r):
        xf = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
        xf = xf * r.reshape(-1, 1)
        upd = 2.0 * xf.T @ xf
        return (h + upd if h is not None else upd)

    if not streaming:
        if not ctx.enabled:
            return jax.jit(acc_dense)
        x_sh = ctx.sharding("dp", None, None)
        h_sh = ctx.sharding(None, None)
        r_sh = ctx.sharding("dp", None)
        return jax.jit(acc_dense, in_shardings=(h_sh, x_sh, r_sh),
                       out_shardings=h_sh)

    s = n_shards or (max(ctx.axis_size("dp"), 1) if ctx.enabled else 1)
    s = max(s, 1)

    def acc_stream(h, x, r):
        upd = hess.accumulate(None, x.reshape(-1, x.shape[-1]),
                              None if r is None else r.reshape(-1),
                              n_shards=s)
        out = upd if h is None else h + upd
        return ctx.shard_leading(out)

    acc = jax.jit(acc_stream)

    if ctx.enabled and ctx.dp and ctx.axis_size("dp") > 1:
        return acc, make_shard_reducer(ctx)
    return acc, jax.jit(hess.reduce_shards)


@partial(jax.jit, static_argnames=("spec", "block"))
def gptq_quantize_batched(ws: jax.Array, hs: jax.Array, spec: QuantSpec,
                          *, damp: float = 0.01, block: int = 128):
    """ws: (N, d_in, d_out); hs: (N, d_in, d_in) — N independent solves
    in one program, one after another (``lax.map``).  Under ``vmap`` the
    TPU compiler keeps ~65 (d_in, d_in) f32 buffers per solve live in the
    row sweep (9 GB for two 4096-wide solves, measured by compiling for a
    v5e); mapped, a group costs one solve's memory, and every solve is
    bitwise the lone ``gptq_quantize`` call."""
    fn = lambda wh: gptq_quantize(wh[0], wh[1], spec, damp=damp, block=block)
    return jax.lax.map(fn, (ws, hs))


@partial(jax.jit, static_argnames=("block",))
def ldlq_quantize_batched(ws: jax.Array, hs: jax.Array, *,
                          damp: float = 0.01, block: int = 128):
    """LDLQ/E8 twin of ``gptq_quantize_batched``: one vmapped program for a
    (N, d_in, d_out) weight stack instead of a per-expert Python loop."""
    fn = lambda w, h: ldlq_quantize(w, h, damp=damp, block=block)
    return jax.vmap(fn)(ws, hs)
