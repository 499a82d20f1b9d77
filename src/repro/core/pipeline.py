"""RSQ layer-wise quantization pipeline (Rotate -> Scale -> Quantize).

Drives the whole recipe over a model:
  0. dataset expansion (circular shifts, Sec 4.4)
  1. fuse norms + rotate the model (QuaRot step; skippable -> GPTQ baseline)
  2. layer-by-layer: capture per-weight inputs (with attention column sums),
     compute token importance R, accumulate H_w = 2 X R^2 X^T per weight,
     run GPTQ (or LDLQ+E8 VQ), write back, propagate *quantized* outputs to
     the next layer (standard GPTQ error-feedback scheme).

Baselines are config points: GPTQ = no rotation + uniform; QuaRot =
rotation + uniform; RSQ = rotation + a token-importance strategy.

Calibration engine
------------------
The hot path is a scheduled stack of fused, trace-cached per-layer stages:

  * **Per-meta jit cache** — capture/apply closures are built and jitted
    once per distinct ``(BlockMeta, param-shape)`` signature, not once per
    layer.  A stack of L homogeneous layers compiles O(distinct metas)
    XLA programs instead of O(L).  ``RSQPipeline.trace_counts`` records
    actual retraces (the regression tests and ``benchmarks/pipeline_bench``
    assert on it).  ``RSQConfig.trace_cache=False`` restores the legacy
    fresh-jit-per-layer behaviour (used as the benchmark baseline).
  * **Fused calibration step** — capture, token importance, and Hessian
    accumulation run as ONE jitted program per batch with the Hessian dict
    donated (``donate_argnums``), so the O(d^2)-per-weight accumulator
    state is updated in place.  Dense and stacked-expert updates both route
    through ``hess.accumulate`` (Pallas ``gram`` kernel auto-on for TPU).
  * **Layer scheduler** — the layer loop itself is pluggable
    (``core/scheduler``): the pipeline exposes its per-layer stages as
    engine hooks (``layer_begin`` / ``layer_capture`` / ``layer_solve`` /
    ``layer_apply`` / ``layer_finalize``) and ``RSQConfig.scheduler``
    selects who drives them.  ``SequentialScheduler`` is the classic
    lock-step loop; ``OverlappedScheduler`` software-pipelines dispatch so
    layer i's GPTQ/LDLQ solve executes while layer i+1's fused capture is
    already being issued over double-buffered activations, with every host
    sync (error-report floats) deferred to one drain — bit-identical
    results, no per-layer pipeline bubble.  Because capture/apply are
    trace-cached per meta, the overlapped schedule adds zero compilations.
  * **Streamed sharded Hessians** — ``RSQConfig.shard_hessians`` switches
    the accumulators to the streaming layout: (S, d, d) partial sums with
    the shard axis on the mesh's data axes (``ParallelCtx.shard_leading``),
    so each device accumulates only its local token chunk and no device
    ever materializes an unsharded per-layer Hessian during accumulation;
    ``hess.reduce_shards`` performs the single solve-time reduction (one
    psum under GSPMD; the standalone streaming API with an explicit ring
    all-reduce lives in ``core/distributed.make_sharded_hessian_fn``).
  * **Batched solves** — GPTQ *and* LDLQ solves are shape-grouped: weights
    sharing ``(d_in, d_out)`` (q/k/v, gate/up) stack into one vmapped
    ``gptq_quantize_batched`` / ``ldlq_quantize_batched`` call and stacked
    experts go through the batched path directly, instead of a sequential
    Python loop.  Solve error reports are built lazily (jax scalars) so
    schedulers decide when the host pays the sync.
  * **Sharded write-back** — ``RSQConfig.pack_output`` folds every solve's
    ``(q, scale, zero)`` straight into the packed serving artifact
    (``RSQPipeline.artifact``; persist via
    ``checkpoint.packed.save_packed_artifact``): codes are packed by a
    jitted ``quantizer.pack_codes`` (a d_in-axis op, so d_out shards pack
    locally) and constrained onto the mesh's model axis — no host ever
    holds an unsharded per-layer ``(q, scales)`` tensor, and the artifact
    is saved one addressable shard at a time.  Input side, the pipeline
    accepts a globally-sharded calibration array from
    ``data.loader.CalibrationLoader`` (disjoint per-data-group slices)
    whose rows feed the streaming accumulators chunk-aligned; the
    solve-time shard reduction routes through the explicit ring collective
    (``distributed.make_shard_reducer``) whenever a live mesh is present.
  * **Stage spans** — ``run`` is one ``pipeline.pass`` span of
    ``runtime.tracing.TRACER`` (off by default), tiled by the spans of
    its stages: ``prepare``, ``prewarm``, each ``layer_*`` hook, ``pack``,
    ``commit`` and ``writeback``.  They time host work only, so a
    profiler trace can charge every idle stretch of the device to one.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import hessian as hess
from repro.core.distributed import (gptq_quantize_batched,
                                    ldlq_quantize_batched,
                                    make_shard_reducer)
from repro.core.expansion import expand_dataset
from repro.core.gptq import gptq_quantize
from repro.core.importance import ImportanceInputs, get_strategy
from repro.core.ldlq import ldlq_quantize
from repro.core.quantizer import QuantSpec, pack_codes
from repro.core.rotation import rotate_model
from repro.core.scheduler import get_scheduler, resolve_hessian_shards
from repro.models.layers import rms_norm
from repro.models.lm import Model, apply_block, capture_block
from repro.runtime.sharding import LOCAL, ParallelCtx
from repro.runtime.tracing import TRACER


@dataclasses.dataclass(frozen=True)
class RSQConfig:
    bits: int = 3
    group_size: int = 128
    sym: bool = True
    rotate: bool = True
    importance: str = "attn_con"  # see core.importance.STRATEGIES
    r_min: float = 0.01
    r_max: float = 1.0
    first_n: int = 1024  # for the First-N / First&Last-N heuristics
    expansion: int = 1  # dataset expansion factor M (paper: 8)
    damp: float = 0.01
    method: str = "gptq"  # gptq | ldlq (E8 vector quantization)
    gptq_block: int = 128
    seed: int = 0
    # restrict the loss to a token chunk (Tab. 1 reproduction):
    chunk_lo: float = 0.0
    chunk_hi: float = 1.0
    # Pallas gram kernel for Hessian accumulation (None: auto-on for TPU)
    use_gram_kernel: Optional[bool] = None
    # per-meta jit cache for capture/apply (False: legacy per-layer jits,
    # kept as the benchmark baseline)
    trace_cache: bool = True
    # layer scheduler: "sequential" | "overlapped" | None (auto: sequential
    # on CPU, overlapped on accelerators) — see core/scheduler
    scheduler: Optional[str] = None
    # streaming sharded Hessian accumulators: False = dense (d, d) dicts;
    # True = shard over the mesh's data axes (S = dp size); int S > 1 = S
    # partial-sum shards regardless of mesh — see hessian.accumulate
    shard_hessians: Any = False
    # packed serving artifact: collect every solve's (q, scale, zero) as
    # packed int codes during write-back (``RSQPipeline.artifact``; persist
    # via checkpoint.packed.save_packed_artifact).  GPTQ only — the LDLQ/E8
    # lattice rounder has no integer codes to pack.
    pack_output: bool = False
    # write-back path for the packed artifact: "sharded" packs on device
    # and keeps codes/scales sharded over the model axis end to end (no
    # host ever holds an unsharded per-layer tensor); "host" is the legacy
    # gather-to-host baseline, kept for bit-parity tests
    pack_writeback: str = "sharded"

    def spec(self) -> QuantSpec:
        return QuantSpec(bits=self.bits, group_size=self.group_size,
                         sym=self.sym)


def _strategy_kwargs(rsq: RSQConfig) -> dict:
    if rsq.importance in ("first_n", "first_last_n"):
        return {"n": rsq.first_n}
    if rsq.importance == "uniform":
        return {}
    return {"r_min": rsq.r_min, "r_max": rsq.r_max}


def _chunk_mask(r: jax.Array, rsq: RSQConfig) -> jax.Array:
    """Tab.-1 style chunk restriction on top of any strategy."""
    if rsq.chunk_lo <= 0.0 and rsq.chunk_hi >= 1.0:
        return r
    t = r.shape[-1]
    idx = jnp.arange(t)
    mask = (idx >= int(rsq.chunk_lo * t)) & (idx < int(rsq.chunk_hi * t))
    return r * mask.astype(r.dtype)


_QUANT_SKIP = ("router",)  # routers stay fp32 (standard MoE practice)


def _is_quantizable(path: str, arr) -> bool:
    if any(s in path for s in _QUANT_SKIP):
        return False
    return arr.ndim >= 2 and min(arr.shape[-2:]) >= 16


def _solve_spec(rsq: RSQConfig, d_in: int) -> tuple[QuantSpec, int]:
    """Per-d_in GPTQ block size + group-size fallback (shared by the
    sequential and batched paths so their outputs are identical)."""
    block = min(rsq.gptq_block, d_in)
    spec = rsq.spec()
    gs = spec.group_size
    if gs != -1 and (gs > block or block % gs or d_in % gs):
        spec = dataclasses.replace(spec, group_size=-1)
    return spec, block


def finalize_layer_report(report: dict) -> dict:
    """Materialize a deferred solve report (jax scalars -> floats).

    This is the host sync of the solve stage; schedulers choose when to pay
    it (per layer for sequential, once at the drain for overlapped)."""
    return {path: float(v) for path, v in report.items()}


def quantize_layer_weights(p_block: dict, hessians: dict[str, Any],
                           rsq: RSQConfig, *, defer: bool = False,
                           collect: Optional[dict] = None) -> tuple[dict, dict]:
    """Solve GPTQ/LDLQ for every captured weight of one block.

    Solves are shape-grouped for both methods: all weights sharing
    ``(d_in, d_out)`` (q/k/v, gate/up, every expert of a stacked
    (E, d_in, d_out) tensor) are stacked into a single
    ``gptq_quantize_batched`` / ``ldlq_quantize_batched`` call — one
    vmapped program per distinct shape instead of one dispatch per weight.

    ``defer=True`` leaves the per-weight error report as jax scalars (no
    host sync); call :func:`finalize_layer_report` to materialize floats.

    ``collect`` (GPTQ only): a dict that receives, per weight path, the raw
    solve outputs ``{"q", "scale", "zero", "dtype"}`` as *device* arrays —
    the feed of the packed serving artifact.  Slicing a batched solve's
    output is a lazy device op, so nothing is gathered here.
    """
    report: dict[str, Any] = {}
    new_p = jax.tree.map(lambda x: x, p_block)

    items = []  # (path, node, name, w, h) for every quantizable weight
    for path, h in hessians.items():
        parts = path.split("/")
        node = new_p
        for key in parts[:-1]:
            node = node[key]
        name = parts[-1]
        w = node[name]
        if not _is_quantizable(path, w):
            continue
        items.append((path, node, name, w, h))

    use_ldlq = rsq.method == "ldlq"

    # ---- group by (d_in, d_out); one batched solve per group
    groups: dict[tuple, list] = {}
    for it in items:
        groups.setdefault(tuple(it[3].shape[-2:]), []).append(it)
    for (d_in, d_out), its in groups.items():
        if use_ldlq:
            spec, block = None, min(rsq.gptq_block, d_in)
        else:
            spec, block = _solve_spec(rsq, d_in)
        n_solves = sum(1 if it[3].ndim == 2 else it[3].shape[0] for it in its)
        if n_solves == 1 and its[0][3].ndim == 2:  # lone 2-D weight: no
            # batch dim to vmap over (a lone (1, d_in, d_out) expert stack
            # stays on the batched path — it already carries the lead axis)
            path, node, name, w, h = its[0]
            out = (ldlq_quantize(w, h, damp=rsq.damp, block=block)
                   if use_ldlq else
                   gptq_quantize(w, h, spec, damp=rsq.damp, block=block))
            node[name] = out["w_deq"].astype(w.dtype)
            report[path] = out["err"]
            if collect is not None and not use_ldlq:
                collect[path] = {"q": out["q"], "scale": out["scale"],
                                 "zero": out["zero"], "dtype": str(w.dtype)}
            continue
        ws = jnp.concatenate(
            [it[3][None] if it[3].ndim == 2 else it[3] for it in its])
        hs = jnp.concatenate(
            [it[4][None] if it[4].ndim == 2 else it[4] for it in its])
        out = (ldlq_quantize_batched(ws, hs, damp=rsq.damp, block=block)
               if use_ldlq else
               gptq_quantize_batched(ws, hs, spec, damp=rsq.damp,
                                     block=block))
        o = 0
        for path, node, name, w, h in its:
            sl = slice(o, o + 1) if w.ndim == 2 else slice(o, o + w.shape[0])
            if w.ndim == 2:
                node[name] = out["w_deq"][o].astype(w.dtype)
                report[path] = out["err"][o]
            else:
                node[name] = out["w_deq"][sl].astype(w.dtype)
                report[path] = out["err"][sl].mean()
            if collect is not None and not use_ldlq:
                idx = o if w.ndim == 2 else sl
                collect[path] = {"q": out["q"][idx],
                                 "scale": out["scale"][idx],
                                 "zero": out["zero"][idx],
                                 "dtype": str(w.dtype)}
            o = sl.stop
    if not defer:
        report = finalize_layer_report(report)
    return new_p, report


@dataclasses.dataclass
class _LayerFns:
    """One cache entry of the calibration engine: the jitted fused
    calibration step, the jitted quantized-forward, and the zero Hessian
    initializer (shapes precomputed via eval_shape, no tracing)."""
    fused: Callable  # (p, x, med, tok, counts, hessians) -> hessians
    apply: Callable  # (p, x, med) -> y
    hess_init: Callable  # () -> {path: zeros}


@dataclasses.dataclass(frozen=True)
class LayerTask:
    """One unit of scheduler work: quantize one block (original params).

    ``index`` is the task's global position in the decoder stack — the
    coordinate the fault-injection (``stage_point``) and checkpointing
    (``layer_commit``) hooks key on.  ``None`` (encoder tasks) opts the
    task out of both."""
    tag: str
    p_blk: Any
    meta: Any
    index: Optional[int] = None


@dataclasses.dataclass
class _RunCtx:
    """Per-run state shared by all engine hooks of one ``run`` call."""
    calib: Any
    counts: Any
    batch_size: int
    media_b: Any
    verbose: bool


class RSQPipeline:
    def __init__(self, model: Model, rsq: RSQConfig,
                 ctx: ParallelCtx = LOCAL):
        self.model = model
        self.cfg: ModelConfig = model.cfg
        self.rsq = rsq
        self.ctx = ctx
        self.strategy = get_strategy(rsq.importance)
        self.skw = _strategy_kwargs(rsq)
        self.use_kernel = (rsq.use_gram_kernel
                           if rsq.use_gram_kernel is not None
                           else jax.default_backend() == "tpu")
        self.n_hshards = resolve_hessian_shards(rsq.shard_hessians, ctx)
        # one streaming partial per data shard: the gram kernel runs per
        # device under shard_map (see _accumulate_local)
        self._local_grams = (self.use_kernel and ctx.enabled and bool(ctx.dp)
                             and self.n_hshards > 1
                             and self.n_hshards == ctx.axis_size("dp"))
        # solve-time reduction of the streaming (S, d, d) accumulators: the
        # explicit collective path (one ring all-reduce over the data axis,
        # core/distributed.make_shard_reducer) whenever a live mesh is
        # present; the plain shard-sum (GSPMD-free) otherwise
        if ctx.enabled and ctx.dp and ctx.axis_size("dp") > 1:
            self._hreduce = make_shard_reducer(ctx)
        else:
            self._hreduce = jax.jit(hess.reduce_shards)
        if rsq.pack_output:
            if rsq.method != "gptq":
                raise ValueError("pack_output needs integer codes; the "
                                 "LDLQ/E8 rounder has none (method='gptq')")
            if rsq.pack_writeback not in ("sharded", "host"):
                raise ValueError(f"unknown pack_writeback "
                                 f"{rsq.pack_writeback!r}")
        self.artifact: Optional[dict] = None
        self._art_entries: dict[str, dict] = {}
        self._art_meta: dict[str, dict] = {}
        self._pack = jax.jit(self._pack_sharded)
        self._layer_fns: dict[Any, _LayerFns] = {}
        self._prewarm: dict[Any, Any] = {}  # layer key -> compile future
        self._rc: Optional[_RunCtx] = None
        # fault-tolerance state (per run): an optional FaultPlan checked at
        # every stage_point, an optional commit callback (QuantizeRunner),
        # restored Hessian accumulators keyed by layer index, and the last
        # decoder index (marks the commit that completes the stack)
        self._fault = None
        self._commit_cb: Optional[Callable] = None
        self._resume_hess: dict[int, dict] = {}
        self._last_index: Optional[int] = None
        # retraces of the cached capture/apply programs; a homogeneous
        # L-layer stack should end a run at 1/1, not L/L.  The lock keeps
        # the counts exact when prewarm traces programs on worker threads.
        self.trace_counts = {"capture": 0, "apply": 0}
        self._trace_lock = threading.Lock()

    # ---------------------------------------------------------------- utils
    def _importance(self, z_in, z_out, tokens, colsum, counts):
        inp = ImportanceInputs(z_in=z_in, z_out=z_out, tokens=tokens,
                               attn_colsum=colsum, token_counts=counts)
        r = self.strategy(inp, **self.skw)
        return _chunk_mask(r, self.rsq)

    def _accumulate(self, hessians, caps, dom, r):
        """Add one batch's contribution to every weight Hessian.

        With ``shard_hessians`` on, accumulators carry a leading (S,) shard
        axis constrained to the mesh's data axes — updates stay local."""
        slot_token = caps.get("ffn/__moe_slot_token")
        for path, x_c in caps.items():
            if path.endswith("__moe_slot_token"):
                continue
            d = dom[path]
            if d in ("stream", "hidden"):
                r_rows = r.reshape(-1)
            elif d == "media":
                r_rows = None
            else:  # expert buffers (E, C, d): scatter r into slots
                rf = jnp.concatenate([r.reshape(-1), jnp.zeros((1,), r.dtype)])
                r_rows = rf[slot_token].reshape(x_c.shape[0], x_c.shape[1])
            if not (x_c.ndim == 3 and d == "expert"):
                x_c = x_c.reshape(-1, x_c.shape[-1])
            if self._local_grams and x_c.ndim == 2 and r_rows is not None:
                hessians[path] = self._accumulate_local(
                    hessians[path], x_c, r_rows)
                continue
            h_new = hess.accumulate(
                hessians.get(path), x_c, r_rows, use_kernel=self.use_kernel,
                n_shards=self.n_hshards)
            if self.n_hshards > 1:
                h_new = self.ctx.shard_leading(h_new)
            hessians[path] = h_new
        return hessians

    def _accumulate_local(self, h, x, r):
        """Streaming accumulation with the Pallas gram kernel on a data
        mesh: each device adds the gram of its own token shard to its own
        (1, d, d) partial.  The kernel is an opaque call that GSPMD would
        run whole, on all tokens, on every device; under ``shard_map`` it
        sees only the local shard, the same contiguous token ranges the
        streaming accumulators assign to each partial."""
        dp = self.ctx.dp if len(self.ctx.dp) > 1 else self.ctx.dp[0]

        def local(hl, xl, rl):
            return hl + hess.accumulate(None, xl, rl, use_kernel=True)[None]

        return jax.shard_map(local, mesh=self.ctx.mesh,
                             in_specs=(P(dp), P(dp), P(dp)),
                             out_specs=P(dp), check_vma=False)(h, x, r)

    def _pack_sharded(self, q, scale, zero):
        """On-device pack for the sharded write-back: codes are produced by
        the jitted ``pack_codes`` (a d_in-axis op, so a d_out shard packs
        locally) and all three artifact tensors are constrained onto the
        model axis when divisible — they stay sharded until the per-shard
        artifact save and no host copy of the unsharded tensor ever
        exists."""
        ctx = self.ctx
        outs = []
        for a in (pack_codes(q, self.rsq.bits), scale, zero):
            if (ctx.enabled and ctx.tp
                    and a.shape[-1] % ctx.axis_size("tp") == 0):
                a = ctx.constrain(a, *([None] * (a.ndim - 1)), "tp")
            outs.append(a)
        return tuple(outs)

    def _put_act(self, a):
        """Re-place one restored (host) activation batch for resume: leading
        batch axis back onto the mesh's data axes when divisible, so the
        resumed run's capture/apply programs see the same input layout (and
        therefore compile to the same partitioning) as the run that died."""
        a = jnp.asarray(a)
        ctx = self.ctx
        if ctx.enabled and ctx.dp and a.shape[0] % ctx.axis_size("dp") == 0:
            a = jax.device_put(
                a, ctx.sharding("dp", *([None] * (a.ndim - 1))))
        return a

    def _put_entry(self, a):
        """Re-place one restored packed-artifact tensor onto the model axis
        (mirrors ``_pack_sharded``'s constraint) so the per-addressable-shard
        artifact save emits the identical shard layout after a resume."""
        a = jnp.asarray(a)
        ctx = self.ctx
        if (ctx.enabled and ctx.tp
                and a.shape[-1] % ctx.axis_size("tp") == 0):
            a = jax.device_put(
                a, ctx.sharding(*([None] * (a.ndim - 1)), "tp"))
        return a

    def _collect_packed(self, task: LayerTask, collect: dict) -> None:
        """Fold one layer's solve outputs into the serving artifact."""
        from repro.checkpoint.packed import _host_gather

        self.stage_point(task.index, "pack")
        tag = task.tag
        with TRACER.span("pipeline.pack", layer=tag):
            for path, sol in collect.items():
                q, scale, zero = sol["q"], sol["scale"], sol["zero"]
                if self.rsq.pack_writeback == "host":
                    # legacy baseline: the unsharded (q, scales, zeros)
                    # land on host, then get packed — the path the sharded
                    # write-back retires (and is regression-tested
                    # against, bit for bit)
                    q_np = _host_gather(q)
                    entry = {"codes": np.asarray(pack_codes(q_np,
                                                            self.rsq.bits)),
                             "scale": _host_gather(scale),
                             "zero": _host_gather(zero)}
                else:
                    codes, s, z = self._pack(q, scale, zero)
                    entry = {"codes": codes, "scale": s, "zero": z}
                name = f"{tag}/{path}"
                self._art_entries[name] = entry
                self._art_meta[name] = {
                    "path": path, "tag": tag, "d_in": int(q.shape[-2]),
                    "group_size": int(q.shape[-2]) // int(scale.shape[-2]),
                    "dtype": sol["dtype"]}

    def _layer_key(self, meta, p_blk):
        p_sig = tuple((tuple(a.shape), str(a.dtype))
                      for a in jax.tree.leaves(p_blk))
        return (meta, p_sig)

    def _get_layer_fns(self, meta, p_blk, x, med) -> _LayerFns:
        """Build (or fetch) the jitted fused/apply programs for one block
        signature.  The jits themselves handle batch-shape polymorphism
        (e.g. a ragged tail batch) by retracing, so the cache key only
        carries what changes the *captured structure*: the meta and the
        block's parameter shapes."""
        key = self._layer_key(meta, p_blk)
        if self.rsq.trace_cache and key in self._layer_fns:
            return self._layer_fns[key]
        cfg, meta_ = self.cfg, meta
        dom: dict[str, str] = {}

        def _probe(p, x, med):
            _, caps, d, _ = capture_block(p, cfg, meta_, x, media=med)
            dom.update(d)
            return caps

        caps_s = jax.eval_shape(_probe, p_blk, x, med)
        hshapes = {}
        shard = (self.n_hshards,) if self.n_hshards > 1 else ()
        for path, s in caps_s.items():
            if path.endswith("__moe_slot_token"):
                continue
            if s.ndim == 3 and dom[path] == "expert":
                hshapes[path] = shard + (s.shape[0], s.shape[-1], s.shape[-1])
            else:
                hshapes[path] = shard + (s.shape[-1], s.shape[-1])

        def hess_init():
            zeros = {p_: jnp.zeros(sh, jnp.float32)
                     for p_, sh in hshapes.items()}
            if self.n_hshards > 1:
                zeros = {p_: self.ctx.shard_leading(z)
                         for p_, z in zeros.items()}
            return zeros

        def _fused(p, x, med, tok, counts, hessians):
            # python side effect at trace time: counts XLA compilations
            with self._trace_lock:
                self.trace_counts["capture"] += 1
            y, caps, dom_t, colsum = capture_block(p, cfg, meta_, x,
                                                   media=med)
            r = self._importance(x, y, tok, colsum, counts)
            return self._accumulate(hessians, caps, dom_t, r)

        def _apply(p, x, med):
            with self._trace_lock:
                self.trace_counts["apply"] += 1
            return apply_block(p, cfg, meta_, x, media=med)[0]

        fns = _LayerFns(fused=jax.jit(_fused, donate_argnums=(5,)),
                        apply=jax.jit(_apply), hess_init=hess_init)
        if self.rsq.trace_cache:
            self._layer_fns[key] = fns
        return fns

    # ----------------------------------------------- scheduler engine hooks
    # A LayerScheduler (core/scheduler) drives these five stages.  All of
    # them only *dispatch* device work; the lone host sync lives in
    # layer_sync/layer_finalize, which is why the overlapped scheduler can
    # defer it.

    def prewarm(self, tasks, acts) -> None:
        """Compile every distinct layer program concurrently.

        On a heterogeneous stack (K distinct metas — hybrid attn/mamba
        models, prefix + group stacks) the lock-step schedule pays the K
        XLA compilations serially, one at each first encounter.  This
        builds + compiles all of them on a thread pool up front (tracing
        contends on the GIL but the multi-second XLA compile releases it),
        so cold calibration wall-clock drops from ~sum(compiles) to
        ~max(compiles).  Shape-matched dummy executions force the
        compilation into the jit call cache; real calls then hit it.
        No-op for homogeneous stacks and with ``trace_cache=False``."""
        with TRACER.span("pipeline.prewarm"):
            if not self.rsq.trace_cache:
                return
            rc = self._rc
            med0 = rc.media_b[0] if rc.media_b is not None else None
            jobs, seen = [], set()
            for task in tasks:
                key = self._layer_key(task.meta, task.p_blk)
                if key in self._layer_fns or key in seen:
                    continue
                seen.add(key)
                jobs.append((key, task))
            if len(jobs) < 2:  # single meta: nothing to overlap
                return
            import concurrent.futures as cf
            import os

            x0 = acts[0]
            tok0 = rc.calib[: x0.shape[0]]

            def build(task):
                fns = self._get_layer_fns(task.meta, task.p_blk, x0, med0)
                # dummy one-batch executions: compile capture AND apply
                # now (values discarded; the donated dict is a throwaway).
                # A real call is required — on this jax, AOT
                # lower().compile() does NOT populate the jit call cache,
                # so the later real call would recompile from scratch
                fns.fused(task.p_blk, x0, med0, tok0, rc.counts,
                          fns.hess_init())
                fns.apply(task.p_blk, x0, med0)
                return fns

            ex = cf.ThreadPoolExecutor(
                max_workers=min(len(jobs), os.cpu_count() or 4))
            self._prewarm = {key: ex.submit(build, task)
                             for key, task in jobs}
            ex.shutdown(wait=False)

    def stage_point(self, index: Optional[int], stage: str,
                    batch: Optional[int] = None) -> None:
        """Per-stage dispatch boundary (see ``core/scheduler`` docstring).
        No-op unless a ``FaultPlan`` was passed to ``run`` — then an armed
        ``(layer, stage[, batch])`` coordinate raises here."""
        if self._fault is not None and index is not None:
            self._fault.check(index, stage, batch)

    def layer_commit(self, task: LayerTask, state: dict, p_new, acts,
                     next_state: Optional[dict] = None) -> None:
        """Durable-progress hook, called once per layer after its apply
        sweep is dispatched.  Forwards everything a checkpointing runner
        needs: the solved params, the propagated activations (= the next
        layer's inputs), the artifact entries folded so far, and — under
        the overlapped schedule — the next layer's already-complete Hessian
        accumulators.  No-op without a runner."""
        if self._commit_cb is None or task.index is None:
            return
        nh, nidx = None, None
        if next_state is not None and next_state.get("hessians") is not None:
            nh = next_state["hessians"]
            nidx = next_state["task"].index
        with TRACER.span("pipeline.commit", layer=task.tag):
            self._commit_cb(
                index=task.index, state=state, p_new=p_new, acts=acts,
                art_entries=self._art_entries, art_meta=self._art_meta,
                next_hessians=nh, next_index=nidx,
                last=task.index == self._last_index)

    def layer_begin(self, task: LayerTask, acts) -> dict:
        """Resolve the trace-cached programs and fresh accumulators."""
        TRACER.count("pipeline.layers")
        with TRACER.span("pipeline.layer_begin", layer=task.tag):
            rc = self._rc
            med0 = rc.media_b[0] if rc.media_b is not None else None
            fut = self._prewarm.pop(self._layer_key(task.meta, task.p_blk),
                                    None)
            if fut is not None:
                fut.result()  # join the background compile; fns now cached
            fns = self._get_layer_fns(task.meta, task.p_blk, acts[0], med0)
            st = {"task": task, "fns": fns,
                  "t0": time.perf_counter(), "pending": None}
            rh = (self._resume_hess.pop(task.index, None)
                  if task.index is not None else None)
            if rh is None:
                st["hessians"] = fns.hess_init()
            else:
                # checkpointed accumulators (exact float32 partial sums):
                # put them back in the streaming layout and skip the
                # capture sweep
                hs = {}
                for p_, a in rh.items():
                    a = jnp.asarray(a)
                    if self.n_hshards > 1:
                        a = self.ctx.shard_leading(a)
                    hs[p_] = a
                st["hessians"] = hs
                st["capture_done"] = True
        return st

    def layer_capture(self, state: dict, bi: int, x_b) -> None:
        """Fused capture+importance+accumulate for one calibration batch
        (the Hessian dict is donated, so state updates in place)."""
        if state.get("capture_done"):  # accumulators restored from a
            return                     # checkpoint — nothing to add
        rc = self._rc
        with TRACER.span("pipeline.layer_capture", layer=state["task"].tag,
                         batch=bi):
            med = rc.media_b[bi] if rc.media_b is not None else None
            tok = rc.calib[bi * rc.batch_size:
                           bi * rc.batch_size + x_b.shape[0]]
            state["hessians"] = state["fns"].fused(
                state["task"].p_blk, x_b, med, tok, rc.counts,
                state["hessians"])

    def layer_solve(self, state: dict):
        """Reduce Hessian shards (one explicit ring all-reduce on a live
        mesh, a plain shard-sum otherwise) and dispatch the batched
        GPTQ/LDLQ solves.  Returns the quantized block params; the error
        report stays deferred in ``state`` (no host sync here).  With
        ``pack_output`` the solve's (q, scale, zero) also flow straight
        into the packed serving artifact — per the configured write-back,
        packed on device and still sharded (default) or gathered to host
        (legacy baseline)."""
        with TRACER.span("pipeline.layer_solve", layer=state["task"].tag):
            hessians = state.pop("hessians")
            if self.n_hshards > 1:
                hessians = {p: self._hreduce(h) for p, h in hessians.items()}
            collect: Optional[dict] = {} if self.rsq.pack_output else None
            p_new, state["pending"] = quantize_layer_weights(
                state["task"].p_blk, hessians, self.rsq, defer=True,
                collect=collect)
        if collect:
            self._collect_packed(state["task"], collect)
        return p_new

    def layer_apply(self, state: dict, p_new, bi: int, x_b):
        """Propagate one batch through the quantized block."""
        rc = self._rc
        with TRACER.span("pipeline.layer_apply", layer=state["task"].tag,
                         batch=bi):
            med = rc.media_b[bi] if rc.media_b is not None else None
            return state["fns"].apply(p_new, x_b, med)

    def layer_sync(self, state: dict) -> None:
        """Materialize the deferred error report now (host sync;
        idempotent).  The sequential scheduler calls this right after the
        solve — the classic lock-step timeline; the overlapped scheduler
        skips it and pays one drain at the end of the stack instead."""
        if not state.get("synced"):
            with TRACER.span("pipeline.layer_sync",
                             layer=state["task"].tag):
                state["pending"] = finalize_layer_report(state["pending"])
            state["synced"] = True

    def layer_finalize(self, state: dict) -> dict:
        """Assemble the layer report (syncing if not already done).  Under
        the overlapped scheduler ``seconds`` spans dispatch-to-drain and
        overlaps across layers — the stack total is the meaningful time."""
        rc = self._rc
        with TRACER.span("pipeline.layer_finalize", layer=state["task"].tag):
            self.layer_sync(state)
            rep = {"weights": state["pending"],
                   "seconds": round(time.perf_counter() - state["t0"], 4)}
            if rc.verbose:
                print(f"  [{state['task'].tag}] {len(rep['weights'])} "
                      f"weights quantized in {rep['seconds']}s", flush=True)
        return rep

    # ----------------------------------------------------------------- main
    def run(self, params: dict, calib_tokens, *, batch_size: int = 8,
            media=None, frames=None, verbose: bool = False,
            fault=None, commit: Optional[Callable] = None,
            resume: Optional[dict] = None):
        """Quantize `params`. calib_tokens: (N, T) int32 (pre-expansion).

        Fault tolerance (see ``core.resume.QuantizeRunner``, which drives
        all three):
          * ``fault`` — a ``runtime.fault.FaultPlan``; armed
            ``(layer, stage)`` coordinates raise at that dispatch boundary.
          * ``commit`` — callback invoked once per decoder layer with the
            solved params, propagated acts, artifact entries and (overlapped
            schedule) the next layer's complete Hessians.
          * ``resume`` — progress restored from a checkpoint:
            ``{"start", "solved", "acts", "art", "art_meta", "hessians",
            "reports"}``; layers below ``start`` are skipped and the stack
            continues from the restored activations, bit-identical to a run
            that never died.

        Returns (new_params, report)."""
        with TRACER.span("pipeline.pass"):
            with TRACER.span("pipeline.prepare"):
                model, cfg, rsq = self.model, self.cfg, self.rsq
                key = jax.random.key(rsq.seed)
                # per-run compile accounting (cached jits from a previous
                # run on the same pipeline legitimately contribute 0 traces
                # to this run)
                self.trace_counts.update(capture=0, apply=0)
                self._art_entries, self._art_meta = {}, {}
                self.artifact = None
                self._fault, self._commit_cb = fault, commit
                self._resume_hess, self._last_index = {}, None
                if resume is not None and cfg.family == "encdec":
                    raise NotImplementedError(
                        "resume covers the decoder stack only; "
                        "encoder-decoder calibration restarts from scratch")
                tag2loc: dict[str, tuple] = {}
                report: dict[str, Any] = {"layers": {},
                                          "rsq": dataclasses.asdict(rsq)}
                scheduler = get_scheduler(rsq.scheduler)
                report["scheduler"] = scheduler.name

                calib = expand_dataset(jnp.asarray(calib_tokens),
                                       rsq.expansion)
                counts = jnp.bincount(calib.reshape(-1),
                                      length=cfg.vocab_size
                                      ).astype(jnp.float32)

                if rsq.rotate:
                    params, rotations = rotate_model(params, cfg, model, key)
                    report["rotated"] = True
                else:
                    params = dict(params)
                    rotations = {}
                # decouple the mutable containers we write into from the
                # caller's
                new_params = dict(params)
                if "prefix" in new_params:
                    new_params["prefix"] = list(new_params["prefix"])
                new_params["groups"] = dict(new_params["groups"])
                if "encoder" in new_params:
                    new_params["encoder"] = dict(new_params["encoder"])

                n = calib.shape[0]
                batches = [calib[i : i + batch_size]
                           for i in range(0, n, batch_size)]
                embed = params["embed"]
                acts = [jnp.asarray(embed[b_]).astype(model.dtype)
                        for b_ in batches]

                media_b = None
                if media is not None:
                    media_b = [media[i : i + batch_size]
                               for i in range(0, n, batch_size)]
                self._rc = _RunCtx(calib=calib, counts=counts,
                                   batch_size=batch_size, media_b=media_b,
                                   verbose=verbose)

            # ---------- encoder stack (enc-dec models) then decoder stack
            if cfg.family == "encdec":
                with TRACER.span("pipeline.prepare"):
                    assert frames is not None
                    frames = jnp.asarray(frames)
                    if "frame_proj" in params:
                        frames = frames @ params["frame_proj"].astype(
                            frames.dtype)
                    enc_acts = [frames[i : i + batch_size]
                                for i in range(0, n, batch_size)]
                    # encoder blocks take no media input
                    self._rc.media_b = None
                    enc_groups = params["encoder"]["groups"]
                    enc_tasks = [
                        LayerTask(tag=f"enc{li}",
                                  p_blk=jax.tree.map(lambda a, li=li: a[li],
                                                     enc_groups)["b0"],
                                  meta=model.enc_metas[0])
                        for li in range(cfg.n_encoder_layers)]
                # the encoder's final activations feed the decoder as
                # media, so the last encoder layer must still propagate
                enc_acts, enc_outs = scheduler.run(self, enc_tasks, enc_acts,
                                                   propagate_last=True)
                with TRACER.span("pipeline.writeback"):
                    for li, (p_new, rep) in enumerate(enc_outs):
                        tag2loc[f"enc{li}"] = ("enc", li)
                        report["layers"][f"enc{li}"] = rep
                        new_params["encoder"]["groups"] = jax.tree.map(
                            lambda full, nw, li=li: full.at[li].set(nw),
                            new_params["encoder"]["groups"], {"b0": p_new})
                    enc_acts = [rms_norm(a, params["encoder"]["final_norm"],
                                         cfg.norm_eps) for a in enc_acts]
                    media_b = enc_acts
                    self._rc.media_b = media_b

            # ---------- decoder prefix + groups
            with TRACER.span("pipeline.prepare"):
                def layer_params(li):
                    if li < len(model.prefix_metas):
                        return (params["prefix"][li],
                                model.prefix_metas[li], ("prefix", li))
                    j = li - len(model.prefix_metas)
                    g, o = divmod(j, model.period)
                    blk = jax.tree.map(lambda a: a[g],
                                       params["groups"])[f"b{o}"]
                    return blk, model.group_metas[o], ("groups", g, o)

                n_layers = (len(model.prefix_metas)
                            + model.n_groups * model.period)
                tasks, locs = [], []
                for li in range(n_layers):
                    p_blk, meta, loc = layer_params(li)
                    tasks.append(LayerTask(tag=f"layer{li}", p_blk=p_blk,
                                           meta=meta, index=li))
                    locs.append(loc)
                self._last_index = n_layers - 1
                start, pre_outs = 0, []
                if resume is not None:
                    start = int(resume["start"])
                    solved = {int(k): v for k, v in resume["solved"].items()}
                    assert sorted(solved) == list(range(start)), (
                        f"resume state is not a contiguous solved prefix: "
                        f"{sorted(solved)} vs start={start}")
                    reps = resume.get("reports") or {}
                    for li in range(start):
                        p_new = jax.tree.map(jnp.asarray, solved[li])
                        rep = dict(reps.get(f"layer{li}")
                                   or {"weights": {}, "seconds": 0.0})
                        rep["resumed"] = True
                        pre_outs.append((p_new, rep))
                    # re-place the already-solved layers' propagated
                    # activations — the scheduler continues the stack from
                    # these
                    acts = [self._put_act(a) for a in resume["acts"]]
                    # packed entries folded before the crash: restore in
                    # artifact order (art_meta, carried through JSON,
                    # preserves insertion order; the checkpointed array
                    # tree does not)
                    for name, em in (resume.get("art_meta") or {}).items():
                        self._art_meta[name] = dict(em)
                        self._art_entries[name] = {
                            k: self._put_entry(v)
                            for k, v in resume["art"][name].items()}
                    for li, hs in (resume.get("hessians") or {}).items():
                        self._resume_hess[int(li)] = hs
            # nothing consumes the last decoder layer's outputs — skip its
            # apply pass (one full batch sweep of dispatched-and-discarded
            # work)
            acts, outs = scheduler.run(self, tasks[start:], acts,
                                       propagate_last=False)
            outs = pre_outs + outs
            with TRACER.span("pipeline.writeback"):
                for task, loc, (p_new, rep) in zip(tasks, locs, outs):
                    tag2loc[task.tag] = loc
                    report["layers"][task.tag] = rep
                    if loc[0] == "prefix":
                        new_params["prefix"][loc[1]] = p_new
                    else:
                        _, g, o = loc
                        stacked = new_params["groups"]

                        def set_at(full, nw, g=g):
                            return full.at[g].set(nw.astype(full.dtype))

                        stacked[f"b{o}"] = jax.tree.map(
                            set_at, stacked[f"b{o}"], p_new)
                        new_params["groups"] = stacked

                self._rc = None
                self._fault = self._commit_cb = None
                if rsq.pack_output:
                    for name, em in self._art_meta.items():
                        em["loc"] = list(tag2loc[em["tag"]])
                    self.artifact = {
                        "entries": self._art_entries, "meta": self._art_meta,
                        "spec": {"bits": rsq.bits, "sym": rsq.sym,
                                 "group_size": rsq.group_size,
                                 "method": rsq.method}}
                    report["packed"] = {"entries": len(self._art_entries),
                                        "writeback": rsq.pack_writeback}
                report["rotations"] = {k: (None if v is None else "set")
                                       for k, v in rotations.items()}
                report["trace_counts"] = dict(self.trace_counts)
        return new_params, report


def quantize_model(model: Model, params: dict, calib_tokens,
                   rsq: RSQConfig, *, ctx: ParallelCtx = LOCAL, **kw):
    return RSQPipeline(model, rsq, ctx=ctx).run(params, calib_tokens, **kw)
