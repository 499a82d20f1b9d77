"""RSQ quantization driver — the paper's main entry point.

Loads (or trains) a model, builds the calibration set, runs the
Rotate-Scale-Quantize pipeline, reports perplexity deltas vs the fp model,
and optionally packs the quantized weights for the serving kernel.

  PYTHONPATH=src python -m repro.launch.quantize --arch llama3-8b-smoke \
      --bits 3 --importance attn_con --expansion 8

Pod-scale data path: ``--shard-calib`` draws the calibration set as
disjoint per-data-group shards assembled into a globally-sharded array
(no host ever materializes the unsharded batch) and turns the streaming
sharded Hessian accumulators on; ``--pack-out DIR`` writes the packed
serving artifact (codes packed on device, sharded write-back) that
``launch.serve --packed DIR`` loads without unpacking on host.

Fault tolerance (resume / verify workflow)
------------------------------------------
``--save-every-layers N`` runs the pipeline under a
``core.resume.QuantizeRunner``: progress is checkpointed at layer-solve
granularity (solved params, propagated activations, packed entries,
loader state) into ``--progress-dir`` (default ``<pack-out>.progress``).
A killed job restarts with the *same* command plus ``--resume``: the
runner restores the latest layer checkpoint, reseeks the calibration
loader, skips the solved prefix and continues mid-stack — the final
packed artifact is byte-identical to a run that never died (pinned by
tests/test_resume.py).  ``--fail-at LAYER:STAGE[:COUNT]`` (repeatable,
``STAGE in {capture, solve, apply, pack}``) injects failures at stage
dispatch points to exercise the recovery path; ``--max-restarts`` bounds
the in-process retry loop (exponential backoff between attempts).

The packed artifact itself is durable: every file is written to a temp
path and atomically renamed, and ``meta.json`` records per-file SHA-256
checksums (format v3) that ``launch.serve --packed`` re-verifies at load
(``--no-verify`` opts out; corrupt files fail with
``checkpoint.ArtifactCorruptError``).
"""
from __future__ import annotations

import argparse
import dataclasses
import json

import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointManager
from repro.checkpoint.packed import save_packed_artifact
from repro.configs import get_config
from repro.core import RSQConfig, RSQPipeline
from repro.data.calibration import calibration_set
from repro.data.loader import CalibrationLoader
from repro.data.synthetic import SyntheticCorpus
from repro.launch.mesh import make_mesh
from repro.models import build_model
from repro.runtime.sharding import LOCAL, ParallelCtx


def eval_ppl(model, params, tokens, batch: int = 8) -> float:
    losses, n = 0.0, 0
    loss_fn = jax.jit(model.loss)
    for i in range(0, tokens.shape[0], batch):
        b = tokens[i : i + batch]
        losses += float(loss_fn(params, {"tokens": b, "labels":
                                         jnp.roll(b, -1, axis=1)})) * b.shape[0]
        n += b.shape[0]
    return float(jnp.exp(losses / n))


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3-8b-smoke")
    ap.add_argument("--n-layers", type=int, default=None,
                    help="depth cut: keep the arch's first N decoder "
                    "layers, every width as published (recorded in the "
                    "packed artifact; launch.serve must be given the same)")
    ap.add_argument("--ckpt", default=None, help="trained checkpoint dir")
    ap.add_argument("--bits", type=int, default=3)
    ap.add_argument("--group-size", type=int, default=128)
    ap.add_argument("--importance", default="attn_con")
    ap.add_argument("--r-min", type=float, default=0.01)
    ap.add_argument("--no-rotate", action="store_true")
    ap.add_argument("--method", default="gptq", choices=["gptq", "ldlq"])
    ap.add_argument("--scheduler", default="auto",
                    choices=["auto", "sequential", "overlapped"],
                    help="layer scheduler (auto: sequential on CPU, "
                    "overlapped on accelerators)")
    ap.add_argument("--shard-hessians", type=int, default=0,
                    help="0: dense accumulators; S>1: S streaming "
                    "partial-sum shards (single-host streaming; on a mesh "
                    "the shard axis lands on the data axes via the "
                    "pipeline's ParallelCtx)")
    ap.add_argument("--shard-calib", action="store_true",
                    help="sharded calibration loading: every data-parallel "
                    "group draws its own disjoint, (seed, shard)-"
                    "deterministic slice of the calibration set and the "
                    "slices assemble into one globally-sharded array — the "
                    "unsharded batch never exists on any host.  With >1 "
                    "local device this builds a data mesh over all devices "
                    "and also enables the streaming sharded Hessian "
                    "accumulators; with 1 device it degenerates to the "
                    "global draw (bit-identical tokens either way)")
    ap.add_argument("--pack-out", default=None, metavar="DIR",
                    help="write the packed serving artifact here: per-"
                    "weight int codes packed on device (sharded write-back "
                    "— no host copy of any unsharded (q, scales) tensor) "
                    "plus the fp residual tree; load with launch.serve "
                    "--packed DIR or checkpoint.packed.load_packed_params")
    ap.add_argument("--save-every-layers", type=int, default=0, metavar="N",
                    help="checkpoint quantization progress every N layer "
                    "solves into --progress-dir (0: no progress "
                    "checkpointing).  A killed run restarts with --resume "
                    "and continues mid-stack, byte-identical to an "
                    "uninterrupted run")
    ap.add_argument("--progress-dir", default=None, metavar="DIR",
                    help="progress-checkpoint directory (default: "
                    "<pack-out>.progress, or ./quantize_progress without "
                    "--pack-out)")
    ap.add_argument("--resume", action="store_true",
                    help="continue from the latest progress checkpoint in "
                    "--progress-dir (without this flag an existing progress "
                    "dir is an error, not a silent restart)")
    ap.add_argument("--fail-at", action="append", default=[],
                    metavar="LAYER:STAGE[:COUNT]",
                    help="inject a failure at a stage dispatch point "
                    "(stage: capture|solve|apply|pack); repeatable — "
                    "exercises the recovery path end to end")
    ap.add_argument("--max-restarts", type=int, default=3,
                    help="bound on in-process recovery restarts "
                    "(exponential backoff between attempts)")
    ap.add_argument("--kv-bits", type=int, default=None,
                    help="serving KV-cache precision recorded in the model "
                    "config and the packed artifact's metadata: 0 = fp "
                    "cache, 8 = int8 + per-token scales, 2 = packed log "
                    "codes + per-chunk scales (weight quantization itself "
                    "is unaffected; launch.serve --kv-bits applies it at "
                    "serving time)")
    ap.add_argument("--expansion", type=int, default=1)
    ap.add_argument("--n-calib", type=int, default=32)
    ap.add_argument("--calib-seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--dtype", default="float32")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None, help="write report JSON here")
    args = ap.parse_args(argv)

    cfg = dataclasses.replace(get_config(args.arch), dtype=args.dtype)
    if args.n_layers is not None:
        try:
            cfg = cfg.cut_depth(args.n_layers)
        except ValueError as e:
            ap.error(str(e))
    if args.kv_bits is not None:
        if args.kv_bits not in (0, 2, 8):
            ap.error(f"--kv-bits {args.kv_bits} is not supported — use 0 "
                     "(KV cache in the activation dtype), 8 (int8 + "
                     "per-token scales) or 2 (packed log codes + "
                     "per-chunk scales)")
        cfg = dataclasses.replace(cfg, kv_bits=args.kv_bits)
    model = build_model(cfg)
    if args.ckpt:
        params = CheckpointManager(args.ckpt).restore()[1]["params"]
    else:
        params = jax.jit(model.init)(jax.random.key(args.seed))

    corpus = SyntheticCorpus(vocab_size=cfg.vocab_size, seed=args.seed)
    ctx = LOCAL
    loader = None
    if args.shard_calib:
        n_dev = jax.device_count()
        if n_dev > 1:
            mesh = make_mesh((n_dev,), ("data",))
            ctx = ParallelCtx(mesh=mesh, dp=("data",))
        loader = CalibrationLoader(corpus, args.n_calib, args.calib_seq,
                                   ctx=ctx, batch_size=args.batch,
                                   seed=args.seed)
        calib = loader.dataset()
    else:
        calib = calibration_set(cfg.vocab_size, args.n_calib, args.calib_seq,
                                seed=args.seed, corpus=corpus)
    heldout = corpus.sample(jax.random.key(12345), args.n_calib,
                            args.calib_seq)

    if args.shard_hessians == -1 and not ctx.enabled:
        # True (shard over mesh data axes) needs a mesh-enabled ParallelCtx;
        # only --shard-calib with >1 local device builds one — refuse
        # rather than silently falling back to dense accumulators
        ap.error("--shard-hessians -1 (mesh mode) needs --shard-calib and "
                 ">1 local device (which build the data mesh); or pass an "
                 "explicit shard count S>1")
    shard_h = (True if args.shard_hessians == -1
               else args.shard_hessians if args.shard_hessians > 1 else False)
    if args.shard_calib and ctx.enabled and not shard_h:
        shard_h = True  # sharded batches feed sharded accumulators directly
    rsq = RSQConfig(bits=args.bits, group_size=args.group_size,
                    rotate=not args.no_rotate, importance=args.importance,
                    r_min=args.r_min, expansion=args.expansion,
                    method=args.method, seed=args.seed,
                    scheduler=(None if args.scheduler == "auto"
                               else args.scheduler),
                    shard_hessians=shard_h,
                    pack_output=args.pack_out is not None)
    base_ppl = eval_ppl(model, params, heldout, args.batch)
    pipe = RSQPipeline(model, rsq, ctx=ctx)
    use_runner = (args.resume or args.save_every_layers > 0
                  or args.progress_dir is not None or bool(args.fail_at))
    runner = None
    if use_runner:
        from repro.core.resume import QuantizeRunner
        from repro.runtime.fault import FaultPlan, RetryPolicy

        progress = args.progress_dir or (
            args.pack_out + ".progress" if args.pack_out
            else "quantize_progress")
        ckpt = CheckpointManager(progress)
        if ckpt.latest_step() is not None and not args.resume:
            ap.error(f"progress dir {progress!r} holds checkpoints from a "
                     f"previous run; pass --resume to continue it, or "
                     f"remove the directory to start over")
        fault = FaultPlan.parse(args.fail_at) if args.fail_at else None
        runner = QuantizeRunner(
            pipe, ckpt, save_every_layers=max(args.save_every_layers, 1),
            policy=RetryPolicy(max_restarts=args.max_restarts),
            loader=loader, resume=args.resume, verbose=True)
        qparams, report = runner.run(params, calib, fault=fault,
                                     batch_size=args.batch, verbose=True)
    else:
        # hand the pipeline the only reference to the fp weights: it
        # rotates them into fresh arrays, so the fp copy is freed then
        # rather than held beside the rotated and quantized copies (at
        # published widths the three do not fit one 16 GB chip with the
        # Hessians)
        fp = [params]
        del params
        qparams, report = pipe.run(fp.pop(), calib, batch_size=args.batch,
                                   verbose=True)
    q_ppl = eval_ppl(model, qparams, heldout, args.batch)
    summary = {
        "arch": args.arch, "n_layers": cfg.n_layers,
        "rsq": dataclasses.asdict(rsq),
        "ppl_fp": base_ppl, "ppl_quant": q_ppl,
        "ppl_ratio": q_ppl / base_ppl,
        "n_weights": sum(len(l["weights"]) for l in report["layers"].values()),
    }
    if runner is not None:
        summary["fault_tolerance"] = {
            "restarts": runner.restarts,
            "ckpt_overhead_s": round(runner.ckpt_overhead_s, 4),
            "events": [e["kind"] for e in runner.events],
        }
    if args.pack_out:
        save_packed_artifact(args.pack_out, pipe.artifact, params=qparams,
                             extra={"arch": args.arch,
                                    "n_layers": cfg.n_layers,
                                    "rsq": dataclasses.asdict(rsq),
                                    "kv_bits": cfg.kv_bits})
        summary["pack_out"] = args.pack_out
    print(json.dumps(summary, indent=2))
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"summary": summary, "report": report}, f, indent=2,
                      default=str)
    return {"params": qparams, "summary": summary, "report": report}


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
