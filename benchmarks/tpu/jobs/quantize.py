"""Quantizer cells: ``RSQPipeline`` on a seeded model, pass after pass.

Set-up: the model's bf16 weights from the seed, on the device in one
call (norm gains 1, standing for the rotated model: rotation is a
one-off step before calibration, and a rotated random Gaussian weight is
again a random Gaussian weight); the calibration tokens from the seed
(``gen.zipf_corpus``); one whole pass of the pipeline, which compiles
the capture, apply and solve programs.

Window: passes of ``RSQPipeline.run`` over the same weights and tokens,
each quantizing every layer of the cut model in turn (AttnCon importance,
Hessian capture, GPTQ, propagation of the quantized outputs), until
``--seconds`` have passed; the window ends at the end of a pass, so at a
layer boundary.  ``quant_layer_s`` is the window over the layers
completed in it.  The pipeline's per-meta trace cache must show no
retrace in any window pass.

Correctness: after the window, the plain reference (``reference/``) runs
RSQ on layers 0 .. ``check_layers`` - 1 from the same seed; for every
quantized weight of those layers, the proxy loss tr((W-Q)^T H (W-Q)) of
the program's Q and of the reference's Q, both under the reference's
Hessian H.  GPTQ's sweep turns last-bit differences into different but
equally good codes, so the codes are not compared one by one; a fault
in importance, Hessian, solve or propagation moves the loss.
"""
from __future__ import annotations

import gc
import threading
import time

from benchmarks.tpu import gen, harness, modelcfg, spec, weights

log = harness.log


def _annotate():
    import jax

    return jax.profiler.TraceAnnotation


def setup(run):
    import jax

    from repro.core.pipeline import RSQConfig, RSQPipeline
    from repro.models import build_model

    c, job = run.cell.config, run.cell.traffic
    cfg = modelcfg.model_config(c, name=run.cell.entry["config"])
    model = build_model(cfg)
    params = weights.dense_params(model, run.seed)
    calib = calib_tokens(run)
    jax.block_until_ready((params, calib))
    rsq = RSQConfig(bits=job["bits"], group_size=job["group_size"], sym=True,
                    rotate=False, importance=job["importance"],
                    r_min=job["r_min"], r_max=job["r_max"],
                    damp=job["damp"], scheduler=job["scheduler"],
                    seed=0)
    return model, params, calib, RSQPipeline(model, rsq)


def calib_tokens(run):
    job = run.cell.traffic
    return gen.zipf_corpus(weights.leaf_key(run.seed, "calibration"),
                           job["n_calib"], job["calib_seq"],
                           modelcfg.hf_dims(run.cell.config)["vocab"])


STAGES = ("layer_begin", "layer_capture", "layer_solve", "layer_apply",
          "layer_finalize")


def span_stages(pipe, annotate, taken: dict):
    """Host spans around the pipeline's per-layer stages, opened from the
    benchmark's side (the scheduler calls them on the instance).  While
    ``taken["on"]`` is set, the solve stage of layer 0 also hands the
    Hessians it is given to ``taken["h"]``: the capture stage's answer,
    compared with the reference's."""
    for name in STAGES:
        fn = getattr(pipe, name)

        def spanned(*a, _fn=fn, _name=name, **kw):
            if (_name == "layer_solve" and taken.get("on")
                    and a[0]["task"].index == 0):
                taken["h"] = dict(a[0]["hessians"])
                taken["on"] = False
            with annotate(f"bench:{_name}"):
                return _fn(*a, **kw)

        setattr(pipe, name, spanned)


def one_pass(pipe, params, calib, batch, annotate):
    import jax

    with annotate("bench:pass"):
        new, report = pipe.run(params, calib, batch_size=batch)
        jax.block_until_ready(new)
    return new, report


def warm_up(pipe, params, calib, batch, n_check, vocab, annotate):
    """Compile every program of a pass in a pass over one calibration
    batch: the per-layer programs see the same shapes as in the window;
    the token counts over the whole calibration set are the one
    whole-set computation of a pass, run here at its full size."""
    import jax.numpy as jnp

    new, _ = one_pass(pipe, params, calib[:batch], batch, annotate)
    kept_layers(new, n_check)  # compiles the slicing
    jnp.bincount(jnp.asarray(calib).reshape(-1),
                 length=vocab).astype(jnp.float32).block_until_ready()


def kept_layers(new, n: int) -> dict:
    """float32 copies of layers 0 .. n-1's quantized projections."""
    import jax.numpy as jnp

    g = new["groups"]["b0"]
    return {p: jnp.asarray(g[p.split("/")[0]][p.split("/")[1]][:n],
                           jnp.float32) for p in modelcfg.LINEARS}


def check(run, kept, h_prog) -> tuple[list, dict]:
    """Against the reference's RSQ on layers 0 .. check_layers - 1: the
    Hessians that the program's solver got for layer 0 (relative
    Frobenius distance, worst weight), and for every layer the proxy
    loss of the program's quantized weights against the reference's,
    both under the reference's Hessians (worst |ratio - 1|).  With
    ``--control 1`` the reference at float8 activations takes the
    program's place: its Hessians and Q give the numbers, and
    ``correct``."""
    import jax.numpy as jnp

    ref = spec.reference_module(run.cell)
    job, p = run.cell.traffic, run.cell.params
    n = p["check_layers"]
    calib = calib_tokens(run)
    kw = dict(bits=job["bits"], group_size=job["group_size"],
              damp=job["damp"], r_min=job["r_min"], r_max=job["r_max"],
              batch=job["batch"])
    t0 = time.perf_counter()
    main = ref.rsq_layers(run.cell.config, run.seed, calib, n, **kw)
    low = (ref.rsq_layers(run.cell.config, run.seed, calib, n,
                          act_dtype=jnp.float8_e4m3fn, **kw)
           if run.control else None)
    loss, h_rel, per, ctrl = {}, 0.0, {}, {}
    for li, w, h, q in main:
        lw = next(low) if low is not None else None
        for path in modelcfg.LINEARS:
            l_ref = float(ref.proxy_loss(w[path], q[path], h[path]))
            l_prog = float(ref.proxy_loss(w[path], kept[path][li], h[path]))
            rel = abs(l_prog / l_ref - 1.0)
            per[f"layer{li}/{path}"] = rel
            loss[li] = max(loss.get(li, 0.0), rel)
            if li == 0:
                h_rel = max(h_rel, _frob_rel(h_prog[path], h[path]))
            if lw is not None:
                l_low = float(ref.proxy_loss(w[path], lw[3][path], h[path]))
                ctrl[f"loss_rel_layer{li}"] = max(
                    ctrl.get(f"loss_rel_layer{li}", 0.0),
                    abs(l_low / l_ref - 1.0))
                if li == 0:
                    ctrl["hessian_rel_layer0"] = max(
                        ctrl.get("hessian_rel_layer0", 0.0),
                        _frob_rel(lw[2][path], h[path]))
        log(f"reference layer {li} at {time.perf_counter() - t0!r} s")
    extra = {"reference_s": time.perf_counter() - t0, "per_weight": per}
    if run.control:
        # the control's Hessians and Q in the program's place
        extra["answers"] = "the control's (reference at float8 activations)"
        h_rel = ctrl["hessian_rel_layer0"]
        loss = {li: ctrl[f"loss_rel_layer{li}"] for li in loss}
    lim = p["limits"]
    checks = [{"name": "hessian_rel_layer0", "value": h_rel,
               "limit": lim["hessian_rel_layer0"],
               "rule": "||H_prog - H_ref||_F / ||H_ref||_F of the Hessians "
                       "the solver got, worst weight of layer 0"}]
    checks += [{"name": f"loss_rel_layer{li}", "value": loss[li],
                "limit": lim[f"loss_rel_layer{li}"],
                "rule": f"|L(Q_prog)/L(Q_ref) - 1| under the reference "
                        f"Hessian, worst weight of layer {li}"}
               for li in sorted(loss)]
    return checks, extra


def _frob_rel(a, b) -> float:
    import jax.numpy as jnp

    a, b = jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32)
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def run(run):
    import jax

    from repro.kernels import demotions

    job, p = run.cell.traffic, run.cell.params
    annotate = _annotate()
    model, params, calib, pipe = setup(run)
    taken = {}
    span_stages(pipe, annotate, taken)
    n_layers = model.cfg.n_layers
    log(f"weights and tokens at {time.perf_counter() - run.t_start!r} s")
    with annotate("bench:warmup"):
        warm_up(pipe, params, calib, job["batch"], p["check_layers"],
                model.cfg.vocab_size, annotate)
    c0 = run.compiles.snapshot()
    setup_s = run.setup_done()

    stop = None
    if run.trace:
        # one traced stretch at the start of the first window pass
        run.tracer.start()
        stop = threading.Timer(p.get("trace_s", 5.0),
                               lambda: run.tracer.stop(whole=True))
    t0 = time.perf_counter()
    if stop is not None:
        stop.start()
    passes, kept, retraces, pass_s = 0, None, [], []
    taken["on"] = True
    while True:
        t1 = time.perf_counter()
        new, report = one_pass(pipe, params, calib, job["batch"], annotate)
        pass_s.append(time.perf_counter() - t1)
        passes += 1
        retraces.append(report["trace_counts"])
        if kept is None:
            kept = kept_layers(new, p["check_layers"])
        del new, report
        if time.perf_counter() - t0 >= run.seconds:
            break
    window = time.perf_counter() - t0
    if stop is not None:
        stop.join()
    c1 = run.compiles.snapshot()
    mem = harness.peak_bytes(jax.devices()[:run.cell.chips])
    inv = run.invariants
    inv["passes"] = f"{passes} of {n_layers} layers; seconds {pass_s!r}"
    inv["retraces_in_window"] = retraces
    inv["compiles_in_window"] = run.compiles.since(c0, c1)
    inv["kernel_demotions"] = demotions.counts()
    inv["memory_peak_bytes"] = mem
    metrics = {"setup_s": setup_s,
               "quant_layer_s": window / (passes * n_layers)}
    log(f"quant_layer_s {metrics['quant_layer_s']!r} over {passes} passes")
    readings = {"config": run.cell.config, "job": job, "params": p,
                "n_layers": n_layers, "pass_s": pass_s}
    del params, pipe
    gc.collect()
    checks, extra = check(run, kept, taken["h"])
    correct = all(c["value"] <= c["limit"] for c in checks)
    return {"correct": correct, "attempted": passes * n_layers, "failed": 0,
            "metrics": metrics, "checks": checks, "readings": readings,
            "memory_peak_bytes": mem, "extra": extra}
