"""Bring-up check on a TPU: quantize and serve llama3-8b widths.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four chips: data-parallel calibration

Run from the root of a checkout.  One chip drives both programs of the
repo through their command-line entry points, at the published
llama3-8b widths (d_model 4096, 32 heads over 8 KV heads, d_ff 14336,
vocab 128256) cut to 4 layers, bf16, random weights from the seed:

  (a) device check — the platform must be ``tpu``; there is no fallback;
  (b) quantize — ``launch.quantize`` (rotate, attn_con-scaled Hessians,
      GPTQ, packed artifact) on 16 x 2048 calibration tokens;
  (c) solver check — layer 0's o-projection, solved on the chip by (b),
      against the float64 ``gptq_quantize_ref`` on the same Hessian;
  (d) serve — ``launch.serve --mode engine`` answers 8 requests from the
      artifact on a paged int8 KV cache; then the keep-packed prefill
      (Pallas ``quant_matmul``) against the dequantized prefill, and one
      paged 2-bit-KV decode (Pallas) against its scan reference;
  (e) zero kernel-to-reference demotions, then the one-line JSON result.

``--chips 4`` runs only the data-parallel calibration path
(``launch.quantize --shard-calib``: a 4-device data mesh with streaming
sharded Hessians and sharded write-back) and the one-device run it is
compared with.

Every phase that fails raises, so the script exits non-zero and prints no
result line; the last line of a passing run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import concurrent.futures
import contextlib
import gc
import json
import math
import shutil
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent
ARCH, N_LAYERS, SEED = "llama3-8b", 4, 0
N_CALIB, CALIB_SEQ = 16, 2048          # calibration sequences x tokens
N_REQ, PROMPT_LEN, GEN = 8, 512, 64    # engine requests
KV2 = dict(b=8, kv=8, g=4, d=128, page=64, n_tiles=9)  # kv2 decode widths
WORK = REPO / ".smoke_artifacts"  # gitignored; removed at the end

# Tolerances (stated, with the reason for each):
# GPTQ proxy loss tr((W-Q)ᵀH(W-Q)), chip solve vs the float64 oracle.
# On CPU the f32 blocked solver lands within 1.5e-4 of the oracle
# (d=512); round-to-nearest without compensation is ~30% worse.  1% keeps
# a wide margin over rounding-order flips and still fails a solver whose
# Hessian inverse lost its precision.
SOLVER_LOSS_RTOL = 1e-2
# Keep-packed vs dequantized prefill logits, relative L2 over (8, vocab):
# the two paths round weights and activations to bf16 in different places
# (1.3e-2 measured on CPU for 2 layers at d_model 256, interpret-mode
# kernels); a wrong kernel tile or scale block gives O(1).
PREFILL_LOGIT_RTOL = 1e-1
# Paged kv2 decode, Pallas kernel vs the scan reference at full f32
# matmul precision: both dequantize the same codes, so they differ only
# by the kernel's f32 dot rounding; max |diff| over max |ref|.
KV2_DECODE_RTOL = 1e-2
# Quantized over fp perplexity of the random-init model at 4 bits.
PPL_RATIO_MAX = 1.10
# Four chips: every device's peak memory must show real work — each holds
# the replicated params and its own Hessian partials, several GB.
MIN_DEVICE_PEAK = 1e9
# Four chips: quantized perplexity of the data-mesh run against the
# one-device run (7e-4 apart on CPU at d_model 256, where 16% of packed
# words differ after GPTQ's sweep amplifies last-bit Hessian differences).
MESH_PPL_RTOL = 5e-3


def device_info(n_chips: int) -> dict:
    import jax

    devs = jax.devices()
    d0 = devs[0]
    info = {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}
    print(f"(a) devices: {devs}", flush=True)
    print(f"(a) platform={info['platform']} kind={info['kind']} "
          f"count={info['count']}", flush=True)
    if d0.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU — JAX found platform "
                 f"{d0.platform!r} ({len(devs)} device(s)); this check "
                 f"runs on the chip only")
    if len(devs) < n_chips:
        sys.exit(f"chip_smoke: --chips {n_chips} needs {n_chips} devices, "
                 f"found {len(devs)}")
    return info


def _gb(n: int) -> str:
    return f"{n / 1e9:.2f} GB"


def peak_bytes(device) -> int:
    return (device.memory_stats() or {}).get("peak_bytes_in_use", 0)


@contextlib.contextmanager
def capture_solve_inputs(out: dict, layer: int, path: str):
    """Record the weight and the captured Hessian that the pipeline hands
    to its solver for one weight of one layer (a host copy, so no device
    buffer outlives the solve).  The Hessian of a streaming sharded
    accumulator is recorded reduced, with where its partials lived."""
    from repro.core.pipeline import RSQPipeline
    import numpy as np

    orig = RSQPipeline.layer_solve

    def spy(self, state):
        task = state["task"]
        if task.index == layer and "h" not in out:
            h = state["hessians"][path]
            if h.ndim == 3:  # (S, d, d) partial sums, one per data shard
                out["partials"] = sorted(
                    (sh.device.id, tuple(sh.data.shape))
                    for sh in h.addressable_shards)
                h = h.sum(axis=0)
            node = task.p_blk
            for key in path.split("/"):
                node = node[key]
            out["h"] = np.asarray(h, np.float64)
            out["w"] = np.asarray(node.astype("float32"), np.float64)
        return orig(self, state)

    RSQPipeline.layer_solve = spy
    try:
        yield out
    finally:
        RSQPipeline.layer_solve = orig


def quantize_args(n_layers: int, pack_out: Path, *extra: str) -> list[str]:
    return ["--arch", ARCH, "--n-layers", str(n_layers),
            "--dtype", "bfloat16", "--bits", "4", "--group-size", "128",
            "--importance", "attn_con", "--n-calib", str(N_CALIB),
            "--calib-seq", str(CALIB_SEQ), "--seed", str(SEED),
            "--pack-out", str(pack_out), *extra]


def check_quantize(summary: dict, layers: dict) -> None:
    for tag, rep in layers.items():
        print(f"(b) {tag}: {len(rep['weights'])} weights in "
              f"{rep['seconds']} s", flush=True)
    print(f"(b) ppl_fp={summary['ppl_fp']} ppl_quant="
          f"{summary['ppl_quant']} ppl_ratio={summary['ppl_ratio']}",
          flush=True)
    vals = [summary[k] for k in ("ppl_fp", "ppl_quant", "ppl_ratio")]
    vals += [rep["seconds"] for rep in layers.values()]
    vals += [float(e) for rep in layers.values()
             for e in rep["weights"].values()]
    if not all(math.isfinite(v) for v in vals):
        raise AssertionError(f"non-finite quantizer output: {vals}")
    if not summary["ppl_ratio"] <= PPL_RATIO_MAX:
        raise AssertionError(f"ppl_ratio {summary['ppl_ratio']} > "
                             f"{PPL_RATIO_MAX}")


def solver_check(cap: dict, art: Path, name: str, n_cols: int = 128):
    """Proxy losses of the chip's GPTQ codes and of ``gptq_quantize_ref``
    on the same weight columns and Hessian (GPTQ solves every column
    independently, so a column slice is the same problem)."""
    import numpy as np

    from repro.checkpoint.packed import load_packed_artifact
    from repro.core.gptq import gptq_quantize_ref
    from repro.core.quantizer import QuantSpec, unpack_codes

    entries, meta = load_packed_artifact(art, verify=False)
    e, em = entries[name], meta["entries"][name]
    rsq = meta["extra"]["rsq"]
    spec = QuantSpec(bits=rsq["bits"], group_size=em["group_size"],
                     sym=rsq["sym"])
    codes = np.asarray(unpack_codes(e["codes"], spec.bits, em["d_in"]))
    gs = em["group_size"]
    scale = np.repeat(np.asarray(e["scale"], np.float64), gs, axis=0)
    zero = np.repeat(np.asarray(e["zero"], np.float64), gs, axis=0)
    w_chip = (scale * (codes - zero))[:, :n_cols]
    w, h = cap["w"][:, :n_cols], cap["h"]
    t0 = time.perf_counter()
    w_ref = gptq_quantize_ref(w, h, spec, damp=rsq["damp"])["w_deq"]
    ref_s = time.perf_counter() - t0

    def loss(wq):
        err = w - np.asarray(wq, np.float64)
        return float(np.sum(err * (h @ err)))

    return loss(w_chip), loss(w_ref), ref_s


def serve_args(art: Path, *extra: str) -> list[str]:
    return ["--arch", ARCH, "--n-layers", str(N_LAYERS),
            "--dtype", "bfloat16", "--packed", str(art), "--seed", str(SEED),
            *extra]


def prefill_parity(art: Path) -> float:
    """Relative L2 distance of the last-position prefill logits of the
    engine's prompts: keep-packed (every projection through the Pallas
    ``quant_matmul``) against load-time-dequantized weights."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from repro.checkpoint.packed import (load_packed_forward_params,
                                         load_packed_params)
    from repro.configs import get_config
    from repro.data.synthetic import SyntheticCorpus
    from repro.models import build_model

    cfg = dataclasses.replace(get_config(ARCH).cut_depth(N_LAYERS),
                              dtype="bfloat16", kv_bits=8)
    model = build_model(cfg)
    tokens = SyntheticCorpus(vocab_size=cfg.vocab_size, seed=SEED).sample(
        jax.random.key(1), N_REQ, PROMPT_LEN)
    prefill = jax.jit(lambda p, x: model.prefill(p, x)[0].astype(
        jnp.float32))
    logits = []
    for loader in (load_packed_forward_params, load_packed_params):
        params, _ = loader(art, verify=False)
        logits.append(prefill(params, tokens))
        del params
    packed, deq = logits
    if not bool(jnp.all(jnp.isfinite(packed))):
        raise AssertionError("non-finite keep-packed prefill logits")
    return float(jnp.linalg.norm(packed - deq) / jnp.linalg.norm(deq))


def kv2_decode_parity() -> float:
    """One paged 2-bit-KV decode step at llama3-8b attention widths (8
    requests, 8 KV heads x 4 query heads each, head dim 128, 576-token
    contexts in 64-token pages): the Pallas kernel against the scan
    reference on the same codes."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.flash_decode import paged_flash_decode
    from repro.models.attention import kv_codec

    b, kv, g, d, page, n_tiles = (KV2[k] for k in
                                  ("b", "kv", "g", "d", "page", "n_tiles"))
    codec = kv_codec(2, page)
    ks = jax.random.split(jax.random.key(SEED), 3)
    k = jax.random.normal(ks[0], (b, n_tiles * page, kv, d), jnp.float32)
    v = jax.random.normal(ks[1], (b, n_tiles * page, kv, d), jnp.float32)
    q = jax.random.normal(ks[2], (b, kv, g, d), jnp.float32) * d ** -0.5

    def pool(x):  # (B, S, ...) -> pages 1.. in request order; page 0 trash
        codes, scales = codec.encode(x)
        codes = codes.reshape((b * n_tiles, page) + codes.shape[2:])
        scales = scales.reshape((b * n_tiles, -1) + scales.shape[2:])
        return (jnp.concatenate([jnp.zeros_like(codes[:1]), codes]),
                jnp.concatenate([jnp.zeros_like(scales[:1]), scales]))

    kq, kscale = pool(k)
    vq, vscale = pool(v)
    tbl = jnp.asarray(1 + np.arange(b * n_tiles).reshape(b, n_tiles),
                      jnp.int32)
    pos = jnp.asarray(np.random.default_rng(SEED).integers(
        page, n_tiles * page, size=b), jnp.int32)
    kw = dict(kv_bits=2, chunk=codec.chunk, dv=d, page=page)
    out = paged_flash_decode(tbl, pos, q, kq, kscale, vq, vscale, **kw,
                             use_kernel=True)
    with jax.default_matmul_precision("highest"):
        ref = paged_flash_decode(tbl, pos, q, kq, kscale, vq, vscale, **kw,
                                 use_kernel=False)
    if not bool(jnp.all(jnp.isfinite(out))):
        raise AssertionError("non-finite kv2 decode output")
    return float(jnp.max(jnp.abs(out - ref)) / jnp.max(jnp.abs(ref)))


def one_chip() -> None:
    import jax

    from repro.kernels import demotions
    from repro.launch import quantize, serve

    dev = jax.devices()[0]
    art = WORK / "llama3-8b-L4-w4g128"
    pool = concurrent.futures.ThreadPoolExecutor(max_workers=1)
    try:
        t0 = time.perf_counter()
        with capture_solve_inputs({}, 0, "mixer/wo") as cap:
            out = quantize.main(quantize_args(N_LAYERS, art, "--batch", "2"))
        summary, layers = out["summary"], out["report"]["layers"]
        del out
        gc.collect()
        check_quantize(summary, layers)
        print(f"(b) quantize ok in {time.perf_counter() - t0:.1f} s; peak "
              f"{_gb(peak_bytes(dev))}", flush=True)

        # (c) the float64 oracle takes about a minute of host time: it
        # runs beside (d), and its verdict is checked before (e)
        solver = pool.submit(solver_check, cap, art, "layer0/mixer/wo")

        t0 = time.perf_counter()
        pages = N_REQ * -(-(PROMPT_LEN + GEN) // 64)
        stats = serve.main(serve_args(
            art, "--mode", "engine", "--kv-bits", "8",
            "--batch", str(N_REQ), "--prompt-len", str(PROMPT_LEN),
            "--gen", str(GEN), "--prefill-chunk", str(PROMPT_LEN // 2),
            "--max-slots", str(N_REQ), "--n-pages", str(pages)))
        outs = list(stats["outputs"].values())
        bad = [(o.request_id, o.status, len(o.tokens)) for o in outs
               if o.status != "ok" or len(o.tokens) != GEN]
        if len(outs) != N_REQ or bad:
            raise AssertionError(f"engine requests not all ok: {bad} "
                                 f"({len(outs)} outputs)")
        print(f"(d) engine: {N_REQ}/{N_REQ} requests ok, {GEN} tokens "
              f"each, pages quiescent; {time.perf_counter() - t0:.1f} s; "
              f"peak {_gb(peak_bytes(dev))}", flush=True)
        rel = prefill_parity(art)
        print(f"(d) prefill logits keep-packed vs dequantized: rel L2 "
              f"{rel:.3e} (tol {PREFILL_LOGIT_RTOL})", flush=True)
        if not rel <= PREFILL_LOGIT_RTOL:
            raise AssertionError("keep-packed prefill parity failed")
        rel = kv2_decode_parity()
        print(f"(d) paged kv2 decode kernel vs scan ref: max rel "
              f"{rel:.3e} (tol {KV2_DECODE_RTOL})", flush=True)
        if not rel <= KV2_DECODE_RTOL:
            raise AssertionError("kv2 decode parity failed")

        l_chip, l_ref, ref_s = solver.result()
        drift = abs(l_chip / l_ref - 1)
        print(f"(c) GPTQ proxy loss, layer0/mixer/wo (first 128 columns): "
              f"chip {l_chip:.6e} vs float64 ref {l_ref:.6e} "
              f"({ref_s:.0f} s on the host): rel {drift:.3e} "
              f"(tol {SOLVER_LOSS_RTOL})", flush=True)
        if not drift <= SOLVER_LOSS_RTOL:
            raise AssertionError("solver check failed")
    finally:
        pool.shutdown(wait=True)
        shutil.rmtree(WORK, ignore_errors=True)
    dem = demotions.counts()
    print(f"(e) kernel-to-reference demotions: {dem}; peak "
          f"{_gb(peak_bytes(dev))}", flush=True)
    if dem:
        raise AssertionError(f"kernels demoted to their references: {dem}")


def _proxy_loss(report: dict) -> float:
    return sum(float(e) for rep in report["layers"].values()
               for e in rep["weights"].values())


def four_chips() -> None:
    """``launch.quantize --shard-calib`` over a 4-device data mesh against
    the one-device run, held to the fake-mesh tests' standards: the
    streaming Hessian partials live one per device and reduce to the
    dense Hessian within 1e-5 (test_scheduler), the sharded write-back
    never gathers a full array to the host and its artifact reloads to
    exactly the params the run returned (test_calib_shard).  GPTQ's
    greedy sweep turns a last-bit Hessian difference into a different but
    equally good code sequence, so the two artifacts are compared by
    quality — summed proxy loss and perplexity — not code for code."""
    import jax
    import numpy as np

    from repro.checkpoint import packed as cp
    from repro.launch import quantize

    n_layers = 2
    one, four = WORK / "one-device", WORK / "data-mesh"
    try:
        # the one-device run keeps the one-chip smoke's batch (its layer
        # programs are then the same shapes); the mesh needs one sequence
        # per device per batch
        with capture_solve_inputs({}, 0, "mixer/wo") as cap1:
            out = quantize.main(quantize_args(n_layers, one, "--batch", "2"))
        s1, err1 = out["summary"], _proxy_loss(out["report"])
        del out
        gc.collect()
        gathers = []
        orig_gather = cp._host_gather
        cp._host_gather = lambda x: (gathers.append(np.shape(x)),
                                     orig_gather(x))[1]
        try:
            with capture_solve_inputs({}, 0, "mixer/wo") as cap4:
                out = quantize.main(quantize_args(
                    n_layers, four, "--batch", "4", "--shard-calib"))
        finally:
            cp._host_gather = orig_gather
        s4, q4 = out["summary"], out["params"]
        err4 = _proxy_loss(out["report"])
        del out
        peaks = [peak_bytes(d) for d in jax.devices()]
        print(f"sharded run: Hessian partials (device, shape) = "
              f"{cap4.get('partials')}; per-device peak "
              f"{[_gb(p) for p in peaks]}", flush=True)
        devices = sorted(i for i, _ in cap4.get("partials", []))
        if devices != sorted(d.id for d in jax.devices()):
            raise AssertionError("Hessian partials not one per device")
        if min(peaks) < MIN_DEVICE_PEAK:
            raise AssertionError(f"a device did no real work: {peaks}")
        h1, h4 = cap1["h"], cap4["h"]
        rel = float(np.abs(h4 - h1).max() / np.abs(h1).max())
        print(f"Hessian layer0/mixer/wo: sharded vs one-device max rel "
              f"{rel:.3e} (tol 1e-5)", flush=True)
        if not rel < 1e-5:
            raise AssertionError("sharded Hessian differs from dense")
        if gathers:
            raise AssertionError(f"sharded write-back gathered {gathers}")
        recon, _ = cp.load_packed_params(four, verify=True)
        same = all(bool((x == y).all()) for x, y in zip(
            jax.tree.leaves(q4), jax.tree.leaves(recon)))
        del recon, q4
        if not same:
            raise AssertionError("sharded artifact does not reload to the "
                                 "params its run returned")
        e1, _ = cp.load_packed_artifact(one, verify=False)
        e4, _ = cp.load_packed_artifact(four, verify=False)
        if sorted(e1) != sorted(e4):
            raise AssertionError("artifacts hold different entries")
        agree = np.mean(np.concatenate([
            (np.asarray(e1[n]["codes"]) == np.asarray(e4[n]["codes"]))
            .ravel() for n in e1]))
        d_err = abs(err4 / err1 - 1)
        d_ppl = abs(s4["ppl_quant"] / s1["ppl_quant"] - 1)
        print(f"artifact: {len(e1)} entries, sharded write-back with 0 host "
              f"gathers, reloads exactly; packed words equal to the "
              f"one-device run: {agree:.6f}; summed GPTQ proxy loss "
              f"{err1:.6e} vs {err4:.6e} (rel {d_err:.2e}, tol "
              f"{SOLVER_LOSS_RTOL}); ppl_quant one-device {s1['ppl_quant']} "
              f"vs mesh {s4['ppl_quant']} (rel {d_ppl:.2e}, tol "
              f"{MESH_PPL_RTOL})", flush=True)
        if not (d_err <= SOLVER_LOSS_RTOL and d_ppl <= MESH_PPL_RTOL):
            raise AssertionError("data-mesh quantization drifted from the "
                                 "one-device run")
    finally:
        shutil.rmtree(WORK, ignore_errors=True)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    info = device_info(args.chips)
    sys.path.insert(0, str(REPO / "src"))
    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips()
    else:
        one_chip()
    print(f"total {time.perf_counter() - t0:.1f} s", flush=True)
    print(json.dumps({"ok": True, "device": info}))


if __name__ == "__main__":
    main()
