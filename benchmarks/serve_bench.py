"""Packed-in-HBM serving benchmark (serving-memory + throughput trajectory).

Quantizes a smoke-sized model once (RSQ, 4-bit, ``pack_output``), persists
the packed artifact, then serves it two ways through the *same* model
code:

  * **fp (dequantized)** — ``load_packed_params``: fp weights rebuilt on
    device at load (the pre-PR-4 ``--packed`` behaviour), plain ``x @ w``
    GEMMs.
  * **packed (keep-packed)** — ``load_packed_forward_params``: the param
    tree holds ``PackedWeight`` codes and every projection runs through
    ``quant_matmul``; no fp copy of a quantized weight ever exists.

Decode is timed on the **fused scan loop** (``launch.serve`` PR 5): one
jitted ``lax.scan`` device program for all ``GEN`` steps, on-device
greedy sampling, donated KV cache.  The legacy one-dispatch-per-token
loop is timed alongside (``decode_tok_s_python``) so the JSON records
the fusion win — the PR-4 numbers showed packed decode *losing* to fp
(3112 vs 4019 tok/s) purely because per-token Python dispatch swamped the
memory-bound GEMMs the packed kernel accelerates.

Reported per path: prefill and decode tok/s plus a gated
``steady_total_s`` (min over interleaved reps of one prefill +
``GEN`` scan-decoded steps on persistent jits — dispatch + execute only;
interleaving the two paths decorrelates machine drift from the path
identity, same trick as pipeline_bench's scheduler timing, and the
min approximates the uncontended machine under this shared container's
load spikes — see ``_ServeTimer.stats``),
and the resident weight bytes of the quantized matrices (fp vs packed,
ratio ~= bits/32 at fp32 params plus group-param overhead).  Results
land in ``BENCH_serve.json`` at the repo root; ``benchmarks/run.py``
applies its >20% regression gate to the ``steady_total_s`` fields plus
its ``SERVE_RATIO_TOL`` gate to the packed/fp decode ratio
(``decode_vs_fp_ratio``: best packed rep over best fp rep, see the
comment in :func:`run`) — packed decode slower than fp (beyond
tolerance) is a regression of the refactor's whole point, not machine
noise — advisory by construction (the CI bench-guard job is
non-blocking).

A **long-context leg** (PR 7) varies the KV-cache codec instead of the
weight representation: decode tok/s vs cached length (512, 2048 rows) for
the fp, int8 and 2-bit-log caches on one shared param tree, plus the
allocated ``kv_cache_resident_bytes`` and ``kv_bytes_ratio_vs_bf16``
(~ bits/16 plus scale rows).  ``run.py`` gates the quantized-vs-fp decode
ratio at the longest length with the same ``SERVE_RATIO_TOL`` — a
quantized cache that decodes slower than fp defeats its purpose.

An **engine leg** (PR 8) benches the continuous-batching serve engine
(``repro.serving``): the same request set — one prompt length, budgets
cycling 3 short : 1 long — runs through the engine on block-paged
kv8 pools under a Poisson arrival trace and through ``generate`` in
arrival-ordered max_slots-sized waves padded to each wave's longest
budget.  Only
requested tokens count on both sides; ``run.py`` gates
``sustained_vs_fixed_ratio`` (fixed over engine sustained tok/s) at
SERVE_RATIO_TOL — continuous batching must not lose sustained
throughput to the fixed batch at equal load — and the engine wall time
rides the generic ``steady_total_s`` gate.  p50/p99 request latency is
recorded ungated (latency is arrival-pattern-shaped, not a regression
signal at this scale).

PR 9 adds **chunked admission** to the engine leg: the same trace runs a
second time with ``prefill_chunk=ENG_CHUNK`` (prompts stream in
page-aligned chunks interleaved with decode bursts instead of one
whole-prompt prefill per admission), recording ``ttft_p50_s`` /
``ttft_p99_s`` and the split of time to first token that the program's
tracer records (``queue_wait_p90_s``, ``ingest_s_per_ktok``) for both
admission modes.  ``run.py`` gates ``chunked.chunked_vs_whole_ratio``
(whole-prompt over chunked sustained tok/s) and
``chunked.p99_vs_whole_ratio`` (chunked over whole-prompt p99 latency)
at the same SERVE_RATIO_TOL.

PR 10 adds an **overload leg**: the engine again, but with the page pool
halved against hot demand (2x oversubscription), a bounded queue and one
expired deadline, recording the preemption / shed / deadline counters,
per-status latency percentiles and p99 under overload.  Its gate is
*terminality* — ``run.py`` fails when ``overload.all_terminal`` is false
(a request that never reaches a definite status is a hang, not noise) —
while the timing rides the generic ``steady_total_s`` gate.

With >= 8 devices (CI's fake-8-device matrix entry) an extra **mesh leg**
runs: a kernel-aligned model (every quantized d_out a multiple of
128 x model-axis) is calibrated under a (2 data x 4 model) mesh, served
keep-packed with ``REPRO_QMM_KERNEL=1``, and the run asserts the
shard_map'd Pallas route carried every projection (zero ref-GEMM
fallbacks).  Its timing is recorded ungated (``mesh_total_s``) — it only
exists on multi-device runs, and interpret-mode kernels are a
correctness tool, not a fast path.

The portable claims are the resident-bytes ratio and the modeled TPU
decode bound (``tpu_decode_roofline``): decode is weight-HBM-bound, so
packed codes cap per-token weight traffic at bits/16 of a bf16 model.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
import shutil
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp

from benchmarks.common import Table

OUT_PATH = Path(__file__).resolve().parent.parent / "BENCH_serve.json"

ARCH = "llama3-8b"
N_LAYERS = 4
D_MODEL = 64
BATCH, PROMPT, GEN = 8, 128, 32
REPS = 9
BITS = 4

# mesh leg (only with >= 8 devices): every quantized d_out must split into
# 128-aligned local tiles across the 4-way model axis for the shard_map'd
# kernel to run
MESH_D_MODEL, MESH_LAYERS, MESH_BATCH, MESH_PROMPT, MESH_GEN = 512, 2, 2, 16, 8
MESH_REPS = 3

# long-context leg (PR 7): decode tok/s vs cached length for the fp, int8
# and 2-bit-log KV caches, same weights throughout — the cache codec is
# the only variable.  Lengths are allocated cache rows (prompt = S - GEN).
LC_BATCH, LC_GEN, LC_REPS = 4, 32, 3
LC_LENGTHS = (512, 2048)

# engine leg (PR 8): continuous batching on paged quantized KV vs the
# fixed batch at equal load.  The workload is the mixed one continuous
# batching targets — mostly short requests with an occasional long one
# (3 short : 1 long in arrival order), so every fixed wave is dragged to
# the long budget and burns (long - short) wasted steps per short
# request while the engine retires shorts and backfills their slots.
# PR 9 runs the engine twice — whole-prompt vs chunked admission
# (--prefill-chunk ENG_CHUNK) — recording ttft/queue wait/ingest for both;
# the 96-token prompt makes chunking non-degenerate (2 chunks/request).
ENG_N_REQ, ENG_PROMPT, ENG_SLOTS, ENG_PAGES = 12, 96, 4, 16
ENG_BURST, ENG_BUDGETS, ENG_RATE, ENG_REPS = 8, (8, 8, 8, 128), 2.0, 5
ENG_CHUNK = 64

# overload leg (PR 10): the same engine with the page pool halved against
# hot demand (max_slots x pages-per-request = 2 x n_pages), a bounded
# queue and one sub-second deadline — preemption-and-requeue, shedding
# and deadline expiry all fire on a real trace.  The leg's gate is
# *terminality*: every submission must end in exactly one definite
# status with the pool quiescent (run.py fails on all_terminal=False);
# p99-under-overload and the preemption/shed counters are recorded
# ungated (they are workload-shaped, not regression signals).
OVL_N_REQ, OVL_BUDGET, OVL_RATE, OVL_REPS = 10, 32, 3.0, 2
OVL_QUEUE_DEPTH = 6


def _quantize_to_artifact(cfg, ctx=None, calib_rows=16, calib_len=64,
                          batch_size=8):
    from repro.core import RSQConfig, RSQPipeline
    from repro.data.synthetic import SyntheticCorpus
    from repro.models import build_model
    from repro.checkpoint.packed import save_packed_artifact

    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.key(0))
    corpus = SyntheticCorpus(vocab_size=cfg.vocab_size, seed=0)
    calib = corpus.sample(jax.random.key(1), calib_rows, calib_len)
    rsq = RSQConfig(bits=BITS, rotate=False, importance="attn_con",
                    pack_output=True,
                    **({"pack_writeback": "sharded"} if ctx else {}))
    pipe = (RSQPipeline(model, rsq, ctx=ctx) if ctx
            else RSQPipeline(model, rsq))
    qparams, _ = pipe.run(params, calib, batch_size=batch_size)
    d = tempfile.mkdtemp(prefix="serve_bench_")
    save_packed_artifact(d, pipe.artifact, params=qparams,
                         extra={"arch": cfg.name})
    return model, d, corpus


def _build():
    from repro.configs import get_config

    cfg = dataclasses.replace(
        get_config(ARCH).reduced(), dtype="float32",
        n_layers=N_LAYERS, d_model=D_MODEL, vocab_size=512)
    model, d, corpus = _quantize_to_artifact(cfg)
    prompts = corpus.sample(jax.random.key(2), BATCH, PROMPT)
    return model, d, prompts


class _ServeTimer:
    """One serving path's persistent jits + per-rep timings.

    The compile pass runs once up front so every timed rep is the
    dispatch + execute path the packed representation actually changes.
    Decode is the fused scan program (the serving default); the legacy
    python loop is timed alongside for the dispatch-overhead trajectory."""

    def __init__(self, model, params, prompts):
        from repro.launch.serve import _prefill_fn, _scan_decode_fn

        self.params, self.prompts = params, prompts
        b, t = prompts.shape
        self.t = t
        self.key = jax.random.key(0)
        self.prefill = _prefill_fn(model, t + GEN)
        self.decode = _scan_decode_fn(model, GEN, False)
        self.step = jax.jit(model.decode_step, donate_argnums=(1,))
        logits, cache = self._prefill()  # compile all three
        tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
        jax.block_until_ready(
            self.decode(self.params, cache, tok, jnp.int32(t), self.key,
                        jnp.float32(0.0)))
        logits, cache = self._prefill()
        jax.block_until_ready(
            self.step(self.params, cache, tok, jnp.int32(t))[0])
        self.prefill_s: list[float] = []
        self.decode_s: list[float] = []
        self.pyloop_s: list[float] = []

    def _prefill(self):
        return self.prefill(self.params, self.prompts, None, None)

    def rep(self):
        t0 = time.perf_counter()
        logits, cache = self._prefill()
        jax.block_until_ready(logits)
        self.prefill_s.append(time.perf_counter() - t0)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
        t0 = time.perf_counter()
        toks = self.decode(self.params, cache, tok, jnp.int32(self.t),
                           self.key, jnp.float32(0.0))
        jax.block_until_ready(toks)
        self.decode_s.append(time.perf_counter() - t0)
        # legacy loop: one jitted dispatch + host round-trip per token.
        # GEN - 1 steps, like the scan program: token 0 comes from the
        # prefill logits on both loops (launch.serve.generate), so the
        # two decode timings credit the same b*GEN tokens to the same
        # number of decode steps.
        logits, cache = self._prefill()
        tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
        t0 = time.perf_counter()
        pos = self.t
        for _ in range(GEN - 1):
            logits, cache = self.step(self.params, cache, tok,
                                      jnp.int32(pos))
            tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
            pos += 1
        jax.block_until_ready(logits)
        self.pyloop_s.append(time.perf_counter() - t0)

    def stats(self) -> dict:
        # min-of-reps, not median: this container's load spikes stretch
        # individual reps by 50%+ and even the median of 9 interleaved
        # reps swings between runs; the minimum approximates the
        # uncontended machine, which is the quantity the regression gate
        # and the packed/fp ratio are meant to compare (and it is always
        # <= the median-based baselines, so switching cannot fake a
        # regression)
        b = self.prompts.shape[0]
        p_s = min(self.prefill_s)
        d_s = min(self.decode_s)
        py_s = min(self.pyloop_s)
        return {
            "prefill_s": round(p_s, 4),
            "decode_s": round(d_s, 4),
            "prefill_tok_s": round(b * self.t / p_s, 1),
            "decode_tok_s": round(b * GEN / d_s, 1),
            "decode_tok_s_python": round(b * GEN / py_s, 1),
            "steady_total_s": round(p_s + d_s, 4),
        }


def _long_context_leg() -> dict:
    """Decode throughput vs cached length for fp / int8 / 2-bit-log KV.

    One tiny GQA model, one param tree (``kv_bits`` never touches the
    weights); per cached length the three cache codecs run interleaved
    reps of un-timed prefill + timed fused scan decode.  Alongside the
    timings the leg records the allocated cache footprint
    (``kv_cache_resident_bytes`` via ``eval_shape`` — nothing allocated)
    and ``kv_bytes_ratio_vs_bf16``: quantized cache bytes over the same
    cache held in bf16, ~ bits/16 plus the scale rows.  ``run.py`` gates
    ``decode_vs_fp_ratio`` at the longest length with SERVE_RATIO_TOL —
    quantized-KV decode losing to fp decode defeats the codec's purpose
    (less cache traffic per generated token), exactly the packed-weight
    decode gate's logic."""
    from repro.configs import get_config
    from repro.data.synthetic import SyntheticCorpus
    from repro.launch.serve import (_prefill_fn, _scan_decode_fn,
                                    kv_cache_resident_bytes)
    from repro.models import build_model

    base = dataclasses.replace(
        get_config(ARCH).reduced(), dtype="float32",
        n_layers=N_LAYERS, d_model=D_MODEL, vocab_size=512)
    params = jax.jit(build_model(base).init)(jax.random.key(0))
    corpus = SyntheticCorpus(vocab_size=base.vocab_size, seed=0)
    key = jax.random.key(0)

    variants = {"fp": 0, "kv8": 8, "kv2": 2}
    out = {name: {} for name in variants}
    for s in LC_LENGTHS:
        t = s - LC_GEN
        prompts = corpus.sample(jax.random.key(3), LC_BATCH, t)
        legs = {}
        for name, bits in variants.items():
            model = build_model(dataclasses.replace(base, kv_bits=bits))
            legs[name] = (model, _prefill_fn(model, s),
                          _scan_decode_fn(model, LC_GEN, False))
        times = {name: [] for name in variants}
        for rep in range(LC_REPS + 1):  # rep 0 compiles, untimed
            for name, (model, pre, dec) in legs.items():
                logits, cache = pre(params, prompts, None, None)
                tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
                jax.block_until_ready(tok)
                t0 = time.perf_counter()
                toks = dec(params, cache, tok, jnp.int32(t), key,
                           jnp.float32(0.0))
                jax.block_until_ready(toks)
                if rep:
                    times[name].append(time.perf_counter() - t0)
        for name, (model, _, _) in legs.items():
            d_s = min(times[name])
            cache_b = kv_cache_resident_bytes(
                jax.eval_shape(lambda m=model: m.init_cache(LC_BATCH, s)))
            out[name][str(s)] = {
                "decode_s": round(d_s, 4),
                "decode_tok_s": round(LC_BATCH * LC_GEN / d_s, 1),
                "kv_cache_resident_bytes": int(cache_b),
            }
    s_max = str(max(LC_LENGTHS))
    fp_leaf = out["fp"][s_max]
    # this bench runs fp32; a bf16 cache holds the same rows at 2 bytes
    bf16_bytes = fp_leaf["kv_cache_resident_bytes"] // 2
    for name in ("kv8", "kv2"):
        leaf = out[name][s_max]
        leaf["decode_vs_fp_ratio"] = round(
            leaf["decode_s"] / fp_leaf["decode_s"], 4)
        leaf["kv_bytes_ratio_vs_bf16"] = round(
            leaf["kv_cache_resident_bytes"] / bf16_bytes, 4)
    return {
        "arch": f"{ARCH}-smoke(d={D_MODEL},L={N_LAYERS})",
        "batch": LC_BATCH, "gen": LC_GEN, "lengths": list(LC_LENGTHS),
        "decode_loop": "scan",
        **out,
    }


def _engine_leg() -> dict:
    """Continuous batching (serving.Engine, Poisson arrivals) vs the
    fixed-batch scan loop at equal load.

    The same ``ENG_N_REQ`` requests — one shared prompt length, budgets
    cycling 3 short : 1 long — run (a) through the engine on paged kv8
    pools under a Poisson arrival trace and (b) through ``generate`` in
    arrival-ordered ``ENG_SLOTS``-sized waves, each wave padded to its
    longest budget (the fixed shape cannot retire early or backfill a
    freed row).  Only
    the *requested* tokens count toward throughput on both sides, so the
    fixed batch pays for its wasted trailing steps.  ``run.py`` gates
    ``sustained_vs_fixed_ratio`` (fixed tok/s over engine sustained
    tok/s, > 1 = engine slower) at SERVE_RATIO_TOL: continuous batching
    losing sustained throughput to the fixed batch at equal load is a
    regression of the engine's whole point.  ``steady_total_s`` (best
    engine wall over reps) rides the generic wall-time gate.

    The engine runs twice — whole-prompt admission and chunked admission
    (``prefill_chunk=ENG_CHUNK``, two chunks per 96-token prompt) — and
    both record ``ttft_p50_s``/``ttft_p99_s``, the p90 queue wait and the
    prompt ingest seconds per 1000 prompt tokens.  The ``chunked`` sub-dict carries
    ``chunked_vs_whole_ratio`` (whole sustained tok/s over chunked
    sustained tok/s, > 1 = chunked slower) and ``p99_vs_whole_ratio``
    (chunked p99 latency over whole-prompt p99); ``run.py`` gates both at
    SERVE_RATIO_TOL — chunked admission costing sustained throughput or
    tail latency against whole-prompt admission defeats its purpose."""
    from repro.configs import get_config
    from repro.data.synthetic import SyntheticCorpus
    from repro.launch.serve import generate
    from repro.models import build_model
    from repro.serving import Engine, ServeRequest, poisson_trace, run_trace

    cfg = dataclasses.replace(
        get_config(ARCH).reduced(), dtype="float32",
        n_layers=N_LAYERS, d_model=D_MODEL, vocab_size=512, kv_bits=8)
    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.key(0))
    corpus = SyntheticCorpus(vocab_size=cfg.vocab_size, seed=0)
    prompts = corpus.sample(jax.random.key(4), ENG_N_REQ, ENG_PROMPT)
    budgets = [ENG_BUDGETS[i % len(ENG_BUDGETS)] for i in range(ENG_N_REQ)]
    reqs = [ServeRequest(tokens=prompts[i].tolist(),
                         max_new_tokens=budgets[i])
            for i in range(ENG_N_REQ)]
    n_req_tok = sum(budgets)

    max_pages = -(-(ENG_PROMPT + max(ENG_BUDGETS)) // model.codec.page_tokens)

    def engine_run(prefill_chunk=None):
        engine = Engine(model, params, max_slots=ENG_SLOTS,
                        n_pages=ENG_PAGES, max_pages_per_request=max_pages,
                        burst_steps=ENG_BURST, prefill_chunk=prefill_chunk)
        stats = run_trace(engine, poisson_trace(reqs, rate=ENG_RATE,
                                                seed=0))
        assert stats["n_tokens"] == n_req_tok, stats["n_tokens"]
        assert engine.pools.free_pages() == ENG_PAGES, "pages leaked"
        return stats

    # rep 0 of each admission mode compiles its programs, untimed; timed
    # reps interleave the two modes so machine drift hits both equally
    # (the _ServeTimer trick), and each reported metric takes the best
    # rep per side — the uncontended-machine quantity the chunked/whole
    # ratios need on this shared container (a single load spike on one
    # side must not fake or mask a structural regression).
    engine_run()
    engine_run(ENG_CHUNK)
    whole_reps, chunked_reps = [], []
    for _ in range(ENG_REPS):
        whole_reps.append(engine_run())
        chunked_reps.append(engine_run(ENG_CHUNK))

    def best_of(reps):
        best = dict(min(reps, key=lambda s: s["wall_s"]))
        for f in ("p50_latency_s", "p99_latency_s", "ttft_p50_s",
                  "ttft_p99_s", "queue_wait_p90_s", "ingest_s_per_ktok"):
            best[f] = min(s[f] for s in reps)
        return best

    best = best_of(whole_reps)
    chunked = best_of(chunked_reps)

    n_gen = max(budgets)
    waves = [prompts[i:i + ENG_SLOTS]
             for i in range(0, ENG_N_REQ, ENG_SLOTS)]
    for w in waves:  # compile pass
        jax.block_until_ready(generate(model, params, w, n_gen))
    fixed_s = None
    for _ in range(ENG_REPS):
        t0 = time.perf_counter()
        for w in waves:
            jax.block_until_ready(generate(model, params, w, n_gen))
        dt = time.perf_counter() - t0
        fixed_s = dt if fixed_s is None else min(fixed_s, dt)
    fixed_tok_s = n_req_tok / fixed_s

    return {
        "arch": f"{ARCH}-smoke(d={D_MODEL},L={N_LAYERS})",
        "kv_bits": 8,
        "n_requests": ENG_N_REQ, "prompt_len": ENG_PROMPT,
        "budgets": list(ENG_BUDGETS), "requested_tokens": n_req_tok,
        "max_slots": ENG_SLOTS, "n_pages": ENG_PAGES,
        "burst_steps": ENG_BURST, "arrival_rate": ENG_RATE,
        "sustained_tok_s": round(best["sustained_tok_s"], 1),
        "p50_latency_s": round(best["p50_latency_s"], 4),
        "p99_latency_s": round(best["p99_latency_s"], 4),
        "ttft_p50_s": round(best["ttft_p50_s"], 4),
        "ttft_p99_s": round(best["ttft_p99_s"], 4),
        "queue_wait_p90_s": round(best["queue_wait_p90_s"], 4),
        "ingest_s_per_ktok": round(best["ingest_s_per_ktok"], 4),
        "rounds": best["rounds"],
        "steady_total_s": round(best["wall_s"], 4),
        "fixed_batch_tok_s": round(fixed_tok_s, 1),
        "fixed_batch_s": round(fixed_s, 4),
        # > 1 = the engine sustains fewer useful tok/s than fixed waves
        "sustained_vs_fixed_ratio": round(
            fixed_tok_s / best["sustained_tok_s"], 4),
        "chunked": {
            "prefill_chunk": ENG_CHUNK,
            "sustained_tok_s": round(chunked["sustained_tok_s"], 1),
            "p50_latency_s": round(chunked["p50_latency_s"], 4),
            "p99_latency_s": round(chunked["p99_latency_s"], 4),
            "ttft_p50_s": round(chunked["ttft_p50_s"], 4),
            "ttft_p99_s": round(chunked["ttft_p99_s"], 4),
            "queue_wait_p90_s": round(chunked["queue_wait_p90_s"], 4),
            "ingest_s_per_ktok": round(chunked["ingest_s_per_ktok"], 4),
            "rounds": chunked["rounds"],
            "steady_total_s": round(chunked["wall_s"], 4),
            # > 1 = chunked admission sustains fewer tok/s than whole-prompt
            "chunked_vs_whole_ratio": round(
                best["sustained_tok_s"] / chunked["sustained_tok_s"], 4),
            # > 1 = chunked admission worsens tail latency vs whole-prompt
            "p99_vs_whole_ratio": round(
                chunked["p99_latency_s"] / max(best["p99_latency_s"], 1e-9),
                4),
        },
    }


def _overload_leg() -> dict:
    """The engine under 2x page oversubscription (serving PR 10).

    Hot demand (``ENG_SLOTS`` x pages-per-request) is twice the pool, the
    queue is bounded (``OVL_QUEUE_DEPTH``) and one request carries an
    already-expired deadline, so a single Poisson trace exercises
    preemption-and-requeue, backpressure shedding and deadline expiry at
    once.  Scheduling is round-based and greedy, so the overload counters
    are deterministic across reps/machines; the in-bench assertions (and
    run.py's ``all_terminal`` gate) pin the robustness contract — every
    submission terminal, pages quiescent, preemption actually exercised —
    while the p99/wall numbers ride the usual advisory gates."""
    from repro.configs import get_config
    from repro.data.synthetic import SyntheticCorpus
    from repro.models import build_model
    from repro.serving import (Engine, SamplingParams, ServeRequest,
                               poisson_trace, run_trace)

    cfg = dataclasses.replace(
        get_config(ARCH).reduced(), dtype="float32",
        n_layers=N_LAYERS, d_model=D_MODEL, vocab_size=512, kv_bits=8)
    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.key(0))
    corpus = SyntheticCorpus(vocab_size=cfg.vocab_size, seed=0)
    prompts = corpus.sample(jax.random.key(5), OVL_N_REQ, ENG_PROMPT)
    page = model.codec.page_tokens
    ppr = -(-(ENG_PROMPT + OVL_BUDGET) // page)
    n_pages = ENG_SLOTS * ppr // 2  # hot demand = 2x the pool
    reqs = [ServeRequest(tokens=prompts[i].tolist(),
                         max_new_tokens=OVL_BUDGET,
                         sampling=SamplingParams(
                             deadline_s=1e-6 if i == 4 else 0.0))
            for i in range(OVL_N_REQ)]

    def one_run():
        engine = Engine(model, params, max_slots=ENG_SLOTS,
                        n_pages=n_pages, max_pages_per_request=ppr,
                        burst_steps=ENG_BURST, queue_depth=OVL_QUEUE_DEPTH)
        stats = run_trace(engine, poisson_trace(reqs, rate=OVL_RATE,
                                                seed=0))
        engine.pools.assert_quiescent()
        return stats

    one_run()  # compile pass, untimed
    runs = [one_run() for _ in range(OVL_REPS)]
    best = min(runs, key=lambda s: s["wall_s"])
    all_terminal = all(s["n_requests"] == OVL_N_REQ for s in runs)
    assert all_terminal, "a submission never reached a terminal status"
    assert best["n_preemptions"] >= 1, "2x oversubscription must preempt"
    assert best["n_deadline"] >= 1, "the expired deadline must retire"
    return {
        "n_requests": OVL_N_REQ, "prompt_len": ENG_PROMPT,
        "budget": OVL_BUDGET, "max_slots": ENG_SLOTS,
        "n_pages": n_pages, "pages_per_request": ppr,
        "oversubscription": 2.0, "queue_depth": OVL_QUEUE_DEPTH,
        "arrival_rate": OVL_RATE,
        "all_terminal": all_terminal,
        "n_preemptions": best["n_preemptions"],
        "n_preempted_requests": best["n_preempted_requests"],
        "shed_rate": round(best["n_shed"] / OVL_N_REQ, 4),
        "n_deadline": best["n_deadline"],
        "n_failed": best["n_failed"],
        "statuses": best["statuses"],
        "p99_latency_s": round(best["p99_latency_s"], 4),
        "per_status": best["per_status"],
        "steady_total_s": round(best["wall_s"], 4),
    }


def _mesh_leg() -> dict | None:
    """shard_map'd kernel serving on the fake multi-device mesh (CI's
    fake-8-device bench-guard entry): keep-packed generate with the
    kernel forced, asserting zero ref-GEMM fallbacks.  Ungated timing."""
    if jax.device_count() < 8:
        return None
    from repro.checkpoint.packed import load_packed_forward_params
    from repro.configs import get_config
    from repro.data.synthetic import SyntheticCorpus
    from repro.kernels.quant_matmul import ops
    from repro.launch.serve import generate
    from repro.models import build_model
    from repro.launch.mesh import make_mesh
    from repro.runtime.sharding import ParallelCtx

    mesh = make_mesh((2, 4), ("data", "model"))
    ctx = ParallelCtx(mesh=mesh, dp=("data",), tp="model")
    cfg = dataclasses.replace(
        get_config(ARCH).reduced(), dtype="float32", n_layers=MESH_LAYERS,
        d_model=MESH_D_MODEL, n_heads=8, n_kv_heads=8, d_head=0,
        d_ff=MESH_D_MODEL, vocab_size=256)
    _, artifact, corpus = _quantize_to_artifact(
        cfg, ctx=ctx, calib_rows=8, calib_len=32, batch_size=4)
    try:
        model = build_model(cfg, ctx)
        params, _ = load_packed_forward_params(artifact, ctx=ctx)
    finally:
        shutil.rmtree(artifact, ignore_errors=True)
    prompts = corpus.sample(jax.random.key(2), MESH_BATCH, MESH_PROMPT)

    ref_calls = []
    orig_ref = ops.quant_matmul_ref
    ops.quant_matmul_ref = lambda *a, **k: (ref_calls.append(1),
                                            orig_ref(*a, **k))[1]
    env_before = os.environ.get("REPRO_QMM_KERNEL")
    os.environ["REPRO_QMM_KERNEL"] = "1"  # read at trace time
    try:
        out = generate(model, params, prompts, MESH_GEN)  # compile
        jax.block_until_ready(out)
        times = []
        for _ in range(MESH_REPS):
            t0 = time.perf_counter()
            out = generate(model, params, prompts, MESH_GEN)
            jax.block_until_ready(out)
            times.append(time.perf_counter() - t0)
    finally:
        ops.quant_matmul_ref = orig_ref
        if env_before is None:
            del os.environ["REPRO_QMM_KERNEL"]
        else:
            os.environ["REPRO_QMM_KERNEL"] = env_before
    assert not ref_calls, (
        f"{len(ref_calls)} ref-GEMM fallbacks on the mesh leg: the "
        "shard_map'd kernel route must carry every projection")
    return {
        "mesh": "2x4(data,model)",
        "arch": f"{ARCH}-mesh(d={MESH_D_MODEL},L={MESH_LAYERS})",
        "mesh_total_s": round(min(times), 4),
        "ref_gemm_fallbacks": 0,
        "shard_map_kernel": True,
    }


def run(table: Table | None = None):
    from repro.checkpoint.packed import (load_packed_forward_params,
                                         load_packed_params)
    from repro.kernels.quant_matmul.ops import PackedWeight
    from repro.launch.serve import resident_weight_bytes

    table = table or Table("serve")
    model, artifact, prompts = _build()
    try:
        deq_params, meta = load_packed_params(artifact)
        pk_params, _ = load_packed_forward_params(artifact)
    finally:
        shutil.rmtree(artifact, ignore_errors=True)

    packed_b, _ = resident_weight_bytes(pk_params)
    itemsize = jnp.dtype(model.dtype).itemsize
    fp_b = sum(
        math.prod(w.w_packed.shape[:-2]) * w.d_in * w.w_packed.shape[-1]
        * itemsize
        for w in jax.tree.leaves(
            pk_params, is_leaf=lambda x: isinstance(x, PackedWeight))
        if isinstance(w, PackedWeight))

    timers = {"fp": _ServeTimer(model, deq_params, prompts),
              "packed": _ServeTimer(model, pk_params, prompts)}
    for _ in range(REPS):  # interleaved: drift hits both paths equally
        for tm in timers.values():
            tm.rep()
    fp, packed = timers["fp"].stats(), timers["packed"].stats()
    # gated packed/fp decode ratio: best packed rep over best fp rep —
    # the uncontended-machine quantity on both sides.  (Not min over
    # paired reps: a single fp-side load spike would let a real packed
    # regression hide behind that pair.)  A structural regression
    # (ref-GEMM fallback, lost kernel fusion, a re-serialized loop)
    # slows every packed rep including the best one, so it still trips;
    # run.py gates this at its own slightly wider SERVE_RATIO_TOL since
    # even best-of-reps ratios wobble ~20% on this shared container.
    packed["decode_vs_fp_ratio"] = round(
        min(timers["packed"].decode_s) / min(timers["fp"].decode_s), 4)

    ratio = packed_b / fp_b
    table.add("serve_fp_dequant", fp["steady_total_s"] * 1e6,
              f"prefill_tok_s={fp['prefill_tok_s']} "
              f"decode_tok_s={fp['decode_tok_s']}")
    table.add("serve_keep_packed", packed["steady_total_s"] * 1e6,
              f"prefill_tok_s={packed['prefill_tok_s']} "
              f"decode_tok_s={packed['decode_tok_s']}")
    table.add("decode_scan_vs_python", 0.0,
              f"packed scan={packed['decode_tok_s']} "
              f"python={packed['decode_tok_s_python']} tok/s")
    table.add("resident_weight_bytes", 0.0,
              f"fp={fp_b} packed={packed_b} ratio={ratio:.3f} "
              f"(~bits/32 at fp32: {BITS / 32:.3f})")

    # decode is weight-HBM-bound on accelerators: per-token weight traffic
    # caps throughput, so packed codes bound the speedup at 16/bits vs a
    # bf16-resident model (8/bits at this bench's fp32 params)
    payload = {
        "arch": f"{ARCH}-smoke(d={D_MODEL},L={N_LAYERS})",
        "bits": BITS,
        "batch": BATCH, "prompt_len": PROMPT, "gen": GEN,
        "decode_loop": "scan",
        "fp": fp,
        "packed": packed,
        "resident_weight_bytes": {
            "fp": int(fp_b), "packed": int(packed_b),
            "ratio": round(ratio, 4),
        },
        "tpu_decode_roofline": {
            "weight_traffic_ratio": round(ratio, 4),
            "bound_speedup_vs_bf16": round(16 / BITS, 2),
        },
        "n_packed_entries": len(meta["entries"]),
        "backend": jax.default_backend(),
    }
    lc = _long_context_leg()
    payload["long_context"] = lc
    s_max = str(max(LC_LENGTHS))
    table.add("long_ctx_decode_fp", lc["fp"][s_max]["decode_s"] * 1e6,
              f"S={s_max} decode_tok_s={lc['fp'][s_max]['decode_tok_s']}")
    for name in ("kv8", "kv2"):
        leaf = lc[name][s_max]
        table.add(f"long_ctx_decode_{name}", leaf["decode_s"] * 1e6,
                  f"S={s_max} decode_tok_s={leaf['decode_tok_s']} "
                  f"vs_fp={leaf['decode_vs_fp_ratio']} "
                  f"kv_bytes_vs_bf16={leaf['kv_bytes_ratio_vs_bf16']}")
    eng = _engine_leg()
    payload["engine"] = eng
    table.add("engine_sustained", eng["steady_total_s"] * 1e6,
              f"tok_s={eng['sustained_tok_s']} "
              f"fixed={eng['fixed_batch_tok_s']} "
              f"ratio={eng['sustained_vs_fixed_ratio']} "
              f"p50={eng['p50_latency_s']}s p99={eng['p99_latency_s']}s")
    ch = eng["chunked"]
    table.add("engine_chunked_prefill", ch["steady_total_s"] * 1e6,
              f"chunk={ch['prefill_chunk']} tok_s={ch['sustained_tok_s']} "
              f"vs_whole={ch['chunked_vs_whole_ratio']} "
              f"ttft_p50={ch['ttft_p50_s']}s ttft_p99={ch['ttft_p99_s']}s "
              f"queue_wait_p90={ch['queue_wait_p90_s']}s "
              f"ingest={ch['ingest_s_per_ktok']}s/ktok")
    ovl = _overload_leg()
    payload["overload"] = ovl
    table.add("engine_overload", ovl["steady_total_s"] * 1e6,
              f"2x oversub preempts={ovl['n_preemptions']} "
              f"shed_rate={ovl['shed_rate']} "
              f"deadline={ovl['n_deadline']} p99={ovl['p99_latency_s']}s "
              f"all_terminal={ovl['all_terminal']}")
    mesh = _mesh_leg()
    if mesh is not None:
        payload["packed_mesh"] = mesh
        table.add("serve_mesh_shard_map", mesh["mesh_total_s"] * 1e6,
                  f"ref_fallbacks={mesh['ref_gemm_fallbacks']}")
    OUT_PATH.write_text(json.dumps(payload, indent=2) + "\n")
    return table


if __name__ == "__main__":
    run()
