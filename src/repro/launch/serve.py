"""Batched serving driver: prefill + fused decode loop with a KV cache,
optional weight-only quantized execution (RSQ output + quant_matmul kernel).

  PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-4b-smoke \
      --batch 4 --prompt-len 32 --gen 16

``--packed DIR`` serves from a packed RSQ artifact (written by
launch.quantize --pack-out).  The default is **keep-packed** serving
(``--keep-packed``): the param tree holds the uint32 codes as
``PackedWeight`` pytree nodes and every dense projection runs through the
fused dequant-GEMM ``quant_matmul`` — no fp array of any quantized
weight's full shape is ever created, on host or in HBM (MLA's absorbed
decode included: its per-head ``wkv_b`` contractions run on the packed
codes through the latent-layout ``quant_matmul_t``), so resident weight
memory is ~bits/16 of the bf16 model.  ``--no-keep-packed`` restores the
legacy load-time device-side dequantization
(``checkpoint.packed.load_packed_params``) for A/B comparisons; both
paths jit prefill and decode through the same model code
(``models.layers.linear`` dispatches per weight type).

Serving performance
-------------------

* ``--loop scan`` (default): the whole generation is ONE device program —
  a jitted ``lax.scan`` over decode steps with the KV cache donated into
  it and sampling (greedy argmax or ``--temperature`` categorical, keys
  derived per step via ``jax.random.fold_in``) on device.  The per-token
  host round-trip + dispatch of the old loop is gone, which is what let
  packed decode overtake fp (decode is memory-bound; the packed kernel's
  16/bits weight-traffic win only shows once dispatch stops dominating).
  ``--loop python`` keeps the legacy one-jitted-dispatch-per-token loop
  as a debug mode; greedy tokens are bit-identical between the two
  (pinned by tests/test_serve_scan.py).
* Kernel policy: ``quant_matmul`` auto-selects the fused Pallas kernel on
  TPU and the fused-XLA ref elsewhere; ``REPRO_QMM_KERNEL=1`` forces the
  kernel (interpret mode off-TPU — a correctness/CI tool, not a fast
  path), ``=0`` forces the ref.
* Mesh behaviour: with a live mesh the artifact's codes load d_out-sharded
  on the model axis and ``quant_matmul`` runs the kernel per shard under
  ``shard_map`` — no code all-gather, no ref-GEMM fallback; ragged local
  tiles and expert stacks under vmap fall back to the GSPMD ref.
* Quantized KV cache (``--kv-bits {0,8,2}``): long-context decode is bound
  by KV-cache HBM traffic — the whole cache is re-read per generated
  token per layer.  ``--kv-bits 8`` stores int8 codes + per-(token, head)
  scales, ``--kv-bits 2`` packed LogQuant-style log codes + one bf16
  scale per (``kv_chunk`` tokens, head) — ~1/2 and ~1/8 the bf16 cache
  bytes.  Prefill writes the cache already quantized and decode appends
  codes, so codes+scales is the cache's *only* representation end to end:
  attention consumes them directly through ``kernels.flash_decode``
  (in-register tile dequant, streaming-softmax (m, l, acc), no fp copy of
  the cache at any size — the zero-dequant guard of
  tests/test_kv_cache.py pins it, MLA's latent cache included).  Under a
  mesh the cache's sequence axis is split across the model axis and each
  device flash-decodes its shard; the shards merge by one tiny
  max/sum-shifted partial-softmax collective — zero cache collectives.

Engine serving (``--mode engine``)
----------------------------------

``--mode engine`` swaps the fixed-shape batch for the request-oriented
serving engine (``repro.serving``): requests are ``ServeRequest`` objects
with their own budget and ``SamplingParams``, arrive on a Poisson trace
(``--arrival-rate`` per scheduling round), prefill into pages popped off
a shared block-paged quantized KV pool (``--n-pages``; page = ``kv_chunk``
tokens across every layer), decode continuously in bursts of
``--burst-steps`` alongside whatever else is in flight, and retire by
releasing their pages for reuse.  ``--prefill-chunk N`` streams prompt
ingestion through the running batch in page-aligned chunks (one chunk
per scheduling round per ingesting request) instead of stalling decode
on whole-prompt prefills — see serving/README.md "Chunked prefill".
Per-request token streams are bit-identical to a single-request
``generate()`` call either way (pinned by tests/test_serving.py).  Requires ``--kv-bits 8`` or ``2`` — the pools
store codes+scales, never fp.  See src/repro/serving/README.md for the
API and the page-size math.

Overload controls: ``--deadline-s`` retires expired requests with status
``deadline_exceeded``, ``--queue-depth`` bounds the submission queue
(rejected submissions are recorded as ``shed``), and ``--fail-at-round
ROUND:STAGE[:COUNT]`` injects failures at the engine's scheduling stage
points (admit/ingest/burst/retire) to exercise the retry/isolation path
— under page pressure the engine preempts-and-requeues rather than
stalling, and every preempted request's tokens stay bit-identical to its
solo run (serving/README.md "Overload policy").

``--kernel-check`` is deprecated: the keep-packed forward now routes
*every* projection through ``quant_matmul`` and the full-forward parity
is pinned by tests/test_serve_packed.py.  The flag survives as a thin
alias that still runs its original cheap startup integrity check (one
artifact entry through ``quant_matmul`` vs the dequantized matmul)
before keep-packed serving; combining it with ``--no-keep-packed`` is an
error.
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import functools
import time
import warnings

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.data.synthetic import SyntheticCorpus
from repro.models import build_model
from repro.serving import (Engine, RequestOutput, SamplingParams,  # noqa: F401
                           ServeRequest, poisson_trace, run_trace)


def _sample_token(logits, temperature: float, key, step) -> jax.Array:
    """(B, V) logits -> (B, 1) int32 token; greedy at temperature 0,
    categorical at ``logits / temperature`` otherwise with the step's key
    derived by ``fold_in`` (deterministic in (key, step) — the python and
    scan loops draw identical streams)."""
    if temperature > 0.0:
        sub = jax.random.fold_in(key, step)
        tok = jax.random.categorical(sub, logits / temperature, axis=-1)
        return tok.astype(jnp.int32)[:, None]
    return jnp.argmax(logits, -1).astype(jnp.int32)[:, None]


@functools.lru_cache(maxsize=64)
def _prefill_fn(model, cache_len: int):
    return jax.jit(lambda p, x, media, frames: model.prefill(
        p, x, media=media, frames=frames, cache_len=cache_len))


@functools.lru_cache(maxsize=64)
def _scan_decode_fn(model, n_gen: int, sampled: bool):
    """One jitted program for the whole generation: ``lax.scan`` over the
    decode steps, KV cache donated in (the scan's double-buffered carry is
    the only cache storage), sampling on device — a single dispatch and a
    single host sync for ``n_gen`` tokens.

    Only the *mode* (greedy vs sampled) is static; the temperature rides
    in as a traced scalar so sweeping it costs zero recompiles — at most
    two programs exist per (model, n_gen).

    Token 0 comes from the prefill logits, so only n_gen - 1 decode
    steps run: each scan iteration emits the token it just *produced*
    and the prefill token is prepended — no trailing decode_step whose
    outputs nothing consumes."""

    def run(params, cache, tok0, pos0, key, temperature):
        def body(carry, step):
            cache, tok, pos = carry
            logits, cache = model.decode_step(params, cache, tok, pos)
            if sampled:
                sub = jax.random.fold_in(key, step + 1)
                nxt = jax.random.categorical(
                    sub, logits / temperature, axis=-1
                ).astype(jnp.int32)[:, None]
            else:
                nxt = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
            return (cache, nxt, pos + jnp.int32(1)), nxt[:, 0]

        (_, _, _), toks = jax.lax.scan(
            body, (cache, tok0, pos0), jnp.arange(n_gen - 1))
        return jnp.concatenate([tok0, toks.T], axis=1)  # (B, n_gen)

    return jax.jit(run, donate_argnums=(1,))


def generate(model, params, prompts, n_gen: int, *, media=None, frames=None,
             temperature: float = 0.0, key=None, loop: str = "scan"):
    """prompts: (B, T) -> (B, n_gen) generated tokens.

    .. deprecated:: the request-oriented serving API is the primary
       surface now — build ``ServeRequest`` objects and drive them
       through :func:`generate_batch` (fixed batch, this loop) or
       ``serving.Engine`` (continuous batching over paged KV pools).
       ``generate`` stays as the thin fixed-shape core both share: a
       homogeneous batch, one prompt length, one temperature, one key.

    Greedy when ``temperature == 0``; otherwise categorical sampling of
    *every* token — including the first one, drawn from the prefill
    logits — with per-step keys ``fold_in(key, step)`` (``key`` is then
    required).  ``loop="scan"`` (default) runs the fused on-device
    generation loop; ``loop="python"`` is the legacy per-token dispatch
    loop, kept as a debug mode — greedy tokens are bit-identical between
    the two."""
    if loop not in ("scan", "python"):
        raise ValueError(f"loop must be 'scan' or 'python', got {loop!r}")
    if temperature > 0.0 and key is None:
        raise ValueError("temperature > 0 requires a PRNG `key`; pass "
                         "key=jax.random.key(seed) (silently degrading to "
                         "greedy was a bug)")
    b, t = prompts.shape
    logits, cache = _prefill_fn(model, t + n_gen)(params, prompts,
                                                  media, frames)
    if key is None:
        key = jax.random.key(0)  # unused at temperature 0 (DCE'd)
    tok = _sample_token(logits, temperature, key, 0)
    if loop == "scan":
        return _scan_decode_fn(model, n_gen, temperature > 0.0)(
            params, cache, tok, jnp.int32(t), key,
            jnp.float32(temperature))
    step = jax.jit(model.decode_step, donate_argnums=(1,))
    toks = [tok]
    pos = t
    for i in range(n_gen - 1):  # token 0 is the prefill sample
        logits, cache = step(params, cache, tok, jnp.int32(pos))
        tok = _sample_token(logits, temperature, key, i + 1)
        toks.append(tok)
        pos += 1
    return jnp.concatenate(toks, axis=1)


def generate_batch(model, params, requests, *, loop: str = "scan"):
    """Serve a list of ``ServeRequest`` through the fixed-batch scan loop.

    .. deprecated:: use ``serving.Engine`` — the engine serves the same
       ``ServeRequest`` objects with bit-identical per-request streams,
       without this loop's homogeneity restrictions, and with continuous
       batching / paged KV reuse on top.  This wrapper emits a
       ``DeprecationWarning`` and will be removed once the CLI's batch
       mode moves over.

    The request-oriented twin of :func:`generate`: one request type shared
    with ``serving.Engine``, same per-request token streams.  The
    fixed-shape loop can only batch *homogeneous* requests — equal prompt
    length and identical ``SamplingParams`` (one temperature / seed / eos
    for the whole batch; per-request budgets are fine, longer requests
    simply own the trailing tokens).  Heterogeneous workloads belong on
    the engine, which exists precisely because this shape restriction is
    what continuous batching removes.

    Returns one token list per request, truncated to its
    ``max_new_tokens`` (eos handling too is engine-only here: the fixed
    batch runs to the longest budget regardless)."""
    warnings.warn(
        "generate_batch is deprecated: serve ServeRequest objects through "
        "serving.Engine (continuous batching, same bit-identical streams, "
        "no homogeneous-batch restrictions)",
        DeprecationWarning, stacklevel=2)
    if not requests:
        return []
    t0 = len(requests[0].tokens)
    sp0 = requests[0].sampling
    if any(len(r.tokens) != t0 for r in requests):
        raise ValueError(
            "generate_batch needs one prompt length per batch (got "
            f"{sorted({len(r.tokens) for r in requests})}); mixed-length "
            "workloads belong on serving.Engine")
    if any(r.sampling != sp0 for r in requests):
        raise ValueError(
            "generate_batch needs identical SamplingParams across the "
            "batch; per-request sampling belongs on serving.Engine")
    if sp0.eos_token >= 0:
        raise ValueError(
            "generate_batch ignores eos_token (the fixed batch runs to "
            "its budget); requests that stop at eos belong on "
            "serving.Engine")
    prompts = jnp.asarray([r.tokens for r in requests], jnp.int32)
    n_gen = max(r.max_new_tokens for r in requests)
    key = (jax.random.key(sp0.seed) if sp0.temperature > 0 else None)
    out = generate(model, params, prompts, n_gen, loop=loop,
                   temperature=sp0.temperature, key=key)
    return [out[i, :r.max_new_tokens].tolist()
            for i, r in enumerate(requests)]


def kv_cache_resident_bytes(cache) -> int:
    """Total bytes resident in a KV-cache tree (codes + scales for a
    quantized cache, fp activations otherwise) — the per-token decode
    HBM traffic is proportional to this."""
    return sum(leaf.size * leaf.dtype.itemsize
               for leaf in jax.tree.leaves(cache))


def resident_weight_bytes(params) -> tuple[int, int]:
    """(packed_bytes, fp_bytes) resident in the tree: bytes held by
    ``PackedWeight`` leaves vs plain fp leaves."""
    from repro.kernels.quant_matmul.ops import PackedWeight

    packed = fp = 0
    for leaf in jax.tree.leaves(
            params, is_leaf=lambda x: isinstance(x, PackedWeight)):
        if isinstance(leaf, PackedWeight):
            packed += leaf.nbytes
        else:
            fp += leaf.size * leaf.dtype.itemsize
    return packed, fp


def _kernel_spot_check(packed_dir: str, meta: dict) -> None:
    """One artifact entry through ``quant_matmul`` vs its dequantized
    matmul — the deprecated ``--kernel-check`` startup integrity check
    (a corrupt/stale artifact fails loudly before serving; the full
    per-projection parity lives in tests/test_serve_packed.py)."""
    from repro.checkpoint.packed import dequantize_entry, load_packed_entry
    from repro.kernels.quant_matmul.ops import (packed_weight_from_artifact,
                                                quant_matmul)

    name = next((n for n, em in meta["entries"].items()
                 if len(em["fields"]["codes"]["shape"]) == 2), None)
    if name is None:  # all-expert-stack artifact: nothing 2-D to drive
        print("kernel-check: no dense 2-D weight in the artifact; skipped")
        return
    em = meta["entries"][name]
    entry = load_packed_entry(packed_dir, name)
    pw = packed_weight_from_artifact(entry, em, meta["spec"])
    x = jax.random.normal(jax.random.key(7), (8, pw.d_in), jnp.float32)
    y = quant_matmul(x, pw)
    ref = x @ dequantize_entry(entry, em, meta["spec"])
    err = float(jnp.max(jnp.abs(y - ref)) / (jnp.max(jnp.abs(ref)) + 1e-9))
    print(f"kernel-check [{name}]: quant_matmul vs dequant rel_err={err:.2e}")
    assert err < 1e-5, err


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-4b-smoke")
    ap.add_argument("--n-layers", type=int, default=None,
                    help="depth cut: keep the arch's first N decoder "
                    "layers (a packed artifact must have been quantized "
                    "with the same cut)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--dtype", default="float32")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--loop", choices=("scan", "python"), default="scan",
                    help="generation loop: 'scan' (default) fuses all "
                    "decode steps into one jitted lax.scan device program "
                    "with on-device sampling and a donated KV cache; "
                    "'python' is the legacy per-token dispatch loop "
                    "(debug; greedy tokens are bit-identical)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0 = greedy); every token "
                    "including the first is sampled, keyed by --seed")
    ap.add_argument("--mode", choices=("batch", "engine"), default="batch",
                    help="'batch' (default): one fixed-shape generate() "
                    "call; 'engine': continuous batching on block-paged "
                    "quantized KV pools — requests arrive on a Poisson "
                    "trace, prefill into freshly allocated pages, and "
                    "retire by releasing them (requires --kv-bits 8|2)")
    ap.add_argument("--max-slots", type=int, default=4,
                    help="engine mode: concurrent decode slots")
    ap.add_argument("--n-pages", type=int, default=64,
                    help="engine mode: allocatable KV pages shared by all "
                    "requests (page = kv_chunk tokens, every layer)")
    ap.add_argument("--burst-steps", type=int, default=8,
                    help="engine mode: decode steps per scheduling round "
                    "(one jitted scan between admissions/retirements)")
    ap.add_argument("--arrival-rate", type=float, default=0.5,
                    help="engine mode: Poisson arrivals per scheduling "
                    "round")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="engine mode: admit prompts in chunks of this "
                    "many tokens (rounded up to a page multiple), "
                    "interleaved with decode bursts — long prompts stop "
                    "stalling the running batch; 0 (default) admits "
                    "whole prompts in one prefill.  Tokens stay "
                    "bit-identical either way (exact chunked prefill)")
    ap.add_argument("--deadline-s", type=float, default=0.0,
                    help="engine mode: per-request deadline in seconds "
                    "from submit — expired requests (queued or decoding) "
                    "retire with status deadline_exceeded; 0 (default) "
                    "disables deadlines")
    ap.add_argument("--queue-depth", type=int, default=0,
                    help="engine mode: bounded submission queue — a "
                    "submit beyond this depth is rejected (EngineSaturated "
                    "with a retry-after hint; the trace driver records it "
                    "as status shed); 0 (default) queues unbounded")
    ap.add_argument("--fail-at-round", action="append", default=[],
                    metavar="ROUND:STAGE[:COUNT]",
                    help="engine mode: inject COUNT failures (default 1) "
                    "at a scheduling-round stage point, stage in "
                    "{admit, ingest, burst, retire} — a failed burst "
                    "retries with backoff, a poisoned request is isolated "
                    "with status failed; repeatable (same spec format as "
                    "launch.quantize --fail-at)")
    ap.add_argument("--kv-bits", type=int, default=None,
                    help="KV-cache precision: 0 = activation dtype "
                    "(default), 8 = int8 codes + per-token scales, 2 = "
                    "packed log codes + per-chunk scales; decode attends "
                    "on the codes directly (kernels.flash_decode)")
    ap.add_argument("--packed", default=None, metavar="DIR",
                    help="serve from a packed RSQ artifact (written by "
                    "launch.quantize --pack-out): weights travel host->"
                    "device as packed int codes")
    ap.add_argument("--keep-packed", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="with --packed: keep codes packed in HBM and run "
                    "every dense projection through quant_matmul (default); "
                    "--no-keep-packed dequantizes whole weights on device "
                    "at load time instead")
    ap.add_argument("--no-verify", action="store_true",
                    help="with --packed: skip the SHA-256 artifact "
                    "integrity check at load time (format v3 artifacts "
                    "record per-file checksums; a corrupt file otherwise "
                    "fails with ArtifactCorruptError before serving)")
    ap.add_argument("--kernel-check", action="store_true",
                    help="deprecated: keep-packed serving (the default) "
                    "already runs every projection through quant_matmul "
                    "(full-forward parity lives in tests/test_serve_packed); "
                    "retained as a one-entry startup integrity check")
    args = ap.parse_args(argv)
    if args.kernel_check:
        if not args.packed:
            ap.error("--kernel-check requires --packed")
        if not args.keep_packed:
            ap.error("--kernel-check checks the keep-packed path; it "
                     "cannot be combined with --no-keep-packed")
        print("--kernel-check is deprecated: keep-packed serving (the "
              "default) routes every projection through quant_matmul; "
              "running the one-entry startup check anyway")

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
    if args.packed:
        from repro.checkpoint.packed import (load_packed_forward_params,
                                             load_packed_params)

        loader = (load_packed_forward_params if args.keep_packed
                  else load_packed_params)
        params, meta = loader(args.packed, verify=not args.no_verify)
        arch = meta.get("extra", {}).get("arch")
        if arch not in (None, args.arch):
            ap.error(f"artifact was quantized for --arch {arch}, serving "
                     f"{args.arch}")
        depth = meta.get("extra", {}).get("n_layers")
        if depth not in (None, cfg.n_layers):
            ap.error(f"artifact was quantized with --n-layers {depth}, "
                     f"serving {cfg.n_layers} layers")
        n_packed = len(meta["entries"])
        packed_b, fp_b = resident_weight_bytes(params)
        mode = "keep-packed" if args.keep_packed else "dequantized"
        print(f"packed artifact: {n_packed} weights ({mode}, "
              f"bits={meta['spec']['bits']}); resident bytes: "
              f"{packed_b / 1e6:.1f}MB packed + {fp_b / 1e6:.1f}MB fp")
        if args.kernel_check:
            _kernel_spot_check(args.packed, meta)
    else:
        params = jax.jit(model.init)(jax.random.key(args.seed))
    corpus = SyntheticCorpus(vocab_size=cfg.vocab_size, seed=args.seed)
    prompts = corpus.sample(jax.random.key(1), args.batch, args.prompt_len)

    if args.mode == "engine":
        if not cfg.kv_bits:
            ap.error("--mode engine pages *quantized* KV codes — pass "
                     "--kv-bits 8 or --kv-bits 2")
        from repro.runtime.fault import FaultPlan

        reqs = [ServeRequest(
            tokens=prompts[i].tolist(),
            max_new_tokens=args.gen,
            sampling=SamplingParams(temperature=args.temperature,
                                    seed=args.seed + i,
                                    deadline_s=args.deadline_s),
        ) for i in range(args.batch)]
        need = -(-(args.prompt_len + args.gen) // model.codec.page_tokens)
        plan = (FaultPlan.parse(args.fail_at_round)
                if args.fail_at_round else None)
        engine = Engine(model, params, max_slots=args.max_slots,
                        n_pages=args.n_pages,
                        max_pages_per_request=max(need, 1),
                        burst_steps=args.burst_steps,
                        prefill_chunk=args.prefill_chunk or None,
                        queue_depth=args.queue_depth or None,
                        fault_plan=plan)
        stats = run_trace(engine, poisson_trace(
            reqs, rate=args.arrival_rate, seed=args.seed))
        admit = ("chunked (%d tokens/chunk)" % engine.prefill_chunk
                 if engine.prefill_chunk else "whole-prompt")
        print(f"engine [{admit} admission]: {stats['n_requests']} requests, "
              f"{stats['n_tokens']} tokens in {stats['wall_s']:.2f}s over "
              f"{stats['rounds']} rounds "
              f"({stats['sustained_tok_s']:.1f} sustained tok/s)")
        print(f"latency: p50={stats['p50_latency_s']:.3f}s "
              f"p99={stats['p99_latency_s']:.3f}s; "
              f"ttft: p50={stats['ttft_p50_s']:.3f}s "
              f"p99={stats['ttft_p99_s']:.3f}s; "
              f"admission stall {stats['admission_stall_s']:.2f}s; "
              f"free pages after drain: {engine.pools.free_pages()}"
              f"/{args.n_pages}")
        print(f"statuses: {stats['statuses']}; "
              f"preemptions: {stats['n_preemptions']} "
              f"({stats['n_preempted_requests']} requests); "
              f"shed: {stats['n_shed']}; deadline: {stats['n_deadline']}; "
              f"failed: {stats['n_failed']}")
        if engine.events.events:
            print(f"engine events: "
                  f"{dict(collections.Counter(engine.events.kinds()))}")
        # every submitted request must have reached a definite terminal
        # status — zero hangs is the overload contract, CI asserts on it
        assert stats["n_requests"] == args.batch, \
            (f"{args.batch - stats['n_requests']} of {args.batch} requests "
             "never reached a terminal status")
        engine.pools.assert_quiescent()
        print(f"all {args.batch} requests terminal; pages quiescent")
        first = next((o for o in stats["outputs"].values()
                      if o.finished_ok), None)
        if first is not None:
            print("sample:", first.tokens[:16])
        return stats

    key = (jax.random.key(args.seed) if args.temperature > 0.0 else None)
    t0 = time.time()
    out = generate(model, params, prompts, args.gen, loop=args.loop,
                   temperature=args.temperature, key=key)
    jax.block_until_ready(out)
    dt = time.time() - t0
    print(f"generated {out.shape} tokens in {dt:.2f}s "
          f"({args.batch * args.gen / dt:.1f} tok/s, loop={args.loop})")
    print("sample:", out[0][:16].tolist())
    if cfg.kv_bits:
        s = args.prompt_len + args.gen
        fp_model = build_model(dataclasses.replace(cfg, kv_bits=0))
        qb = kv_cache_resident_bytes(
            jax.eval_shape(lambda: model.init_cache(args.batch, s)))
        fb = kv_cache_resident_bytes(
            jax.eval_shape(lambda: fp_model.init_cache(args.batch, s)))
        print(f"kv cache resident: {qb / 1e6:.2f}MB (kv_bits="
              f"{cfg.kv_bits}) vs {fb / 1e6:.2f}MB fp — ratio {qb / fb:.3f}")
    return out


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
