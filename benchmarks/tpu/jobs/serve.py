"""Serving cells: ``serving.Engine`` on seeded packed weights.

Set-up: the packed 4-bit weights from the seed, on the device in one
call; the engine with the cell's slots and page pool; one request of
every prompt length the mix can send and one allocation of every page
count a request can need (so every program the window drives is
compiled or loaded from the cache here); then ``preroll_s`` seconds of
the same traffic (counted in ``setup_s``), so that the window starts at
its steady occupancy.

Window, ``--seconds`` long, by the traffic file's ``arrivals``:

* ``open``: requests are submitted when due (Poisson, wall time) whether
  or not earlier ones have finished; the engine steps while it is busy
  and the driver sleeps until the next due time while it is not.  Time
  to first token runs from when a request was due; a request that
  arrived in the window and was shed, failed or never finished counts as
  missing any limit (its latency is taken as running to the end of the
  drain).  After the window the run drains what arrived in it.
* ``backlog``: a closed backlog of at least ``backlog_per_slot`` x slots
  waiting requests; output tokens of requests that finished inside the
  window, over the window.

Correctness: once the window has closed and the engine is freed, a
sample of finished requests drawn from the seed, the one with the most
tokens among them, goes through the plain float32 reference as prompt
plus served tokens (see ``check``).
"""
from __future__ import annotations

import gc
import statistics
import time

import numpy as np

from benchmarks.tpu import gen, harness, modelcfg, spec, weights

log = harness.log


def quantile(xs, q: float) -> float:
    """The q-quantile by Python's ``statistics.quantiles`` (exclusive)."""
    xs = sorted(xs)
    if len(xs) == 1:
        return float(xs[0])
    cut = statistics.quantiles(xs, n=100, method="exclusive")
    return float(cut[int(round(q * 100)) - 1])


class Probe:
    """Host spans and counts around the engine's layers, from the
    benchmark's side: rounds and their host time, output tokens, decode
    bursts with their live slots and cache lengths, prefill chunks."""

    def __init__(self, engine, annotate):
        self.engine = engine
        self.annotate = annotate
        self.record = self.traced = False
        self.rounds_s, self.slots, self.tokens_out = [], [], 0
        # the engine's load (running and queued requests) when the window
        # opens and when it closes
        self.at_start = self.at_close = None
        # inside the traced window, for the kernels' operation counts:
        # per burst the live slots' cache lengths; per prefill call its
        # (length, tokens before it)
        self.bursts, self.prefill = [], []
        # the logits of each request's first token, as the engine samples
        # it from its prompt program: kept for the correctness check
        self.first_logits, pending = {}, []
        burst, start, ingest, token0 = (engine._burst, engine._start,
                                        engine._advance_ingest,
                                        engine._sample_token0)

        def token0_probe(logits, sp):
            if pending:
                self.first_logits[pending.pop(0)] = logits
            return token0(logits, sp)

        def burst_probe():
            act = engine.act.copy()
            if self.record:
                self.slots.append(int(act.sum()))
            if self.traced:
                self.bursts.append((engine.pos[act] + 1).tolist())
            with annotate("bench:burst"):
                return burst()

        def start_probe(slot, rid, req, ids):
            if self.traced:
                self.prefill.append((len(req.tokens), 0))
            pending[:] = [rid]
            with annotate("bench:prefill"):
                return start(slot, rid, req, ids)

        def ingest_probe(outs):
            pending[:] = []
            for s, ing in enumerate(engine._ingest):
                if ing is not None:
                    t = len(engine._slot_req[s].tokens)
                    n = min(engine.prefill_chunk, t - ing["start"])
                    if self.traced:
                        self.prefill.append((n, ing["start"]))
                    if ing["start"] + n >= t and ing["resume"] is None:
                        pending.append(engine._slot_rid[s])
            with annotate("bench:ingest"):
                return ingest(outs)

        engine._burst, engine._start = burst_probe, start_probe
        engine._advance_ingest = ingest_probe
        engine._sample_token0 = token0_probe

    def _emitted(self) -> dict:
        e = self.engine
        return {rid: len(e._slot_tokens[s] or [])
                for s, rid in enumerate(e._slot_rid) if rid is not None}

    def step(self):
        before = self._emitted() if self.record else None
        t0 = time.perf_counter()
        with self.annotate("bench:step"):
            outs = self.engine.step()
        if self.record:
            self.rounds_s.append(time.perf_counter() - t0)
            after = self._emitted()
            after.update({o.request_id: len(o.tokens) for o in outs})
            self.tokens_out += sum(n - before.get(r, 0)
                                   for r, n in after.items())
        return outs


def _annotate():
    import jax

    return jax.profiler.TraceAnnotation


def build(run):
    """(model, params, engine, geometry) of the cell from the seed."""
    from repro.kernels.quant_matmul.ops import PackedWeight
    from repro.models import build_model
    from repro.serving.engine import Engine

    c, p, mix = run.cell.config, run.cell.params, run.cell.traffic
    bits, gs = p["bits"], p["group_size"]
    cfg = modelcfg.model_config(c, name=run.cell.entry["config"],
                                kv_bits=p["kv_bits"])
    model = build_model(cfg)

    def wrap(w, s, z, d_in):
        return PackedWeight(w_packed=w, scale=s, zero=z, bits=bits,
                            group_size=gs, d_in=d_in)

    params = weights.packed_params(model, run.seed, bits, gs, wrap)
    page = model.codec.page_tokens
    max_len = max(mix["prompt_grid"]) + mix["output"]["max"]
    max_pages = -(-max_len // page)
    engine = Engine(model, params, max_slots=p["max_slots"],
                    n_pages=p["n_pages"], max_pages_per_request=max_pages,
                    prefill_chunk=p.get("prefill_chunk"))
    return model, params, engine, max_pages


def warm_up(engine, mix: dict, vocab: int, max_pages: int, reqs, annotate):
    """Compile (or load) every program the window can drive: one request
    of every prompt length of the mix, all at once (the prompt programs
    are per length and per slot, so this builds what one request at a time
    would, in the rounds of the longest prompt alone); one allocation and
    release of every page count; and the slice of a fresh allocation that
    a whole-prompt prefill writes into (the engine takes ``ids[:n]`` of
    the allocated page ids, an op compiled per pair of lengths), for every
    pair that the requests of this run can make."""
    import jax.numpy as jnp

    from repro.serving.engine import ServeRequest

    rng = np.random.default_rng(0)
    page = engine.page
    with annotate("bench:warmup"):
        for t in gen.prompt_lengths(mix):
            engine.submit(ServeRequest(
                tokens=rng.integers(2, vocab, t).tolist(), max_new_tokens=2))
        engine.drain()
        for n in range(1, max_pages + 1):
            engine.pools.release(engine.pools.alloc(n))
        chunk = engine.prefill_chunk
        pairs = {(-(-(len(r.prompt) + r.max_new) // page),
                  -(-len(r.prompt) // page))
                 for r in reqs if chunk is None or len(r.prompt) <= chunk}
        for need, n in sorted(pairs):
            jnp.zeros((need,), jnp.int32)[:n].block_until_ready()


class TraceWindow:
    """Starts the run's trace ``trace_offset_s`` into the window and stops
    it ``trace_s`` later, each time with the device settled."""

    def __init__(self, run, engine, probe, w0):
        p = run.cell.params
        self.on = run.trace
        self.t0 = w0 + p.get("trace_offset_s", 2.0)
        self.t1 = self.t0 + p.get("trace_s", 4.0)
        self.run, self.engine, self.probe = run, engine, probe
        self.cm = None
        self.done = False

    def tick(self, now):
        if self.on and self.cm is None and not self.done and now >= self.t0:
            _settle(self.engine)
            self.cm = self.run.tracer.window()
            self.cm.__enter__()
            self.probe.traced = True
        if self.cm is not None and now >= self.t1:
            self.close()

    def close(self):
        if self.cm is not None:
            _settle(self.engine)
            self.cm.__exit__(None, None, None)
            self.cm, self.done = None, True
            self.probe.traced = False


def _submit(engine, req, due, w0, w1):
    from repro.serving.engine import EngineSaturated, ServeRequest

    rec = {"due": due, "prompt": req.prompt, "max_new": req.max_new,
           "out": None, "status": None, "window": w0 <= due < w1}
    try:
        rec["rid"] = engine.submit(ServeRequest(
            tokens=req.prompt.tolist(), max_new_tokens=req.max_new))
    except EngineSaturated:
        rec["status"] = "shed"
    return rec


def drive(run, engine, probe, reqs, t0, w0, w1, annotate):
    """Drive the engine from ``t0`` until the window [w0, w1) closes (wall
    times).  ``open``: each request is submitted when due and the driver
    sleeps while the engine is idle; ``backlog``: the queue is refilled to
    ``backlog_per_slot`` x slots before every round.  Returns the records
    of the requests sent in the window, all records by request id, and
    the generator's lateness per request sent in the window."""
    mix, p = run.cell.traffic, run.cell.params
    recs, by_rid, late = [], {}, []
    backlog = mix["arrivals"] == "backlog"
    target = int(p.get("backlog_per_slot", 2) * engine.max_slots)
    tw = TraceWindow(run, engine, probe, w0)
    i = 0
    while True:
        now = time.time()
        probe.record = w0 <= now < w1
        if probe.record and probe.at_start is None:
            probe.at_start = engine.load()
        tw.tick(now)
        if now >= w1:
            probe.at_close = engine.load()
            break
        with annotate("bench:submit"):
            while True:
                if backlog:
                    if engine.load()["queued"] >= target:
                        break
                    due = now
                elif i >= len(reqs) or t0 + reqs[i].due > now:
                    break
                else:
                    due = t0 + reqs[i].due
                rec = _submit(engine, reqs[i % len(reqs)], due, w0, w1)
                if "rid" in rec:
                    by_rid[rec["rid"]] = rec
                if rec["window"]:
                    late.append(time.time() - due)
                    recs.append(rec)
                i += 1
        if engine.busy:
            for o in probe.step():
                by_rid[o.request_id]["out"] = o
        else:
            nxt = t0 + reqs[i].due if i < len(reqs) else w1
            with annotate("bench:wait"):
                time.sleep(max(0.0, min(nxt, w1) - time.time()))
    tw.close()
    probe.record = False
    return recs, by_rid, late


def latencies(recs, t_end: float):
    """(ttft_s, tpot_ms, failed) over the records: time to first token
    from when each request was due; per output token after the first;
    a shed, failed or unfinished request counts as missing any limit (its
    latency runs to ``t_end``)."""
    ttft, tpot, failed = [], [], 0
    for r in recs:
        o = r["out"]
        if o is None or not o.finished_ok:
            failed += 1
            ttft.append(t_end - r["due"])
            tpot.append((t_end - r["due"]) * 1e3)
            continue
        ttft.append(o.first_token_time - r["due"])
        tpot.append((o.finish_time - o.first_token_time)
                    / max(len(o.tokens) - 1, 1) * 1e3)
    return ttft, tpot, failed


def _settle(engine):
    import jax

    jax.block_until_ready(engine.pools.pools)


def drain(engine, probe, by_rid, limit_s: float):
    t_end = time.time() + limit_s
    while engine.busy and time.time() < t_end:
        for o in probe.step():
            by_rid[o.request_id]["out"] = o


def sample_requests(outs, seed: int, n: int):
    """``n`` finished requests drawn from the seed, the longest first."""
    ok = [o for o in outs if o["out"] is not None and o["out"].finished_ok]
    if not ok:
        return []
    longest = max(ok, key=lambda o: len(o["prompt"]) + len(o["out"].tokens))
    rest = [o for o in ok if o is not longest]
    rng = np.random.default_rng(seed)
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[k] for k in sorted(pick)]


def check(run, sample, first_logits) -> tuple[list, dict]:
    """Against the plain reference, over the sample: the worst relative
    distance of the logits the engine sampled each first token from (the
    prompt programs), and the median over the sampled requests of the
    share of each one's served tokens whose reference logit lies more
    than two bfloat16 steps below the reference's best (decode, through
    the paged cache).  The share pooled over all served tokens and the
    widest gap are printed beside them: a random model's greedy output
    can pass again and again through positions where any rounding turns
    the last hidden state round, and one long request then carries the
    pooled share (see PERF.md).

    With ``--control 1`` the control takes the program's place: the
    reference at float8 activations gives the first-token logits, and at
    each served position its own first token stands for the served one,
    so both numbers, and ``correct``, are the control's."""
    import jax.numpy as jnp

    ref = spec.reference_module(run.cell)
    p = run.cell.params
    lim = p["limits"]
    checks = [{"name": "first_logits_rel", "value": None,
               "limit": lim["first_logits_rel"],
               "rule": "|l_prog - l_ref| / |l_ref - mean| of each first "
                       "token's logits, worst over the sample"},
              {"name": "flip_share_median", "value": None,
               "limit": lim["flip_share_median"],
               "rule": "median over the sampled requests of the share of "
                       "served tokens whose reference logit lies more than "
                       "two bfloat16 steps below the best"}]
    if not sample:
        return checks, {"compared_requests": 0}
    kw = dict(max_rows=run.cell.traffic["output"]["max"])
    packed = (p["bits"], p["group_size"])
    seqs, rows, served, owner = [], [], [], []
    for i, o in enumerate(sample):
        t, toks = len(o["prompt"]), o["out"].tokens
        seqs.append(np.concatenate([o["prompt"],
                                    np.asarray(toks[:-1], np.int32)]))
        rows.append(np.arange(t - 1, t - 1 + len(toks)))
        served += list(toks)
        owner += [i] * len(toks)
    t0 = time.perf_counter()
    logits = ref.serve_logits(run.cell.config, run.seed, packed, seqs, rows,
                              **kw)
    first = np.cumsum([0] + [len(r) for r in rows])[:-1]
    # a request preempted inside its prompt takes its first token on the
    # replay, which the probe does not see; one with none seen at all means
    # the engine no longer calls the hook that Probe wraps
    seen = [k for k, o in enumerate(sample) if o["rid"] in first_logits]
    if not seen:
        raise RuntimeError("Probe saw no first-token logits: the engine no "
                           "longer samples them through _sample_token0")
    got = [first_logits[sample[k]["rid"]] for k in seen]
    if run.control:
        low = ref.serve_logits(run.cell.config, run.seed, packed, seqs, rows,
                               act_dtype=jnp.float8_e4m3fn, **kw)
        served, got = low.argmax(-1), list(low[first[seen]])
    served = jnp.asarray(served)
    best = logits.max(-1)
    gap = np.asarray(best - jnp.take_along_axis(logits, served[:, None],
                                                -1)[:, 0])
    owner = np.asarray(owner)
    flip = gap > _bf16_step(best)
    extra = {"reference_s": time.perf_counter() - t0,
             "compared_tokens": int(served.shape[0]),
             "requests": [{"prompt": len(o["prompt"]),
                           "tokens": len(o["out"].tokens),
                           "preempted": o["out"].n_preempted,
                           "gap": float(gap[owner == i].max()),
                           "flips": int(flip[owner == i].sum())}
                          for i, o in enumerate(sample)]}
    checks[0]["value"] = _first_rel(got, logits[first[seen]])
    checks[1]["value"] = float(statistics.median(
        float(flip[owner == i].mean()) for i in range(len(sample))))
    extra["flip_share_pooled"] = float(flip.mean())
    extra["logit_gap"] = float(gap.max())
    if run.control:
        extra["answers"] = "the control's (reference at float8 activations)"
    return checks, extra


def _bf16_step(best):
    """Twice the spacing of bfloat16 numbers at each best logit: the
    engine takes its argmax over bfloat16 logits, so it cannot order two
    tokens closer than one spacing, and its hidden state carries its own
    bfloat16 rounding (``first_logits_rel``) besides."""
    b = np.abs(np.asarray(best, np.float64))
    return 2.0 * np.exp2(np.floor(np.log2(np.maximum(b, 1e-30))) - 7)


def _first_rel(got, want) -> float:
    import jax.numpy as jnp

    worst = 0.0
    for g, w in zip(got, want):
        g = jnp.asarray(g, jnp.float32).reshape(-1)
        worst = max(worst, float(jnp.linalg.norm(g - w)
                                 / jnp.linalg.norm(w - w.mean())))
    return worst


def run(run):
    import jax

    from repro.kernels import demotions

    cell, p, mix = run.cell, run.cell.params, run.cell.traffic
    annotate = _annotate()
    model, params, engine, max_pages = build(run)
    log(f"built weights and engine at {time.perf_counter() - run.t_start!r}"
        f" s; peak {harness.peak_bytes(jax.devices()[:1])!r} B")
    vocab = model.cfg.vocab_size
    probe = Probe(engine, annotate)
    preroll = float(p.get("preroll_s", 0.0))
    if mix["arrivals"] == "open":
        reqs = gen.open_loop(mix, run.seed, preroll, run.seconds, vocab,
                             p["rate"])
    elif mix["arrivals"] == "backlog":
        reqs = gen.backlog(mix, run.seed, p["pool"], vocab)
    else:
        raise ValueError(f"unknown arrivals {mix['arrivals']!r}")
    warm_up(engine, mix, vocab, max_pages, reqs, annotate)
    log(f"warmed up at {time.perf_counter() - run.t_start!r} s")
    c0 = run.compiles.snapshot()
    # the pre-roll of traffic is set-up of a fixed length
    setup_s = run.setup_done(ahead=preroll)
    t0 = time.time()
    w0, w1 = t0 + preroll, t0 + preroll + run.seconds
    recs, by_rid, late = drive(run, engine, probe, reqs, t0, w0, w1,
                               annotate)
    c1 = run.compiles.snapshot()
    if mix["arrivals"] == "open":
        drain(engine, probe, by_rid, p.get("drain_limit_s", 60.0))
    t_end = time.time()
    mem = harness.peak_bytes(jax.devices()[:cell.chips])
    ttft, tpot, failed = latencies(recs, t_end)
    metrics = {"setup_s": setup_s}
    if mix["arrivals"] == "open" and ttft:
        metrics["ttft_p90_s"] = quantile(ttft, 0.9)
        metrics["tpot_p90_ms"] = quantile(tpot, 0.9)
        log(f"ttft_s p50 {quantile(ttft, 0.5)!r} p90 "
            f"{metrics['ttft_p90_s']!r}; tpot_ms p50 {quantile(tpot, 0.5)!r}"
            f" p90 {metrics['tpot_p90_ms']!r}; over {len(ttft)} requests")
    metrics["out_tok_s"] = probe.tokens_out / run.seconds
    log(f"output tokens in the window {probe.tokens_out} "
        f"({metrics['out_tok_s']!r} /s)")
    if mix["arrivals"] == "backlog":
        # a closed backlog: what was sent in the window and is still
        # running at its close is not failed
        failed = sum(1 for r in recs if r["status"] == "shed" or (
            r["out"] is not None and not r["out"].finished_ok))
    inv = run.invariants
    inv["requests"] = (f"sent {len(recs)} succeeded "
                       f"{sum(1 for r in recs if r['out'] is not None and r['out'].finished_ok)} "
                       f"failed {failed} (sent in the window)")
    inv["generator_lateness_s"] = (
        f"p50 {quantile(late, 0.5)!r} max {max(late)!r}" if late else "none")
    inv["compiles_in_window"] = run.compiles.since(c0, c1)
    inv["kernel_demotions"] = demotions.counts()
    inv["preemptions"] = engine.n_preemptions
    inv["memory_peak_bytes"] = mem
    inv["rounds_in_window"] = len(probe.rounds_s)
    inv["load_at_window_start"] = probe.at_start
    inv["load_at_window_close"] = probe.at_close
    readings = {"rounds_s": probe.rounds_s, "slots": probe.slots,
                "bursts": probe.bursts, "prefill": probe.prefill,
                "config": cell.config, "params": p,
                "burst_steps": engine.burst_steps,
                "max_slots": engine.max_slots}
    done = [r for r in by_rid.values() if r["out"] is not None]
    sample = sample_requests(done, run.seed, p["check_requests"])
    first_logits = {o["rid"]: probe.first_logits[o["rid"]]
                    for o in sample if o["rid"] in probe.first_logits}
    del engine, params, probe
    gc.collect()
    checks, extra = check(run, sample, first_logits)
    correct = bool(sample) and all(
        c["value"] is not None and c["value"] <= c["limit"] for c in checks)
    return {"correct": correct, "attempted": len(recs), "failed": failed,
            "metrics": metrics, "checks": checks, "readings": readings,
            "memory_peak_bytes": mem, "extra": extra}
