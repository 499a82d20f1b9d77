"""Continuous-batching serve engine over block-paged quantized KV pools.

Requests arrive (``submit``), prefill into freshly allocated pages, join
the running decode batch at the next scheduling round (``step``), and
retire as soon as they hit EOS or their token budget — releasing their
pages for the next admission.  Decode runs in *bursts*: a jitted
``lax.scan`` of ``burst_steps`` paged decode steps whose carry holds every
slot's token / position / emitted-count / liveness, so the host only
intervenes at scheduling rounds, exactly like the fixed-batch scan loop
of ``launch.serve``.

Determinism contract (pinned by tests/test_serving.py): a request's
tokens are bitwise the ones ``launch.serve.generate`` produces for the
same prompt alone at batch 1 with the same ``SamplingParams`` — the
engine replicates its sampling stream exactly (token ``j`` is drawn with
``fold_in(key(seed), j)``; token 0 comes from the prefill logits) and the
paged attention matches the flat cache bitwise at tile = page.  The one
structural exception is MoE models, where expert-capacity dropping
couples tokens across the batch (true of any batched serving, the
fixed-batch loop included).

Admission policy: pages for the *whole* request (prompt + max_new_tokens,
rounded up to whole pages) are reserved at admission — a running request
can never hit the allocator mid-flight.  With ``prefill_chunk=N`` the
prompt is ingested chunk by chunk, one chunk per scheduling round per
ingesting slot, interleaved with decode bursts (see serving/README.md).

Overload policy (this module's degradation story, see serving/README.md
"Overload policy"):

* **Preemption-and-requeue** — when the head-of-queue request cannot be
  admitted (pages, or a slot held by strictly-lower priority), the
  scheduler preempts the lowest-priority / youngest eligible running
  request: releases its pages, records its emitted tokens, requeues it.
  On re-admission the prompt is re-ingested through the exact prefill
  path (rebuilding its KV pages bitwise) and the already-emitted tokens
  are *replayed* through teacher-forced decode steps inside the normal
  burst — each replayed step reproduces the original step's inputs and
  cache bits, so the resumed ``fold_in(key(seed), j)`` sampling stream
  continues bit-identically to an unpreempted run.
* **Deadlines / priority** — ``SamplingParams.deadline_s`` retires
  expired requests (queued or running) with status ``deadline_exceeded``;
  ``priority`` orders admission and bounds who may be preempted.
* **Backpressure** — ``queue_depth`` / ``admit_watermark`` bound the
  queue; a rejected ``submit`` raises :class:`EngineSaturated` with a
  retry-after hint and the pool occupancy instead of queueing unbounded.
* **Fault injection + watchdog** — ``fault_plan`` arms
  ``(round, stage in runtime.fault.SERVE_STAGES)`` failure points; a
  failed burst retries per ``RetryPolicy`` (state is untouched when a
  stage point fires, so the retry re-runs from identical inputs), a
  poisoned request is isolated with status ``failed``, and a stuck-round
  watchdog emits structured events before raising :class:`EngineStuck`.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.flash_decode import paged_work_len
from repro.runtime.fault import EventLog, RetryPolicy
from repro.runtime.tracing import TRACER
from repro.serving.paged import PagedPools


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request sampling: greedy at ``temperature == 0``, categorical
    over ``logits / temperature`` otherwise, keyed by ``seed`` (the same
    stream ``launch.serve.generate`` draws for ``key(seed)``).
    ``eos_token`` stops generation early when sampled (-1: never).

    ``priority`` orders admission (higher first; FIFO within a level) and
    bounds preemption — a request only ever preempts strictly-lower
    priority for a slot, lower-or-equal-but-younger for pages.
    ``deadline_s`` (0: none) retires the request with status
    ``deadline_exceeded`` once that many seconds have passed since
    ``submit``, whether it is still queued or already decoding."""
    temperature: float = 0.0
    seed: int = 0
    eos_token: int = -1
    priority: int = 0
    deadline_s: float = 0.0


@dataclasses.dataclass(frozen=True)
class ServeRequest:
    """One generation request: prompt token ids + a token budget + its
    sampling params.  The single request type shared by the engine, the
    CLI and ``generate_batch``."""
    tokens: tuple
    max_new_tokens: int
    sampling: SamplingParams = SamplingParams()

    def __post_init__(self):
        object.__setattr__(self, "tokens", tuple(int(t) for t in self.tokens))
        if not self.tokens:
            raise ValueError("ServeRequest needs at least one prompt token")
        if self.max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {self.max_new_tokens}")


@dataclasses.dataclass
class RequestOutput:
    """Terminal record for one request.  ``status`` is always definite:
    ``ok`` (finished clean, never preempted), ``preempted_N`` (finished
    clean after N preemptions — tokens still bit-identical to a solo
    run), ``deadline_exceeded`` (partial tokens), ``failed`` (isolated by
    a fault), or ``shed`` (rejected at submit; synthesized by
    ``run_trace``, never by the engine itself)."""
    request_id: int
    tokens: list
    prompt_len: int
    submit_time: float
    finish_time: float
    first_token_time: float = 0.0
    status: str = "ok"
    n_preempted: int = 0

    @property
    def latency(self) -> float:
        return self.finish_time - self.submit_time

    @property
    def ttft(self) -> float:
        """Time to first token: submit -> the round that sampled token 0
        from the (last chunk of the) prefill."""
        return self.first_token_time - self.submit_time

    @property
    def finished_ok(self) -> bool:
        """Full budget / EOS reached (possibly after preemptions)."""
        return self.status == "ok" or self.status.startswith("preempted")


class EngineSaturated(RuntimeError):
    """``submit`` rejected by backpressure: the bounded queue (or the
    demand watermark) is full.  Carries ``retry_after_s`` (hint from the
    engine's service-time estimate), ``occupancy`` (live page fraction)
    and ``queued`` for programmatic callers; ``run_trace`` records such
    requests with status ``shed``."""


class EngineStuck(RuntimeError):
    """The watchdog saw no progress for twice its round budget while the
    engine was still busy — raised so a wedged engine fails loudly
    instead of hanging ``drain()`` forever."""


@dataclasses.dataclass
class _QueueEntry:
    """One queued (or preempted-and-requeued) request.  ``resume`` is
    None for a fresh submission, else the tokens already emitted before
    preemption (>= 1: token 0 was drawn at the original admission)."""
    rid: int
    req: ServeRequest
    resume: Optional[list] = None

    @property
    def key(self):
        # admission order: highest priority first, then FIFO by rid — a
        # preempted request keeps its original rid, so it re-enters ahead
        # of same-priority requests submitted after it
        return (-self.req.sampling.priority, self.rid)


@functools.lru_cache(maxsize=64)
def _prefill_fn(model, cache_len: int):
    return jax.jit(lambda p, x: model.prefill(p, x, cache_len=cache_len))


@functools.lru_cache(maxsize=64)
def _resume_prefill_fn(model, cache_len: int):
    """Prompt re-ingest for a preempted request: same prefill float ops
    (so the rebuilt KV pages are bitwise the originals) minus the head
    projection — token 0 was already drawn before preemption."""
    return jax.jit(lambda p, x: model.prefill(p, x, cache_len=cache_len,
                                              logits=False)[1])


@functools.lru_cache(maxsize=64)
def _extend_fn(model, t_total: int, last: bool):
    """One exact-mode chunk step: fp prefix buffers donated through."""
    return jax.jit(
        lambda p, x, start, state: model.paged_extend_step(
            p, x, start, state, t_total=t_total, last=last),
        donate_argnums=(3,))


@functools.lru_cache(maxsize=64)
def _extend_paged_fn(model, t_total: int, last: bool):
    """One paged-mode chunk step: reads the request's quantized pages."""
    def run(p, x, start, pools, tbl):
        logits, _, cc = model.paged_extend_step(
            p, x, start, None, t_total=t_total, last=last, pools=pools,
            page_tbl=tbl)
        return logits, cc
    return jax.jit(run)


@functools.lru_cache(maxsize=16)
def _burst_fn(model, n_steps: int):
    """One jitted program for a scheduling round: ``n_steps`` paged decode
    steps with per-slot sampling state in the scan carry, pools donated.

    Emits ``(toks, emitted)`` per step; slots deactivate in-carry on EOS /
    budget so a retired-mid-burst slot stops emitting (and its appends
    divert to the trash page) without any host round-trip.

    ``forced``/``fmask`` (n_steps, B) teacher-force the emitted token at
    masked steps — the preemption-resume replay: a replayed step feeds the
    same input token at the same position into the same cache bits as the
    original run, so its KV append (and every later logit) is bitwise the
    original; the sampling stream is untouched (``fold_in`` is stateless
    per step) and resumes exactly at the first unmasked step."""

    def run(params, pools, tbl, tok, pos, nem, act, temp, seeds, eos,
            max_new, forced, fmask):
        keys = jax.vmap(jax.random.key)(seeds)
        safe_temp = jnp.where(temp > 0, temp, 1.0)

        def sample_one(key, nem_i, logits_i, temp_i):
            sub = jax.random.fold_in(key, nem_i)
            return jax.random.categorical(
                sub, logits_i[None] / temp_i, axis=-1).astype(jnp.int32)[0]

        def body(carry, xs):
            f, m = xs
            pools, tok, pos, nem, act = carry
            logits, pools = model.paged_decode_step(params, pools, tbl, tok,
                                                    pos, act)
            sampled = jax.vmap(sample_one)(keys, nem, logits, safe_temp)
            greedy = jnp.argmax(logits, -1).astype(jnp.int32)
            nxt = jnp.where(temp > 0, sampled, greedy)
            nxt = jnp.where(m, f, nxt)  # replayed step: teacher-forced
            emitted = act
            nem2 = nem + act.astype(jnp.int32)
            done = act & ((nxt == eos) | (nem2 >= max_new))
            return (pools, nxt[:, None], pos + act.astype(jnp.int32), nem2,
                    act & ~done), (jnp.where(act, nxt, -1), emitted)

        (pools, tok, pos, nem, act), (toks, em) = jax.lax.scan(
            body, (pools, tok, pos, nem, act), (forced, fmask),
            length=n_steps)
        return pools, tok, pos, nem, act, toks, em

    return jax.jit(run, donate_argnums=(1,))


class Engine:
    """Continuous-batching engine: ``submit()`` requests, drive scheduling
    rounds with ``step()`` (or ``drain()`` to completion); each round
    expires deadlines, admits queued requests (preempting if the head
    cannot fit), advances prompt ingestion, runs one decode burst for
    every live slot at once, and retires the finished."""

    def __init__(self, model, params, *, max_slots: int = 4,
                 n_pages: int = 64, max_pages_per_request: int = 8,
                 burst_steps: int = 8, prefill_chunk: Optional[int] = None,
                 prefill_attn: str = "exact",
                 queue_depth: Optional[int] = None,
                 admit_watermark: Optional[float] = None,
                 fault_plan=None, retry: Optional[RetryPolicy] = None,
                 watchdog_rounds: int = 256, on_event=None):
        cfg = model.cfg
        metas = tuple(model.prefix_metas) + tuple(model.group_metas)
        bad = sorted({m.mixer for m in metas} - {"attn", "mla"})
        if bad:
            raise ValueError(
                f"paged serving supports attn/mla mixers, model has {bad} "
                "— ssm/cross-attention state is per-slot, not per-page; "
                "serve such models through launch.serve.generate")
        if any(m.has_cross for m in metas) or cfg.family == "encdec":
            raise ValueError(
                "paged serving does not support cross-attention caches "
                "(media/encoder KV is request-global, not paged); use "
                "launch.serve.generate")
        if getattr(model.ctx, "enabled", False):
            raise ValueError(
                "the engine is meshless — it owns the batch axis and the "
                "paged kernels take no shard_map route; build the model "
                "with the LOCAL ctx for serving")
        if prefill_attn not in ("exact", "paged"):
            raise ValueError(
                f"prefill_attn must be 'exact' or 'paged', got "
                f"{prefill_attn!r}")
        self.model = model
        self.params = params
        self.pools = PagedPools(model, n_pages)  # validates kv_bits
        self.page = self.pools.page
        self.max_slots = max_slots
        self.max_pages = max_pages_per_request
        self.burst_steps = burst_steps
        # items a step of the GQA paged decode walks, live or not (its
        # work list); the MLA paged decode keeps the dense grid
        self._work_len = (paged_work_len(max_slots, self.max_pages,
                                         n_pages + 1)
                          if any(m.mixer == "attn" for m in metas) else None)
        if prefill_chunk is not None:
            if prefill_chunk < 1:
                raise ValueError(
                    f"prefill_chunk must be >= 1, got {prefill_chunk}")
            # chunk boundaries must be page-aligned (a kv2 scale group
            # never straddles a chunk, and chunk pages scatter whole)
            prefill_chunk = -(-prefill_chunk // self.page) * self.page
        self.prefill_chunk = prefill_chunk
        self.prefill_attn = prefill_attn
        self.queue_depth = queue_depth
        self.admit_watermark = admit_watermark
        self.fault_plan = fault_plan
        self.retry = retry if retry is not None else RetryPolicy()
        self.watchdog_rounds = watchdog_rounds
        self.events = EventLog(on_event, verbose=False)
        self._now = time.time  # monkeypatchable clock (deadline tests)

        # per-slot scheduling state lives on the HOST: admission writes a
        # handful of scalars per request, and as numpy rows that is free —
        # as device arrays it was ~10 tiny dispatches per admission, a
        # measurable slice of small-model serving time.  The burst uploads
        # the (tiny) state with its dispatch and the results mirror back.
        b = max_slots
        self.tbl = np.zeros((b, self.max_pages), np.int32)
        self.tok = np.zeros((b, 1), np.int32)
        self.pos = np.zeros((b,), np.int32)
        self.nem = np.zeros((b,), np.int32)
        self.act = np.zeros((b,), bool)
        self.temp = np.zeros((b,), np.float32)
        self.seeds = np.zeros((b,), np.uint32)
        self.eos = np.full((b,), -1, np.int32)
        self.max_new = np.ones((b,), np.int32)

        self._queue: list[_QueueEntry] = []
        self._next_rid = 0
        self._slot_rid = [None] * b          # rid occupying each slot
        self._slot_pages = [None] * b        # np page ids of each slot
        self._slot_tokens = [None] * b       # emitted tokens (host)
        self._slot_req = [None] * b
        self._ingest = [None] * b            # chunked-prefill progress
        self._replay = [None] * b            # forced tokens left to replay
        self._slot_base = [0] * b            # tokens held at admission
        self._submit_time = {}
        self._first_token_time = {}
        self._n_preempted = {}               # rid -> preemption count
        self._outputs = []
        self._round = 0
        self._idle_rounds = 0
        self._progress = False
        self._service_ema = None             # EMA of completed latency
        self.n_preemptions = 0

    # ------------------------------------------------------------------ API
    def submit(self, request: ServeRequest) -> int:
        """Queue a request; returns its id.  Admission happens at the next
        ``step()``.  Requests that can never fit are rejected here, and
        backpressure (``queue_depth`` / ``admit_watermark``) rejects with
        :class:`EngineSaturated` + a retry-after hint instead of queueing
        unbounded."""
        need = self._pages_for(request)
        sizing = self.pools.sizing(len(request.tokens),
                                   request.max_new_tokens)
        if need > self.max_pages:
            raise ValueError(
                f"request needs {sizing} but the page table holds "
                f"{self.max_pages} per request — raise "
                "max_pages_per_request or split the request")
        if need > self.pools.n_pages:
            # fail fast with the allocator's own sizing math: this request
            # can never fit even an empty pool, so queueing it would only
            # defer the same failure to admission time
            raise self.pools.exhausted(
                need, have=self.pools.n_pages,
                context=f" (submit: {sizing} can never fit)")
        queued = len(self._queue)
        if self.queue_depth is not None and queued >= self.queue_depth:
            occ, hint = self.pools.occupancy(), self._retry_after()
            raise self._saturated(
                f"engine saturated: {queued} queued at queue_depth="
                f"{self.queue_depth}, pool occupancy {occ:.0%} — "
                f"retry after ~{hint:.2f}s", hint, occ, queued)
        if self.admit_watermark is not None:
            cap = self.admit_watermark * self.pools.n_pages
            demand = ((self.pools.n_pages - self.pools.free_pages())
                      + sum(self._pages_for(e.req) for e in self._queue)
                      + need)
            if demand > cap:
                occ, hint = self.pools.occupancy(), self._retry_after()
                raise self._saturated(
                    f"engine saturated: outstanding demand of {demand} "
                    f"pages exceeds the admit watermark ({cap:.0f} = "
                    f"{self.admit_watermark:g} x {self.pools.n_pages} "
                    f"pages), pool occupancy {occ:.0%} — retry after "
                    f"~{hint:.2f}s", hint, occ, queued)
        rid = self._next_rid
        self._next_rid += 1
        self._queue.append(_QueueEntry(rid, request))
        t = self._submit_time[rid] = self._now()
        TRACER.phase(rid, "queued", t, prompt=len(request.tokens))
        return rid

    def load(self) -> dict:
        """Live occupancy snapshot (the watermark signal): free pages,
        pool occupancy, queued / running request counts."""
        return {"free_pages": self.pools.free_pages(),
                "occupancy": self.pools.occupancy(),
                "queued": len(self._queue),
                "running": sum(r is not None for r in self._slot_rid)}

    def step(self) -> list:
        """One scheduling round: expire deadlines, admit queued requests
        into free slots (preempting when the head cannot fit), advance
        every ingesting slot by one prompt chunk, run one decode burst
        over the live batch, retire the finished.  Returns every request
        that reached a terminal status this round."""
        with TRACER.span("engine.round"):
            self._round += 1
            TRACER.count("engine.rounds")
            self._progress = False
            outs = self._expire_deadlines()
            with TRACER.span("engine.admit"):
                self._admit(outs)
            self._advance_ingest(outs)
            if self.act.any():
                self._burst_guarded(outs)
            with TRACER.span("engine.retire"):
                outs.extend(self._retire_guarded())
            self._watchdog()
        return outs

    @property
    def busy(self) -> bool:
        """True while any request is queued, ingesting, decoding, or
        finished but not yet retired (a retire-stage fault defers
        retirement by one round)."""
        return (bool(self._queue) or bool(self.act.any())
                or any(r is not None for r in self._slot_rid))

    def drain(self) -> list:
        """Run ``step()`` until every submitted request has finished, then
        verify the page free list is back to its initial size — any page
        leaked (or double-counted) by admission/preemption/retirement
        fails loudly here rather than as mysterious exhaustion later."""
        out = []
        while self.busy:
            out.extend(self.step())
        self.pools.assert_quiescent()
        return out

    # ------------------------------------------------------------ internals
    def _pages_for(self, req: ServeRequest) -> int:
        return -(-(len(req.tokens) + req.max_new_tokens) // self.page)

    def _saturated(self, msg: str, hint: float, occ: float,
                   queued: int) -> EngineSaturated:
        err = EngineSaturated(msg)
        err.retry_after_s, err.occupancy, err.queued = hint, occ, queued
        return err

    def _retry_after(self) -> float:
        """Back-of-envelope retry hint: expected service time per request
        (EMA of completed latencies, 100ms floor before any completion)
        times queue-ahead-of-you, divided across the slots."""
        ema = self._service_ema if self._service_ema is not None else 0.1
        return ema * (len(self._queue) + 1) / self.max_slots

    def _phase(self, rid: int, phase: str, t: Optional[float] = None
               ) -> None:
        """Open ``phase`` of request ``rid``'s traced lifecycle at ``t``
        (now on the engine's clock), closing the one before."""
        if TRACER.enabled:
            TRACER.phase(rid, phase, self._now() if t is None else t)

    def _check_fault(self, stage: str) -> None:
        if self.fault_plan is not None:
            self.fault_plan.check(self._round, stage)

    # ---------------------------------------------------- deadline expiry
    def _expire_deadlines(self) -> list:
        now = self._now()

        def expired(rid, req):
            d = req.sampling.deadline_s
            return d > 0 and now - self._submit_time[rid] > d

        outs, keep = [], []
        for ent in self._queue:
            if expired(ent.rid, ent.req):
                outs.append(self._finish(ent.rid, ent.req,
                                         list(ent.resume or []),
                                         "deadline_exceeded"))
            else:
                keep.append(ent)
        self._queue = keep
        for s in range(self.max_slots):
            rid = self._slot_rid[s]
            if rid is not None and expired(rid, self._slot_req[s]):
                outs.append(self._fail_slot(s, "deadline_exceeded"))
        if outs:
            self._progress = True
        return outs

    # ------------------------------------------------------------ admission
    def _admit(self, outs: list) -> None:
        while self._queue:
            ent = min(self._queue, key=lambda e: e.key)
            need = self._pages_for(ent.req)
            slot = next((s for s in range(self.max_slots)
                         if self._slot_rid[s] is None), None)
            if slot is None:
                # slot pressure: only a strict priority inversion may
                # preempt for a slot (equal-priority FIFO holds its slot)
                victims = self._victims(ent, strict=True)
                if not victims or not self._fits_after(need, victims):
                    TRACER.count("engine.admit_blocked_slots")
                    return
                slot = victims[0]
                self._preempt(slot, ent.rid)
            if need > self.pools.free_pages():
                if not self._preempt_to_fit(need, ent):
                    if any(r is not None for r in self._slot_rid):
                        # wait for a retirement to free pages
                        TRACER.count("engine.admit_blocked_pages")
                        return
                    # empty engine and still no room: raise the actionable
                    # exhaustion error (pool is simply too small)
                    self.pools.alloc(need, context=f" (request {ent.rid})")
            self._queue.remove(ent)
            try:
                self._check_fault("admit")
                ids = self.pools.alloc(need, context=f" (request {ent.rid})")
            except Exception as e:
                if not self.retry.is_recoverable(e):
                    raise
                outs.append(self._finish(ent.rid, ent.req,
                                         list(ent.resume or [])[
                                             :ent.req.max_new_tokens],
                                         "failed", error=repr(e)))
                continue
            self._phase(ent.rid, "ingest")
            try:
                if ent.resume is not None:
                    self._start_resume(slot, ent, ids)
                elif (self.prefill_chunk is not None
                        and len(ent.req.tokens) > self.prefill_chunk):
                    self._start_chunked(slot, ent.rid, ent.req, ids)
                else:
                    self._start(slot, ent.rid, ent.req, ids)
            except Exception as e:
                if not self.retry.is_recoverable(e):
                    raise
                # poisoned request: release its reservation, clear the
                # slot, fail it — the engine keeps serving everyone else
                self.pools.release(np.asarray(ids))
                self._clear_slot(slot)
                outs.append(self._finish(ent.rid, ent.req,
                                         list(ent.resume or [])[
                                             :ent.req.max_new_tokens],
                                         "failed", error=repr(e)))
                continue
            self._progress = True

    def _victims(self, ent: _QueueEntry, *, strict: bool) -> list:
        """Preemptable slots for admitting ``ent``, best victim first
        (lowest priority, then youngest — highest rid).  Eligibility: the
        slot holds a decoding request that has emitted >= 1 *fresh* token
        since its (re-)admission — a just-admitted or mid-replay request
        is never preempted, so every admission banks real progress before
        it can be evicted and the preempt/resume cycle always terminates
        (no livelock).  ``strict``: victim priority must be strictly
        lower (slot preemption — equal-priority FIFO keeps its slot);
        else lower or equal (page preemption: a starving head-of-queue
        may evict the youngest same-priority running request)."""
        eprio = ent.req.sampling.priority
        out = []
        for s in range(self.max_slots):
            rid = self._slot_rid[s]
            if rid is None or self._ingest[s] is not None:
                continue
            if len(self._slot_tokens[s]) - self._slot_base[s] < 1:
                continue
            vprio = self._slot_req[s].sampling.priority
            if vprio < eprio or (not strict and vprio == eprio):
                out.append((vprio, -rid, s))
        return [s for _, _, s in sorted(out)]

    def _fits_after(self, need: int, victims: list) -> bool:
        have = self.pools.free_pages()
        have += sum(len(self._slot_pages[s]) for s in victims)
        return need <= have

    def _preempt_to_fit(self, need: int, ent: _QueueEntry) -> bool:
        """Free pages for ``ent`` by preempting eligible victims, fewest
        first; preempts nobody (returns False) when even every eligible
        victim would not make it fit."""
        victims = self._victims(ent, strict=False)
        if not self._fits_after(need, victims):
            return False
        for s in victims:
            if need <= self.pools.free_pages():
                break
            self._preempt(s, ent.rid)
        return True

    def _preempt(self, slot: int, for_rid: int) -> None:
        """Evict the request in ``slot``: release its pages, record its
        emitted tokens, requeue it (it keeps its original rid, so it
        re-enters ahead of same-priority later submissions)."""
        rid = self._slot_rid[slot]
        req = self._slot_req[slot]
        tokens = list(self._slot_tokens[slot])
        self.pools.release(self._slot_pages[slot])
        self._clear_slot(slot)
        self._n_preempted[rid] = self._n_preempted.get(rid, 0) + 1
        self.n_preemptions += 1
        TRACER.count("engine.preemptions")
        self._phase(rid, "queued")
        self._queue.append(_QueueEntry(rid, req, resume=tokens))
        self.events.emit("preempt", request=rid, for_request=for_rid,
                         round=self._round, n_tokens=len(tokens),
                         pages_freed=self.pools.free_pages())

    def _start(self, slot: int, rid: int, req: ServeRequest, ids) -> None:
        t = len(req.tokens)
        sp = req.sampling
        with TRACER.span("engine.prefill", rid=rid):
            prompt = jnp.asarray(req.tokens, jnp.int32)[None]
            logits, cache = _prefill_fn(self.model, t)(self.params, prompt)
            n_pp = -(-self.model._cache_len(t) // self.page)
            self.pools.write_prefill(cache, ids[:n_pp])
            tok0 = self._sample_token0(logits, sp)
        TRACER.count("engine.prompt_tokens", t)
        TRACER.count("engine.tokens_emitted")
        now = self._first_token_time[rid] = self._now()
        self._phase(rid, "decode", now)
        self._claim_slot(slot, rid, req, ids)
        self._slot_tokens[slot] = [tok0]
        # token 0 is admission work, not burst progress: the slot is not
        # preemption-eligible until a burst emits a fresh token
        self._slot_base[slot] = 1
        self._arm_decode(slot, req, tok0)

    def _start_resume(self, slot: int, ent: _QueueEntry, ids) -> None:
        """Re-admit a preempted request: re-ingest the prompt through the
        exact prefill (bitwise the original pages), then queue its emitted
        tokens for teacher-forced replay inside the normal bursts — the
        replayed appends rebuild the generated-token KV codes bitwise, and
        the sampling stream resumes at ``fold_in(key(seed), n)``."""
        req, t = ent.req, len(ent.req.tokens)
        if (self.prefill_chunk is not None and t > self.prefill_chunk):
            self._start_chunked(slot, ent.rid, req, ids, resume=ent.resume)
            return
        with TRACER.span("engine.prefill", rid=ent.rid):
            prompt = jnp.asarray(req.tokens, jnp.int32)[None]
            cache = _resume_prefill_fn(self.model, t)(self.params, prompt)
            n_pp = -(-self.model._cache_len(t) // self.page)
            self.pools.write_prefill(cache, ids[:n_pp])
        TRACER.count("engine.prompt_tokens", t)
        self._claim_slot(slot, ent.rid, req, ids)
        self._arm_resume(slot, req, ent.resume)
        self._phase(ent.rid, "decode")

    def _start_chunked(self, slot: int, rid: int, req: ServeRequest,
                       ids, resume: Optional[list] = None) -> None:
        """Claim a slot for chunk-by-chunk ingestion: pages are reserved
        and the slot occupied, but no prefill compute happens here — each
        ``step()`` advances the slot one chunk via ``_advance_ingest``
        (the slot's ``act`` stays False until its last chunk samples
        token 0, or — on preemption resume — arms the replay)."""
        t = len(req.tokens)
        self._claim_slot(slot, rid, req, ids)
        self._slot_tokens[slot] = []
        state = (self.model.init_ingest(t)
                 if self.prefill_attn == "exact" else None)
        self._ingest[slot] = {"start": 0, "state": state, "resume": resume}

    def _claim_slot(self, slot: int, rid: int, req: ServeRequest,
                    ids) -> None:
        ids_np = np.asarray(ids)
        self._slot_rid[slot] = rid
        self._slot_pages[slot] = ids_np
        self._slot_tokens[slot] = []
        self._slot_req[slot] = req
        self._slot_base[slot] = 0
        self.tbl[slot] = 0
        self.tbl[slot, :len(ids_np)] = ids_np

    def _clear_slot(self, slot: int) -> None:
        self._slot_rid[slot] = self._slot_pages[slot] = None
        self._slot_tokens[slot] = self._slot_req[slot] = None
        self._ingest[slot] = self._replay[slot] = None
        self._slot_base[slot] = 0
        self.act[slot] = False

    def _advance_ingest(self, outs: list) -> None:
        """Advance every ingesting slot by ONE prompt chunk — batched
        admission: the per-round ingest cost is one chunk per queued
        request, never a whole prompt, so decode bursts stay interleaved
        with long-prompt arrivals."""
        for s in range(self.max_slots):
            ing = self._ingest[s]
            if ing is None:
                continue
            try:
                self._check_fault("ingest")
            except Exception as e:
                if not self.retry.is_recoverable(e):
                    raise
                outs.append(self._fail_slot(s, "failed", error=repr(e)))
                continue
            req, rid = self._slot_req[s], self._slot_rid[s]
            t = len(req.tokens)
            start = ing["start"]
            n = min(self.prefill_chunk, t - start)
            last = start + n >= t
            with TRACER.span("engine.ingest", rid=rid,
                             chunk=start // self.prefill_chunk):
                chunk = jnp.asarray(req.tokens[start:start + n],
                                    jnp.int32)[None]
                if ing["state"] is not None:
                    logits, state, cc = _extend_fn(self.model, t, last)(
                        self.params, chunk, jnp.int32(start), ing["state"])
                else:
                    tbl = jnp.asarray(
                        self._slot_pages[s][:start // self.page], jnp.int32)
                    logits, cc = _extend_paged_fn(self.model, t, last)(
                        self.params, chunk, jnp.int32(start),
                        self.pools.pools, tbl)
                    state = None
                n_cp = -(-n // self.page)
                first = start // self.page
                self.pools.write_prefill(
                    cc, jnp.asarray(self._slot_pages[s][first:first + n_cp],
                                    jnp.int32))
                tok0 = (self._sample_token0(logits, req.sampling)
                        if last and ing["resume"] is None else None)
            TRACER.count("engine.prompt_tokens", n)
            self._progress = True
            if not last:
                ing["start"] = start + n
                ing["state"] = state
                continue
            resume = ing["resume"]
            self._ingest[s] = None
            if resume is not None:
                self._arm_resume(s, req, resume)
                self._phase(rid, "decode")
                continue
            TRACER.count("engine.tokens_emitted")
            now = self._first_token_time[rid] = self._now()
            self._phase(rid, "decode", now)
            self._slot_tokens[s] = [tok0]
            self._slot_base[s] = 1  # see _start: token 0 is not progress
            self._arm_decode(s, req, tok0)

    def _sample_token0(self, logits, sp: SamplingParams) -> int:
        """Token 0 from the prefill logits — the exact draw generate()
        makes (``fold_in(key(seed), 0)``), shared by whole-prompt and
        chunked admission."""
        if sp.temperature > 0:
            return int(jax.random.categorical(
                jax.random.fold_in(jax.random.key(sp.seed), 0),
                logits / jnp.float32(sp.temperature), axis=-1)[0])
        return int(jnp.argmax(logits, -1)[0])

    def _arm_decode(self, slot: int, req: ServeRequest, tok0: int) -> None:
        """Write the slot's decode-time sampling state rows after token 0."""
        sp = req.sampling
        done0 = (req.max_new_tokens == 1 or tok0 == sp.eos_token)
        self.tok[slot, 0] = tok0
        self.pos[slot] = len(req.tokens)
        self.nem[slot] = 1
        self.act[slot] = not done0
        self.temp[slot] = sp.temperature
        self.seeds[slot] = np.uint32(sp.seed & 0xFFFFFFFF)
        self.eos[slot] = sp.eos_token
        self.max_new[slot] = req.max_new_tokens

    def _arm_resume(self, slot: int, req: ServeRequest,
                    tokens: list) -> None:
        """Arm decode to continue a preempted stream: the slot re-enters
        the burst as if it had just emitted token 0 (input ``tokens[0]``
        at the prompt boundary, ``nem = 1``), with ``tokens[1:]`` queued
        as teacher-forced outputs — after the replay drains, ``nem`` has
        advanced to ``len(tokens)`` and the next draw is
        ``fold_in(key(seed), len(tokens))``, exactly where the preempted
        stream left off.  A preempted request is always mid-stream (a
        finished one retires before it could be preempted), so the slot
        arms active unconditionally."""
        self._slot_tokens[slot] = list(tokens)
        self._slot_base[slot] = len(tokens)
        self._replay[slot] = collections.deque(tokens[1:]) or None
        sp = req.sampling
        self.tok[slot, 0] = tokens[0]
        self.pos[slot] = len(req.tokens)
        self.nem[slot] = 1
        self.act[slot] = True
        self.temp[slot] = sp.temperature
        self.seeds[slot] = np.uint32(sp.seed & 0xFFFFFFFF)
        self.eos[slot] = sp.eos_token
        self.max_new[slot] = req.max_new_tokens

    # --------------------------------------------------------------- decode
    def _burst_guarded(self, outs: list) -> None:
        """Run the burst under the retry policy: an injected burst fault
        fires at the stage point *before* the dispatch (pools and slot
        rows untouched), so each retry re-runs the identical burst —
        tokens stay bit-identical through any number of retries.  Retries
        exhausted: the decoding requests are failed (isolated) and the
        engine keeps serving its queue."""
        attempt = 0
        while True:
            try:
                self._check_fault("burst")
                self._burst()
                return
            except Exception as e:
                if not self.retry.is_recoverable(e):
                    raise
                attempt += 1
                if attempt > self.retry.max_restarts:
                    self.events.emit("burst_poisoned", round=self._round,
                                     attempts=attempt, error=repr(e))
                    for s in range(self.max_slots):
                        if (self._slot_rid[s] is not None
                                and self._ingest[s] is None):
                            outs.append(self._fail_slot(s, "failed",
                                                        error=repr(e)))
                    return
                back = self.retry.backoff(attempt)
                self.events.emit("burst_retry", round=self._round,
                                 attempt=attempt, backoff_s=back,
                                 error=repr(e))
                if back:
                    time.sleep(back)

    def _burst(self) -> None:
        R, b = self.burst_steps, self.max_slots
        pos0 = self.pos
        with TRACER.span("engine.burst.dispatch"):
            forced = np.zeros((R, b), np.int32)
            fmask = np.zeros((R, b), bool)
            consumed = [0] * b
            for s in range(b):
                q = self._replay[s]
                if q:
                    k = min(R, len(q))
                    forced[:k, s] = [q[i] for i in range(k)]
                    fmask[:k, s] = True
                    consumed[s] = k
            (self.pools.pools, tok, pos, nem, act,
             toks, em) = _burst_fn(self.model, self.burst_steps)(
                self.params, self.pools.pools, self.tbl, self.tok, self.pos,
                self.nem, self.act, self.temp, self.seeds, self.eos,
                self.max_new, forced, fmask)
        with TRACER.span("engine.burst.readback"):
            # np.array, not np.asarray: admission mutates these rows in
            # place
            self.tok, self.pos = np.array(tok), np.array(pos)
            self.nem, self.act = np.array(nem), np.array(act)
            toks, em = np.asarray(toks), np.asarray(em)
            if em.any():
                self._progress = True  # replay advancing counts as progress
            fresh = 0
            for s in range(self.max_slots):
                if self._slot_rid[s] is None or self._ingest[s] is not None:
                    continue
                k = consumed[s]
                if k:
                    # the first k emissions are the teacher-forced replay —
                    # already in _slot_tokens; only fresh tokens append
                    for _ in range(k):
                        self._replay[s].popleft()
                    if not self._replay[s]:
                        self._replay[s] = None
                new = toks[em[:, s], s][k:]
                fresh += len(new)
                self._slot_tokens[s].extend(int(t) for t in new)
        TRACER.count("engine.tokens_emitted", fresh)
        if TRACER.enabled and self._work_len is not None:
            # work items that computed: each step's live slots, tiles
            # 0 .. pos // page of each (a slot live at step r sits at
            # pos0 + r: slots only ever leave a burst)
            tiles = np.minimum((pos0 + np.arange(R)[:, None]) // self.page
                               + 1, self.max_pages)
            TRACER.count("engine.decode_tiles_live", int(tiles[em].sum()))
            TRACER.count("engine.decode_tiles_grid", R * self._work_len)

    # --------------------------------------------------------------- retire
    def _retire_guarded(self) -> list:
        try:
            self._check_fault("retire")
        except Exception as e:
            if not self.retry.is_recoverable(e):
                raise
            # retirement is idempotent host bookkeeping: defer to the next
            # round (the finished slots simply stay resident one round)
            self.events.emit("retire_deferred", round=self._round,
                             error=repr(e))
            return []
        return self._retire()

    def _retire(self) -> list:
        finished = []
        for s in range(self.max_slots):
            rid = self._slot_rid[s]
            if rid is None or self.act[s] or self._ingest[s] is not None:
                continue
            self.pools.release(self._slot_pages[s])
            req = self._slot_req[s]
            toks = self._slot_tokens[s][:req.max_new_tokens]
            self._clear_slot(s)
            finished.append(self._finish(rid, req, toks, "ok"))
            self._progress = True
        return finished

    def _fail_slot(self, slot: int, status: str,
                   error: Optional[str] = None):
        """Terminate the request occupying ``slot`` with a non-ok status:
        release its pages, clear the slot, record the partial tokens."""
        rid = self._slot_rid[slot]
        req = self._slot_req[slot]
        toks = list(self._slot_tokens[slot] or [])[:req.max_new_tokens]
        self.pools.release(self._slot_pages[slot])
        self._clear_slot(slot)
        return self._finish(rid, req, toks, status, error=error)

    def _finish(self, rid: int, req: ServeRequest, tokens: list,
                status: str, error: Optional[str] = None) -> RequestOutput:
        """Build the terminal RequestOutput for ``rid`` (every request
        ends here exactly once, whatever its fate)."""
        n_pre = self._n_preempted.pop(rid, 0)
        if status == "ok" and n_pre:
            status = f"preempted_{n_pre}"
        now = self._now()
        TRACER.finish(rid, now, status)
        out = RequestOutput(
            request_id=rid,
            tokens=tokens,
            prompt_len=len(req.tokens),
            submit_time=self._submit_time.pop(rid),
            finish_time=now,
            first_token_time=self._first_token_time.pop(rid, 0.0),
            status=status,
            n_preempted=n_pre)
        if out.finished_ok:
            lat = out.latency
            self._service_ema = (lat if self._service_ema is None
                                 else 0.7 * self._service_ema + 0.3 * lat)
        else:
            self.events.emit("request_" + status, request=rid,
                             round=self._round, n_tokens=len(tokens),
                             **({"error": error} if error else {}))
        self._outputs.append(out)
        self._progress = True
        return out

    # ------------------------------------------------------------- watchdog
    def _watchdog(self) -> None:
        """Stuck-round detection: a busy engine must make progress every
        round (tokens emitted, a chunk ingested, a request admitted or
        retired).  ``watchdog_rounds`` idle rounds emit a structured
        ``stuck_round`` event; twice that raises :class:`EngineStuck` so
        ``drain()`` fails loudly instead of spinning forever."""
        if not self.busy or self._progress:
            self._idle_rounds = 0
            return
        self._idle_rounds += 1
        if self._idle_rounds == self.watchdog_rounds:
            self.events.emit("stuck_round", round=self._round,
                             idle_rounds=self._idle_rounds,
                             queued=len(self._queue),
                             free_pages=self.pools.free_pages())
        if self._idle_rounds >= 2 * self.watchdog_rounds:
            raise EngineStuck(
                f"no scheduling progress for {self._idle_rounds} rounds "
                f"(round {self._round}: {len(self._queue)} queued, "
                f"{self.pools.free_pages()} of {self.pools.n_pages} pages "
                "free) — the engine is wedged; see the stuck_round event")
