"""Overload behavior of the serve engine (PR 10).

The degradation contract: under slot/page pressure the engine preempts,
requeues and later *resumes* requests such that their final tokens are
still bitwise the solo batch-1 ``generate`` stream — resume re-ingests
the prompt through the exact prefill (bitwise pages) and replays the
already-emitted tokens through teacher-forced decode steps, so the
``fold_in(key(seed), j)`` sampling stream continues exactly where it
left off.  Around that core: deadlines, priority ordering, bounded-queue
backpressure (``EngineSaturated``), engine-stage fault injection reusing
``runtime.fault`` (a failed burst retries bit-identically; a poisoned
request is isolated), a stuck-round watchdog, and page accounting that
turns double frees / leaks into loud ``PageAccountingError``s.
"""
import dataclasses
import functools

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.data.synthetic import SyntheticCorpus
from repro.models import build_model
from repro.runtime.fault import FaultPlan, InjectedFailure, RetryPolicy
from repro.serving import (Engine, EngineSaturated, EngineStuck,
                           PageAccountingError, PagedPools, RequestOutput,
                           SamplingParams, ServeRequest, poisson_trace,
                           run_trace)
from repro.serving.trace import _status_group

PAIRS = [("qwen1.5-4b", 8), ("qwen1.5-4b", 2),
         ("deepseek-v2-236b", 8), ("deepseek-v2-236b", 2)]


@functools.lru_cache(maxsize=None)
def _model_params(name, kv_bits):
    # capacity_factor=100: see test_serving — MoE capacity dropping is the
    # one batch-coupling exception to the bit-identity contract; lift it.
    cfg = dataclasses.replace(get_config(name).reduced(), dtype="float32",
                              capacity_factor=100.0, kv_bits=kv_bits)
    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.key(0))
    return model, params


def _prompts(model, n, t):
    corpus = SyntheticCorpus(vocab_size=model.cfg.vocab_size, seed=0)
    return corpus.sample(jax.random.key(2), n, t)


def _baseline(model, params, prompt, n_gen, sp):
    from repro.launch.serve import generate
    import jax.numpy as jnp
    key = jax.random.key(sp.seed) if sp.temperature > 0 else None
    out = generate(model, params, jnp.asarray(prompt, jnp.int32)[None],
                   n_gen, temperature=sp.temperature, key=key)
    return out[0].tolist()


# ---------------------------------------------------------------- preemption
@pytest.mark.parametrize("name,kv_bits", PAIRS)
def test_preempted_request_bit_identical(name, kv_bits):
    """The tentpole pin: a 3-page pool cannot hold two 2-page requests, so
    admitting B preempts mid-stream A (at position 63 — mid-page) and the
    two then trade the pool until both finish; every preempt/resume cycle
    re-ingests the prompt and replays the emitted tokens across the page
    boundary, and BOTH final streams must be bitwise the solo baseline.
    A samples at temperature (the stronger pin: the resumed ``fold_in``
    stream must continue at the right draw index, not just re-argmax)."""
    model, params = _model_params(name, kv_bits)
    prompts = _prompts(model, 2, 60)
    sp_a = SamplingParams(temperature=1.3, seed=7)
    sp_b = SamplingParams()
    base_a = _baseline(model, params, prompts[0].tolist(), 12, sp_a)
    base_b = _baseline(model, params, prompts[1].tolist(), 6, sp_b)

    engine = Engine(model, params, max_slots=2, n_pages=3,
                    max_pages_per_request=2, burst_steps=3)
    ra = engine.submit(ServeRequest(tokens=prompts[0].tolist(),
                                    max_new_tokens=12, sampling=sp_a))
    engine.step()  # A admitted, emits token 0 + one burst (pos 63, mid-page)
    assert engine.load()["running"] == 1
    rb = engine.submit(ServeRequest(tokens=prompts[1].tolist(),
                                    max_new_tokens=6, sampling=sp_b))
    outs = {o.request_id: o for o in engine.drain()}

    assert outs[ra].tokens == base_a, "preempted stream diverged from solo"
    assert outs[rb].tokens == base_b
    assert outs[ra].n_preempted >= 1
    assert outs[ra].status == f"preempted_{outs[ra].n_preempted}"
    assert outs[ra].finished_ok and outs[rb].finished_ok
    assert engine.n_preemptions >= 1
    assert "preempt" in engine.events.kinds()
    assert engine.pools.free_pages() == 3


def test_preempted_chunked_prefill_resumes_bit_identical():
    """Preemption x chunked prefill: A's 150-token prompt is re-ingested
    chunk by chunk on resume (the ``_start_chunked(resume=...)`` path) and
    B's whole-prompt resume rides the exact-prefill path — both streams
    must stay bitwise the solo baselines through the pool trade."""
    model, params = _model_params("qwen1.5-4b", 8)
    pa = _prompts(model, 1, 150)[0].tolist()
    pb = _prompts(model, 2, 60)[1].tolist()
    sp = SamplingParams()
    base_a = _baseline(model, params, pa, 8, sp)
    base_b = _baseline(model, params, pb, 12, sp)

    engine = Engine(model, params, max_slots=2, n_pages=4,
                    max_pages_per_request=3, burst_steps=4,
                    prefill_chunk=64)
    ra = engine.submit(ServeRequest(tokens=pa, max_new_tokens=8))
    rb = engine.submit(ServeRequest(tokens=pb, max_new_tokens=12))
    outs = {o.request_id: o for o in engine.drain()}
    assert outs[ra].tokens == base_a
    assert outs[rb].tokens == base_b
    assert outs[ra].n_preempted >= 1, "pool pressure should preempt A"
    assert engine.pools.free_pages() == 4


def test_priority_orders_preemption_and_admission():
    """A high-priority arrival takes a slot from the *youngest* strictly
    lower-priority running request: C (younger) is preempted, A (older)
    runs undisturbed, and all three streams stay bitwise correct."""
    model, params = _model_params("qwen1.5-4b", 8)
    prompts = _prompts(model, 3, 60)
    sp = SamplingParams()
    hi = SamplingParams(priority=1)
    bases = [_baseline(model, params, prompts[0].tolist(), 8, sp),
             _baseline(model, params, prompts[1].tolist(), 8, sp),
             _baseline(model, params, prompts[2].tolist(), 4, hi)]

    engine = Engine(model, params, max_slots=2, n_pages=4,
                    max_pages_per_request=2, burst_steps=4)
    ra = engine.submit(ServeRequest(tokens=prompts[0].tolist(),
                                    max_new_tokens=8))
    rc = engine.submit(ServeRequest(tokens=prompts[1].tolist(),
                                    max_new_tokens=8))
    engine.step()  # A and C admitted, both emit fresh tokens
    rb = engine.submit(ServeRequest(tokens=prompts[2].tolist(),
                                    max_new_tokens=4, sampling=hi))
    outs = {o.request_id: o for o in engine.drain()}

    ev = next(e for e in engine.events if e["kind"] == "preempt")
    assert ev["request"] == rc and ev["for_request"] == rb
    assert outs[ra].status == "ok", "older same-priority victim chosen"
    assert outs[rc].n_preempted == 1
    assert outs[rb].status == "ok"
    assert [outs[r].tokens for r in (ra, rc, rb)] == bases


# ------------------------------------------------------------------ deadlines
def test_deadline_expires_queued_and_running_requests():
    """``deadline_s`` retires an expired request whether it is still
    queued (empty tokens) or mid-decode (partial tokens), with status
    ``deadline_exceeded`` — driven by a monkeypatched engine clock."""
    model, params = _model_params("qwen1.5-4b", 8)
    prompts = _prompts(model, 2, 12)
    engine = Engine(model, params, max_slots=1, n_pages=2,
                    max_pages_per_request=1, burst_steps=2)
    clock = {"now": 0.0}
    engine._now = lambda: clock["now"]
    ra = engine.submit(ServeRequest(
        tokens=prompts[0].tolist(), max_new_tokens=20,
        sampling=SamplingParams(deadline_s=5.0)))
    rb = engine.submit(ServeRequest(
        tokens=prompts[1].tolist(), max_new_tokens=4,
        sampling=SamplingParams(deadline_s=1.0)))
    engine.step()  # A admitted (1 slot); B waits in queue
    clock["now"] = 2.0
    outs = {o.request_id: o for o in engine.step()}
    assert outs[rb].status == "deadline_exceeded"
    assert outs[rb].tokens == [], "queued request never emitted"
    clock["now"] = 6.0
    outs = {o.request_id: o for o in engine.step()}
    assert outs[ra].status == "deadline_exceeded"
    assert 0 < len(outs[ra].tokens) < 20, "running request keeps partials"
    assert not outs[ra].finished_ok
    assert engine.events.kinds().count("request_deadline_exceeded") == 2
    engine.drain()
    assert engine.pools.free_pages() == 2


# --------------------------------------------------------------- backpressure
def test_bounded_queue_rejects_with_retry_hint():
    """``queue_depth`` bounds the queue: the rejecting ``EngineSaturated``
    carries a retry-after hint, the live occupancy and the queue length,
    and the same request is accepted once the engine drains."""
    model, params = _model_params("qwen1.5-4b", 8)
    prompts = _prompts(model, 2, 12)
    engine = Engine(model, params, max_slots=1, n_pages=4, queue_depth=1)
    engine.submit(ServeRequest(tokens=prompts[0].tolist(), max_new_tokens=4))
    req_b = ServeRequest(tokens=prompts[1].tolist(), max_new_tokens=4)
    with pytest.raises(EngineSaturated, match="retry after") as ei:
        engine.submit(req_b)
    assert ei.value.retry_after_s > 0
    assert 0.0 <= ei.value.occupancy <= 1.0
    assert ei.value.queued == 1
    assert "occupancy" in str(ei.value)
    engine.drain()
    engine.submit(req_b)  # accepted now
    assert len(engine.drain()) == 1


def test_admit_watermark_bounds_outstanding_demand():
    """``admit_watermark`` rejects a submission whose page demand (live +
    queued + incoming) exceeds the watermark fraction of the pool."""
    model, params = _model_params("qwen1.5-4b", 8)
    prompts = _prompts(model, 3, 60)
    engine = Engine(model, params, max_slots=2, n_pages=4,
                    max_pages_per_request=2, admit_watermark=1.0)
    reqs = [ServeRequest(tokens=p.tolist(), max_new_tokens=8)
            for p in prompts]
    engine.submit(reqs[0])  # demand 2 of 4
    engine.submit(reqs[1])  # demand 4 of 4
    with pytest.raises(EngineSaturated, match="admit watermark"):
        engine.submit(reqs[2])  # demand 6 > 4
    engine.drain()
    engine.submit(reqs[2])
    assert engine.drain()[0].finished_ok
    assert engine.pools.free_pages() == 4


# ------------------------------------------------------------ fault injection
@pytest.mark.parametrize("kv_bits", [8, 2])
def test_burst_fault_retries_bit_identical(kv_bits):
    """An injected burst failure fires *before* the dispatch (pools and
    slot rows untouched), so the retried burst re-runs from identical
    inputs and every stream stays bitwise the solo baseline."""
    model, params = _model_params("qwen1.5-4b", kv_bits)
    prompts = _prompts(model, 2, 60)
    sps = [SamplingParams(), SamplingParams(temperature=1.3, seed=7)]
    budgets = [10, 7]
    bases = [_baseline(model, params, prompts[i].tolist(), budgets[i],
                       sps[i]) for i in range(2)]
    plan = FaultPlan({(2, "burst"): 1})
    engine = Engine(model, params, max_slots=2, n_pages=8,
                    max_pages_per_request=2, burst_steps=4,
                    fault_plan=plan, retry=RetryPolicy(backoff_s=0.0))
    rids = [engine.submit(ServeRequest(tokens=prompts[i].tolist(),
                                       max_new_tokens=budgets[i],
                                       sampling=sps[i])) for i in range(2)]
    outs = {o.request_id: o for o in engine.drain()}
    assert plan.fired == [{"layer": 2, "stage": "burst", "batch": None}]
    assert "burst_retry" in engine.events.kinds()
    for rid, base in zip(rids, bases):
        assert outs[rid].status == "ok"
        assert outs[rid].tokens == base, "retried burst diverged"


def test_burst_retries_exhausted_isolates_batch_engine_continues():
    """A burst that keeps failing past ``max_restarts`` poisons the
    decoding requests (status ``failed``, pages released) but the engine
    itself stays serviceable for later submissions."""
    model, params = _model_params("qwen1.5-4b", 8)
    prompts = _prompts(model, 3, 60)
    base_c = _baseline(model, params, prompts[2].tolist(), 6,
                       SamplingParams())
    plan = FaultPlan({(2, "burst"): 3})  # fires through every retry
    engine = Engine(model, params, max_slots=2, n_pages=8,
                    max_pages_per_request=2, burst_steps=4, fault_plan=plan,
                    retry=RetryPolicy(max_restarts=2, backoff_s=0.0))
    ra = engine.submit(ServeRequest(tokens=prompts[0].tolist(),
                                    max_new_tokens=10))
    rb = engine.submit(ServeRequest(tokens=prompts[1].tolist(),
                                    max_new_tokens=10))
    outs = {o.request_id: o for o in engine.drain()}
    assert outs[ra].status == outs[rb].status == "failed"
    assert engine.events.kinds().count("burst_retry") == 2
    assert "burst_poisoned" in engine.events.kinds()
    rc = engine.submit(ServeRequest(tokens=prompts[2].tolist(),
                                    max_new_tokens=6))
    outs = {o.request_id: o for o in engine.drain()}
    assert outs[rc].tokens == base_c, "engine must keep serving after poison"


def test_admit_and_ingest_faults_isolate_one_request():
    """A fault at the admit / ingest stage fails only the request being
    worked on — its pages are released and every other request finishes
    bitwise clean."""
    model, params = _model_params("qwen1.5-4b", 8)
    prompts = _prompts(model, 2, 30)
    base = _baseline(model, params, prompts[1].tolist(), 6,
                     SamplingParams())
    engine = Engine(model, params, max_slots=2, n_pages=4,
                    fault_plan=FaultPlan({(1, "admit"): 1}),
                    retry=RetryPolicy(backoff_s=0.0))
    ra = engine.submit(ServeRequest(tokens=prompts[0].tolist(),
                                    max_new_tokens=6))
    rb = engine.submit(ServeRequest(tokens=prompts[1].tolist(),
                                    max_new_tokens=6))
    outs = {o.request_id: o for o in engine.drain()}
    assert outs[ra].status == "failed" and outs[ra].tokens == []
    assert outs[rb].tokens == base
    assert "request_failed" in engine.events.kinds()

    long_p = _prompts(model, 1, 150)[0].tolist()
    engine = Engine(model, params, max_slots=2, n_pages=4,
                    max_pages_per_request=3, prefill_chunk=64,
                    fault_plan=FaultPlan({(2, "ingest"): 1}),
                    retry=RetryPolicy(backoff_s=0.0))
    ra = engine.submit(ServeRequest(tokens=long_p, max_new_tokens=6))
    rb = engine.submit(ServeRequest(tokens=prompts[1].tolist(),
                                    max_new_tokens=6))
    outs = {o.request_id: o for o in engine.drain()}
    assert outs[ra].status == "failed", "chunked ingest fault isolates A"
    assert outs[rb].tokens == base
    assert engine.pools.free_pages() == 4


def test_retire_fault_defers_one_round():
    """A retire-stage fault defers retirement (idempotent bookkeeping) by
    one round; the request still finishes with its exact stream."""
    model, params = _model_params("qwen1.5-4b", 8)
    p = _prompts(model, 1, 12)[0].tolist()
    base = _baseline(model, params, p, 4, SamplingParams())
    engine = Engine(model, params, max_slots=1, n_pages=2, burst_steps=4,
                    fault_plan=FaultPlan({(1, "retire"): 1}),
                    retry=RetryPolicy(backoff_s=0.0))
    rid = engine.submit(ServeRequest(tokens=p, max_new_tokens=4))
    assert engine.step() == []  # finished, but retirement deferred
    assert "retire_deferred" in engine.events.kinds()
    outs = {o.request_id: o for o in engine.drain()}
    assert outs[rid].tokens == base and outs[rid].status == "ok"


def test_watchdog_raises_on_wedged_engine():
    """A busy engine making zero progress emits a ``stuck_round`` event at
    ``watchdog_rounds`` idle rounds and raises ``EngineStuck`` at twice
    that, so ``drain()`` fails loudly instead of spinning forever."""
    model, params = _model_params("qwen1.5-4b", 8)
    p = _prompts(model, 1, 12)[0].tolist()
    engine = Engine(model, params, max_slots=1, n_pages=2,
                    watchdog_rounds=3)
    engine.submit(ServeRequest(tokens=p, max_new_tokens=8))
    engine._burst = lambda: None  # wedge: bursts never emit anything
    with pytest.raises(EngineStuck, match="wedged"):
        for _ in range(20):
            engine.step()
    assert "stuck_round" in engine.events.kinds()


# ------------------------------------------------------------ overload traces
@pytest.mark.parametrize("name,kv_bits",
                         [("qwen1.5-4b", 8), ("deepseek-v2-236b", 2)])
def test_oversubscribed_trace_all_terminal_and_bit_identical(name, kv_bits):
    """The acceptance scenario: a Poisson trace whose hot page demand is
    2x the pool (4 slots x 2 pages against 4 pages) must drain with every
    request terminal, zero allocator errors, preemptions actually
    exercised, and every stream — preempted ones included — bitwise its
    solo baseline."""
    model, params = _model_params(name, kv_bits)
    prompts = _prompts(model, 8, 60)
    budgets = [8, 12, 9, 10, 8, 11, 12, 9]
    sps = [SamplingParams() if i % 2 == 0
           else SamplingParams(temperature=1.3, seed=i) for i in range(8)]
    reqs = [ServeRequest(tokens=prompts[i].tolist(),
                         max_new_tokens=budgets[i], sampling=sps[i])
            for i in range(8)]
    engine = Engine(model, params, max_slots=4, n_pages=4,
                    max_pages_per_request=2, burst_steps=4)
    stats = run_trace(engine, poisson_trace(reqs, rate=2.0, seed=3))

    assert stats["n_requests"] == 8
    assert sum(stats["statuses"].values()) == 8
    assert stats["n_shed"] == stats["n_deadline"] == stats["n_failed"] == 0
    assert stats["n_preemptions"] >= 1, "2x oversubscription must preempt"
    assert stats["n_preempted_requests"] >= 1
    assert "preempted" in stats["per_status"]
    outs = stats["outputs"]
    for i, rid in enumerate(sorted(outs)):  # rids issued in arrival order
        assert outs[rid].finished_ok
        assert outs[rid].ttft > 0
        base = _baseline(model, params, prompts[i].tolist(), budgets[i],
                         sps[i])
        assert outs[rid].tokens == base, \
            f"request {i} diverged under oversubscription"
    engine.pools.assert_quiescent()
    assert engine.pools.free_pages() == 4


def test_trace_sheds_over_queue_depth():
    """``run_trace`` records backpressure-rejected submissions as
    synthetic ``shed`` outputs (negative ids) so every submission is
    accounted for."""
    model, params = _model_params("qwen1.5-4b", 8)
    p = _prompts(model, 1, 12)[0].tolist()
    reqs = [ServeRequest(tokens=p, max_new_tokens=4) for _ in range(3)]
    engine = Engine(model, params, max_slots=1, n_pages=2,
                    max_pages_per_request=1, queue_depth=1)
    # rate 50: all three arrive in round 0 -> one queued, two shed
    stats = run_trace(engine, poisson_trace(reqs, rate=50.0, seed=0))
    assert stats["n_requests"] == 3
    assert stats["n_shed"] == 2 == stats["statuses"]["shed"]
    shed = [o for o in stats["outputs"].values() if o.status == "shed"]
    assert all(o.request_id < 0 and o.tokens == [] for o in shed)
    done = [o for o in stats["outputs"].values() if o.finished_ok]
    assert len(done) == 1 and len(done[0].tokens) == 4
    assert stats["per_status"]["shed"]["n"] == 2


def test_run_trace_overload_counters_on_stub_engine():
    """The summary's overload counters / per-status percentiles, pinned on
    hand-built outputs (one of each terminal status + one shed)."""
    outs = [RequestOutput(request_id=0, tokens=[1, 2], prompt_len=2,
                          submit_time=0.0, finish_time=1.0,
                          first_token_time=0.5),
            RequestOutput(request_id=1, tokens=[3], prompt_len=2,
                          submit_time=0.0, finish_time=2.0,
                          first_token_time=0.5, status="preempted_2",
                          n_preempted=2),
            RequestOutput(request_id=2, tokens=[], prompt_len=2,
                          submit_time=0.0, finish_time=3.0,
                          status="deadline_exceeded"),
            RequestOutput(request_id=3, tokens=[4], prompt_len=2,
                          submit_time=0.0, finish_time=4.0, status="failed")]

    class Stub:
        n_preemptions = 2

        def __init__(self):
            self._pending = list(outs)
            self._n = 0

        def submit(self, req):
            self._n += 1
            if self._n == 3:
                raise _saturated()

        @property
        def busy(self):
            return bool(self._pending)

        def step(self):
            return [self._pending.pop(0)] if self._pending else []

    def _saturated():
        e = EngineSaturated("full")
        e.retry_after_s, e.occupancy, e.queued = 0.1, 1.0, 2
        return e

    reqs = [ServeRequest(tokens=[1, 2], max_new_tokens=2)] * 5
    stats = run_trace(Stub(), poisson_trace(reqs, rate=100.0, seed=0))
    assert stats["n_requests"] == 5
    assert stats["statuses"] == {"ok": 1, "preempted_2": 1, "shed": 1,
                                 "deadline_exceeded": 1, "failed": 1}
    assert stats["n_shed"] == 1 and stats["n_deadline"] == 1
    assert stats["n_failed"] == 1
    assert stats["n_preemptions"] == 2
    assert stats["n_preempted_requests"] == 1
    assert set(stats["per_status"]) == {"ok", "preempted", "shed",
                                        "deadline_exceeded", "failed"}
    assert stats["per_status"]["preempted"]["n"] == 1
    # service percentiles cover only the completed requests
    assert stats["p50_latency_s"] == pytest.approx(
        float(np.percentile([1.0, 2.0], 50)))
    assert _status_group("preempted_7") == "preempted"
    assert _status_group("ok") == "ok"


# ------------------------------------------------------------ page accounting
def test_page_accounting_guards():
    """Double free, trash-page release, duplicate ids and post-drain leaks
    all raise ``PageAccountingError`` instead of corrupting the stack."""
    model, _ = _model_params("qwen1.5-4b", 8)
    pools = PagedPools(model, 4)
    ids = np.asarray(pools.alloc(2))
    pools.release(ids)
    with pytest.raises(PageAccountingError, match="double free"):
        pools.release(ids)
    with pytest.raises(PageAccountingError, match="trash page"):
        pools.release(np.zeros(1, np.int32))
    ids2 = np.asarray(pools.alloc(2))
    with pytest.raises(PageAccountingError, match="duplicate"):
        pools.release(np.array([ids2[0], ids2[0]], np.int32))
    with pytest.raises(PageAccountingError, match="leak"):
        pools.assert_quiescent()
    pools.release(ids2)
    pools.assert_quiescent()


def test_engine_drain_detects_leaked_pages():
    """``drain()`` ends with a free-list audit: a page that never came
    back (here: leaked by reaching around the engine) fails the drain."""
    model, params = _model_params("qwen1.5-4b", 8)
    engine = Engine(model, params, max_slots=1, n_pages=4)
    engine.pools.alloc(1, context=" (leaked on purpose)")
    engine.submit(ServeRequest(tokens=[1, 2, 3], max_new_tokens=2))
    with pytest.raises(PageAccountingError, match="leak"):
        engine.drain()


def test_exhaustion_error_carries_occupancy_and_hint():
    """The allocator's sizing error exposes need/have/occupancy (and an
    optional retry-after hint) as attributes, with one shared sizing
    sentence between submit-time and runtime failures."""
    model, params = _model_params("qwen1.5-4b", 8)
    engine = Engine(model, params, max_slots=1, n_pages=2,
                    max_pages_per_request=8)
    with pytest.raises(Exception, match="can never fit") as ei:
        engine.submit(ServeRequest(tokens=[1] * 60, max_new_tokens=200))
    err = ei.value
    assert err.need == -(-260 // engine.page) and err.have == 2
    assert err.occupancy == pytest.approx(0.0)  # empty pool, still too small
    assert "occupancy" in str(err) and "need" in str(err)
    hinted = engine.pools.exhausted(4, retry_after_s=0.25)
    assert "Retry after ~0.25s" in str(hinted)
    assert hinted.retry_after_s == 0.25
