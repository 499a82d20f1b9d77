"""The program's tracer (``runtime.tracing``) and its spans inside the
serving engine and the calibration pipeline.

Contracts pinned here: spans nest per thread with their parents and
request ids; tracing off records nothing at all; a request's ``queued``
and ``ingest`` phases before token 0 add up to its time to first token
exactly, on the engine's own clock, through chunked prefill and through
a preemption (whose re-queue opens ``queued`` again); the pipeline's
stage spans cover a pass and leave its outputs and compile counts bit
for bit what they are with tracing off; and the launchers' ``--trace-out``
writes the whole record as one JSON file.
"""
import dataclasses
import functools
import itertools
import json
import threading

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import RSQConfig, RSQPipeline
from repro.data.synthetic import SyntheticCorpus
from repro.models import build_model
from repro.runtime import tracing
from repro.runtime.fault import EventLog
from repro.runtime.tracing import TRACER
from repro.serving import Engine, ServeRequest


@pytest.fixture(autouse=True)
def fresh_tracer():
    """Every test starts from an empty record with tracing off, and
    leaves it so."""
    TRACER.enabled = False
    TRACER.clear()
    yield
    TRACER.enabled = False
    TRACER.clear()


@functools.lru_cache(maxsize=None)
def _engine_model():
    cfg = dataclasses.replace(get_config("qwen1.5-4b").reduced(),
                              dtype="float32", kv_bits=8)
    model = build_model(cfg)
    return model, jax.jit(model.init)(jax.random.key(0))


def _prompts(model, n, t):
    corpus = SyntheticCorpus(vocab_size=model.cfg.vocab_size, seed=0)
    return [p.tolist() for p in corpus.sample(jax.random.key(2), n, t)]


def _fake_clock(engine):
    """Whole seconds, one per read: every difference of two readings is
    exact in floating point."""
    ticks = itertools.count(1)
    engine._now = lambda: float(next(ticks))


def _pool_trade(model, params):
    """A 150-token prompt ingested in 64-token chunks beside a 60-token
    one, on a pool too small for both: the chunked request is preempted
    and re-ingested."""
    pa = _prompts(model, 1, 150)[0]
    pb = _prompts(model, 2, 60)[1]
    engine = Engine(model, params, max_slots=2, n_pages=4,
                    max_pages_per_request=3, burst_steps=4,
                    prefill_chunk=64)
    _fake_clock(engine)
    ra = engine.submit(ServeRequest(tokens=pa, max_new_tokens=8))
    rb = engine.submit(ServeRequest(tokens=pb, max_new_tokens=12))
    return engine, ra, rb


# ------------------------------------------------------------------ tracer
def test_spans_nest_with_parents_and_rids():
    TRACER.enabled = True
    with TRACER.span("outer", rid=7):
        with TRACER.span("inner", rid=7, chunk=1):
            pass
        with TRACER.span("sibling"):
            pass
    seen = {}

    def other_thread():
        with TRACER.span("elsewhere"):
            seen["ok"] = True

    t = threading.Thread(target=other_thread)
    with TRACER.span("held"):
        t.start()
        t.join()
    rec = TRACER.record()
    by = {s["name"]: s for s in rec["spans"]}
    assert set(by) == {"repro.outer", "repro.inner", "repro.sibling",
                       "repro.elsewhere", "repro.held"}
    assert by["repro.outer"]["parent"] is None
    assert by["repro.inner"]["parent"] == by["repro.outer"]["id"]
    assert by["repro.sibling"]["parent"] == by["repro.outer"]["id"]
    assert (by["repro.inner"]["rid"], by["repro.inner"]["chunk"]) == (7, 1)
    assert "rid" not in by["repro.sibling"]
    # another thread's span has its own stack: no parent across threads
    assert by["repro.elsewhere"]["parent"] is None and seen["ok"]
    o, i = by["repro.outer"], by["repro.inner"]
    assert o["start"] <= i["start"] <= i["end"] <= o["end"]
    assert rec["prefix"] == tracing.PREFIX == "repro."


def test_tracing_off_records_nothing():
    """Off (the default), a span is one shared no-op object, and a whole
    engine drain with chunked prefill, a preemption and its event leaves
    the record empty."""
    assert TRACER.span("x") is TRACER.span("y", rid=1)
    model, params = _engine_model()
    engine, ra, _ = _pool_trade(model, params)
    outs = {o.request_id: o for o in engine.drain()}
    assert outs[ra].n_preempted >= 1 and "preempt" in engine.events.kinds()
    assert TRACER.record() == {"prefix": "repro.", "spans": [],
                               "counters": {}, "requests": [], "events": []}


def test_counters_and_spans_lose_nothing_across_threads():
    """More threads than cores count and open nested spans at a short
    switch interval: no count is lost, and each span's parent is its own
    thread's enclosing span."""
    import os
    import sys

    n_threads, n = 2 * (os.cpu_count() or 2) + 2, 300
    TRACER.enabled = True

    def work(k):
        for _ in range(n):
            with TRACER.span("outer", rid=k):
                TRACER.count("hits")
                with TRACER.span("inner", rid=k):
                    TRACER.count("hits")

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(k,))
                   for k in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    rec = TRACER.record()
    assert rec["counters"]["hits"] == 2 * n * n_threads
    assert len(rec["spans"]) == 2 * n * n_threads
    by_id = {s["id"]: s for s in rec["spans"]}
    assert len(by_id) == len(rec["spans"])
    for s in rec["spans"]:
        if s["name"] == "repro.inner":
            parent = by_id[s["parent"]]
            assert parent["name"] == "repro.outer"
            assert parent["rid"] == s["rid"]
        else:
            assert s["parent"] is None


def test_events_land_as_instants_on_the_span_clock():
    log = EventLog(verbose=False)
    log.emit("before", n=0)
    TRACER.enabled = True
    with TRACER.span("work"):
        log.emit("restart", step=3)
    rec = TRACER.record()
    assert [e["kind"] for e in rec["events"]] == ["restart"]
    assert log.kinds() == ["before", "restart"]
    span = rec["spans"][0]
    assert span["start"] <= rec["events"][0]["time"] <= span["end"]


# ------------------------------------------------------------------ engine
def test_ttft_splits_into_queued_and_ingest_exactly():
    """Per request, on the engine's clock: the phases before token 0 are
    ``queued`` then ``ingest`` and add up to ``first_token_time -
    submit_time`` exactly; the last phase ends at the finish.  The
    preempted request's re-queue opens ``queued`` again."""
    TRACER.enabled = True
    model, params = _engine_model()
    engine, ra, rb = _pool_trade(model, params)
    outs = {o.request_id: o for o in engine.drain()}
    assert outs[ra].n_preempted >= 1
    reqs = {r["rid"]: r for r in TRACER.record()["requests"]}
    for rid, out in outs.items():
        phases = reqs[rid]["phases"]
        names = [p for p, _, _ in phases]
        first = names.index("decode")
        assert names[:first] == ["queued", "ingest"]
        before = sum(t1 - t0 for _, t0, t1 in phases[:first])
        assert before == out.first_token_time - out.submit_time
        assert phases[0][1] == out.submit_time
        assert phases[first][1] == out.first_token_time
        assert phases[-1][2] == out.finish_time
        assert all(a[2] == b[1] for a, b in zip(phases, phases[1:]))
        assert reqs[rid]["status"] == out.status
    assert reqs[ra]["prompt"] == 150 and reqs[rb]["prompt"] == 60
    names_a = [p for p, _, _ in reqs[ra]["phases"]]
    assert names_a.count("queued") == 1 + outs[ra].n_preempted
    assert names_a[:6] == ["queued", "ingest", "decode",
                           "queued", "ingest", "decode"]
    rec = TRACER.record()
    waits = tracing.queue_waits(rec, [ra, rb])
    assert len(waits) == 2 and waits[0] > 0
    assert tracing.ingest_s_per_ktok(rec, [ra]) > 0
    assert tracing.ingest_s_per_ktok(rec, [999]) is None


def test_engine_spans_and_counters():
    """The round's phases nest under ``engine.round``; every prompt chunk
    is one ``engine.ingest`` span with its request id and chunk index;
    the counters add up to the work done."""
    TRACER.enabled = True
    model, params = _engine_model()
    prompts = [_prompts(model, 3, 150)[0]] + _prompts(model, 3, 40)[1:]
    engine = Engine(model, params, max_slots=2, n_pages=16,
                    max_pages_per_request=4, burst_steps=4,
                    prefill_chunk=64)
    rids = [engine.submit(ServeRequest(tokens=p, max_new_tokens=5))
            for p in prompts]
    rounds = 0
    while engine.busy:
        engine.step()
        rounds += 1
    rec = TRACER.record()
    spans = rec["spans"]
    by_id = {s["id"]: s for s in spans}
    names = {s["name"] for s in spans}
    assert names == {"repro.engine." + n for n in (
        "round", "admit", "prefill", "ingest", "burst.dispatch",
        "burst.readback", "retire")}
    for s in spans:
        if s["name"] != "repro.engine.round":
            parent = by_id[s["parent"]]["name"]
            want = ("repro.engine.admit" if s["name"].endswith("prefill")
                    else "repro.engine.round")
            assert parent == want, (s["name"], parent)
    ingest = [(s["rid"], s["chunk"]) for s in spans
              if s["name"] == "repro.engine.ingest"]
    assert ingest == [(rids[0], 0), (rids[0], 1), (rids[0], 2)]
    prefill = sorted(s["rid"] for s in spans
                     if s["name"] == "repro.engine.prefill")
    assert prefill == rids[1:]
    c = rec["counters"]
    assert c["engine.rounds"] == rounds
    assert c["engine.prompt_tokens"] == sum(len(p) for p in prompts)
    assert c["engine.tokens_emitted"] == 3 * 5
    # three requests, two slots: the third waits for a slot
    assert c["engine.admit_blocked_slots"] >= 1
    assert "engine.preemptions" not in c


def test_decode_work_list_counters():
    """The engine counts the paged decode's work items that compute and
    the items its grid walks: some of each, never more live than walked,
    and fewer than the dense slots x table-width grid would walk."""
    TRACER.enabled = True
    model, params = _engine_model()
    engine = Engine(model, params, max_slots=3, n_pages=6,
                    max_pages_per_request=4, burst_steps=4)
    for p in _prompts(model, 3, 40):
        engine.submit(ServeRequest(tokens=p, max_new_tokens=6))
    engine.drain()
    c = TRACER.record()["counters"]
    live, grid = c["engine.decode_tiles_live"], c["engine.decode_tiles_grid"]
    assert 0 < live <= grid
    # 3 slots x 4 table entries = 12 a step; the list is 6 + 1 + 3 = 10
    assert grid % (4 * 10) == 0
    # every request decodes 5 steps after its first token, all on its
    # first page: 3 x 5 one-tile items
    assert live == 3 * 5


def test_preemptions_counted_beside_the_engine_count():
    TRACER.enabled = True
    model, params = _engine_model()
    engine, _, _ = _pool_trade(model, params)
    engine.drain()
    c = TRACER.record()["counters"]
    assert c["engine.preemptions"] == engine.n_preemptions >= 1
    assert c["engine.admit_blocked_pages"] >= 1


# ---------------------------------------------------------------- pipeline
@pytest.fixture(scope="module")
def toy4():
    cfg = dataclasses.replace(
        get_config("llama3-8b").reduced(), dtype="float32",
        n_layers=4, d_model=64, vocab_size=256)
    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.key(0))
    calib = jax.random.randint(jax.random.key(1), (8, 32), 0, cfg.vocab_size)
    return model, params, calib


def _quantize(toy4, scheduler):
    model, params, calib = toy4
    pipe = RSQPipeline(model, RSQConfig(bits=4, rotate=True,
                                        importance="attn_con",
                                        scheduler=scheduler))
    q, report = pipe.run(params, calib, batch_size=4)
    return q, report, pipe


@pytest.mark.parametrize("scheduler", ["overlapped", "sequential"])
def test_pipeline_bit_identical_with_tracing_on(toy4, scheduler):
    q_off, rep_off, pipe_off = _quantize(toy4, scheduler)
    assert TRACER.record()["spans"] == []
    with TRACER.recording():
        q_on, rep_on, pipe_on = _quantize(toy4, scheduler)
    for a, b in zip(jax.tree.leaves(q_off), jax.tree.leaves(q_on)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert pipe_on.trace_counts == pipe_off.trace_counts
    assert rep_on["trace_counts"] == rep_off["trace_counts"]
    for tag, rep in rep_off["layers"].items():
        assert rep["weights"] == rep_on["layers"][tag]["weights"]
    assert not TRACER.enabled


def test_pipeline_stage_spans_cover_the_pass(toy4):
    """Every stage of a pass is a span under ``pipeline.pass``, with its
    layer (and batch); together they leave almost none of the pass
    outside a closed span.  ``prep_s_per_layer`` reads them."""
    with TRACER.recording():
        _quantize(toy4, "overlapped")
    rec = TRACER.record()
    spans = rec["spans"]
    (pas,) = [s for s in spans if s["name"] == "repro.pipeline.pass"]
    top = sorted((s for s in spans if s["parent"] == pas["id"]),
                 key=lambda s: s["start"])
    stages = {s["name"].split(".")[-1] for s in top}
    assert stages == {"prepare", "prewarm", "layer_begin", "layer_capture",
                      "layer_solve", "layer_apply", "layer_finalize",
                      "writeback"}
    caps = [(s["layer"], s["batch"]) for s in spans
            if s["name"] == "repro.pipeline.layer_capture"]
    assert caps == [(f"layer{i}", b) for i in range(4) for b in range(2)]
    sync = [s for s in spans if s["name"] == "repro.pipeline.layer_sync"]
    assert len(sync) == 4
    assert all(by["name"] == "repro.pipeline.layer_finalize"
               for by in ({x["id"]: x for x in spans}[s["parent"]]
                          for s in sync))
    covered = sum(s["end"] - s["start"] for s in top)
    assert covered >= 0.9 * (pas["end"] - pas["start"])
    assert rec["counters"]["pipeline.layers"] == 4
    prep = tracing.span_s(rec, "pipeline.prepare", "pipeline.prewarm")
    assert tracing.prep_s_per_layer(rec) == pytest.approx(prep / 4)
    assert prep > 0


# --------------------------------------------------------------- launchers
def test_serve_trace_out_writes_one_json(tmp_path, capsys):
    from repro.launch import serve

    path = tmp_path / "serve_trace.json"
    serve.main(["--arch", "llama3-8b-smoke", "--mode", "engine",
                "--kv-bits", "8", "--batch", "3", "--prompt-len", "12",
                "--gen", "3", "--max-slots", "2", "--arrival-rate", "2",
                "--trace-out", str(path)])
    assert not TRACER.enabled
    rec = json.loads(path.read_text())
    assert set(rec) == {"prefix", "spans", "counters", "requests", "events"}
    assert rec["counters"]["engine.tokens_emitted"] == 9
    assert len(rec["requests"]) == 3
    assert any(s["name"] == "repro.engine.round" for s in rec["spans"])
    assert tracing.queue_waits(rec) and tracing.ingest_s_per_ktok(rec) > 0
    assert "ttft split: queue wait p50=" in capsys.readouterr().out


def test_quantize_trace_out_writes_one_json(tmp_path):
    from repro.launch import quantize

    path = tmp_path / "quant_trace.json"
    out = quantize.main(["--arch", "llama3-8b-smoke", "--n-layers", "1",
                         "--bits", "4", "--n-calib", "4", "--calib-seq",
                         "16", "--batch", "2", "--trace-out", str(path)])
    assert not TRACER.enabled
    rec = json.loads(path.read_text())
    assert rec["counters"]["pipeline.layers"] == 1
    assert out["summary"]["prep_s_per_layer"] == pytest.approx(
        tracing.prep_s_per_layer(rec))
    assert {s["name"] for s in rec["spans"]} >= {
        "repro.pipeline.pass", "repro.pipeline.prepare",
        "repro.pipeline.layer_solve", "repro.pipeline.writeback"}
