"""The program's own host spans (``repro.*``, ``runtime.tracing``) in a
profiler trace of the chat cell.

``testdata/serve_program_trace.json.gz`` is 0.25 s of a traced chat run
with the program's tracer on (one TPU v5 lite chip, seed 2147483823):
the end of one decode burst, the round boundary and the start of the
next burst, with the benchmark's ``bench:*`` spans and the program's
``repro.engine.*`` spans on the device trace's clock.
"""
from pathlib import Path

from benchmarks.tpu import trace_reduce

DATA = Path(__file__).with_name("testdata") / "serve_program_trace.json.gz"
PROGRAM = "repro."


def _events():
    return trace_reduce.load(DATA)


def _host(events, name):
    return [(e["start_ns"], e["start_ns"] + e["dur_ns"]) for e in events
            if e["name"] == name]


def test_program_spans_leave_the_reduction_unchanged():
    """Host events of the program change none of the numbers the
    reduction gives: busy, window, op, module and idle-gap seconds."""
    events = _events()
    assert any(e["name"].startswith(PROGRAM) for e in events)
    bench_only = [e for e in events if not e["name"].startswith(PROGRAM)]
    assert trace_reduce.reduce(events) == trace_reduce.reduce(bench_only)


def test_program_spans_nest_inside_the_benchmark_spans():
    """``Probe`` wraps the engine's methods from outside, so each program
    span lies inside the benchmark span around the same call."""
    events = _events()
    outer = {"repro.engine.round": "bench:step",
             "repro.engine.admit": "bench:step",
             "repro.engine.retire": "bench:step",
             "repro.engine.burst.dispatch": "bench:burst",
             "repro.engine.burst.readback": "bench:burst"}
    for inner, name in outer.items():
        spans = _host(events, inner)
        assert spans, inner
        for s, t in spans:
            assert any(a <= s and t <= b for a, b in _host(events, name)), \
                (inner, name)


def test_burst_idle_lies_in_dispatch_or_readback():
    """Every device idle gap inside a decode burst falls in the program's
    ``burst.dispatch`` or ``burst.readback`` span: the benchmark's one
    span around the burst now splits into the two."""
    events = _events()
    win = next(e for e in events if e["name"] == trace_reduce.WINDOW)
    w0, w1 = win["start_ns"], win["start_ns"] + win["dur_ns"]
    (plane,) = trace_reduce.device_planes(events)
    busy = trace_reduce._union(
        (max(e["start_ns"], w0), min(e["start_ns"] + e["dur_ns"], w1))
        for e in events if e["plane"] == plane
        and e["line"] == trace_reduce.OPS_LINE)
    edges = [w0] + [x for s, t in busy for x in (s, t)] + [w1]
    bursts = _host(events, "bench:burst")
    parts = (_host(events, "repro.engine.burst.dispatch")
             + _host(events, "repro.engine.burst.readback"))
    in_burst = 0.0
    for a, b in zip(edges[::2], edges[1::2]):
        mid = 0.5 * (a + b)
        if b <= a or not any(s <= mid <= t for s, t in bursts):
            continue
        in_burst += b - a
        assert any(s <= mid <= t for s, t in parts), mid
    assert in_burst > 0
