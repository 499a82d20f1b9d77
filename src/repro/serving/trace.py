"""Arrival traces for the engine: Poisson arrivals in scheduling-round
units, plus a driver that submits on schedule, records per-request
latency, sheds rejected submissions, and summarizes the run — including
the overload counters (preemptions / shed / deadline-expired / failed),
per-status latency percentiles, and the queue wait and prompt ingest
time read from the program's tracer."""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from repro.runtime.tracing import TRACER, ingest_s_per_ktok, queue_waits
from repro.serving.engine import EngineSaturated, RequestOutput


@dataclasses.dataclass(frozen=True)
class TraceEvent:
    step: int       # scheduling round at which the request arrives
    request: object  # ServeRequest


def poisson_trace(requests, rate: float, seed: int = 0):
    """Poisson arrivals: exponential inter-arrival times at ``rate``
    requests per scheduling round (continuous arrival times floor to the
    round in which the engine first sees them)."""
    rng = np.random.default_rng(seed)
    t, events = 0.0, []
    for req in requests:
        t += rng.exponential(1.0 / rate)
        events.append(TraceEvent(step=int(t), request=req))
    return events


def _status_group(status: str) -> str:
    """Collapse ``preempted_N`` into one bucket; everything else is its
    own group (``ok`` / ``deadline_exceeded`` / ``shed`` / ``failed``)."""
    return "preempted" if status.startswith("preempted") else status


def run_trace(engine, trace):
    """Drive the engine through an arrival trace to completion.

    Submits each event at its scheduled round — a submission rejected by
    backpressure (:class:`EngineSaturated`) is recorded as a synthetic
    output with status ``shed`` (negative request id) rather than
    retried — then keeps stepping until everything drains.  Every
    submitted request ends in exactly one output with a definite status.

    The tracer (``runtime.tracing.TRACER``) records while the trace runs,
    so the split of time to first token is read where the engine keeps
    it: each submitted request's ``queued`` and ``ingest`` phases.

    Returns a summary dict: outputs (by request id), wall-clock p50/p99
    latency and time-to-first-token over the *completed* requests (status
    ``ok``/``preempted_*`` — shed and expired requests would skew the
    service-time percentiles), total emitted tokens, sustained tok/s,
    the p50/p90 of the submitted requests' queue wait (seconds queued,
    summed over re-queues) and their prompt ingest seconds per 1000
    prompt tokens (0 where nothing finished), the overload counters
    (``n_preemptions`` — preemption events, ``n_shed`` / ``n_deadline`` /
    ``n_failed`` — terminal statuses), a ``statuses`` histogram and
    ``per_status`` latency percentiles."""
    events = sorted(trace, key=lambda e: e.step)
    outputs, rids, i, round_ix, n_shed = [], [], 0, 0, 0
    t0 = time.time()
    with TRACER.recording():
        while i < len(events) or engine.busy:
            while i < len(events) and events[i].step <= round_ix:
                try:
                    rids.append(engine.submit(events[i].request))
                except EngineSaturated:
                    n_shed += 1
                    now = time.time()
                    outputs.append(RequestOutput(
                        request_id=-n_shed,
                        tokens=[],
                        prompt_len=len(events[i].request.tokens),
                        submit_time=now, finish_time=now, status="shed"))
                i += 1
            outputs.extend(engine.step())
            round_ix += 1
    wall = time.time() - t0
    record = TRACER.record()
    waits = queue_waits(record, rids) or [0.0]
    done = [o for o in outputs if o.finished_ok]
    lats = np.array([o.latency for o in done]) if done else np.zeros(1)
    ttfts = ([o.ttft for o in done if o.first_token_time > 0]
             or [o.ttft for o in outputs if o.first_token_time > 0])
    ttfts = np.array(ttfts) if ttfts else np.zeros(1)
    n_tok = sum(len(o.tokens) for o in outputs)
    statuses: dict = {}
    groups: dict = {}
    for o in outputs:
        statuses[o.status] = statuses.get(o.status, 0) + 1
        groups.setdefault(_status_group(o.status), []).append(o.latency)
    per_status = {
        g: {"n": len(ls),
            "p50_latency_s": float(np.percentile(ls, 50)),
            "p99_latency_s": float(np.percentile(ls, 99))}
        for g, ls in sorted(groups.items())}
    return {
        "outputs": {o.request_id: o for o in outputs},
        "n_requests": len(outputs),
        "n_tokens": n_tok,
        "wall_s": wall,
        "sustained_tok_s": n_tok / max(wall, 1e-9),
        "p50_latency_s": float(np.percentile(lats, 50)),
        "p99_latency_s": float(np.percentile(lats, 99)),
        "ttft_p50_s": float(np.percentile(ttfts, 50)),
        "ttft_p99_s": float(np.percentile(ttfts, 99)),
        "queue_wait_p50_s": float(np.percentile(waits, 50)),
        "queue_wait_p90_s": float(np.percentile(waits, 90)),
        "ingest_s_per_ktok": float(ingest_s_per_ktok(record, rids) or 0.0),
        "rounds": round_ix,
        "n_preemptions": int(getattr(engine, "n_preemptions", 0)),
        "n_preempted_requests": sum(1 for o in outputs if o.n_preempted),
        "n_shed": statuses.get("shed", 0),
        "n_deadline": statuses.get("deadline_exceeded", 0),
        "n_failed": statuses.get("failed", 0),
        "statuses": statuses,
        "per_status": per_status,
    }
