"""In-memory program tracer: host spans, counters, request lifecycles and
runner events on one timeline.

One process-wide :data:`TRACER`, off by default.  The serving engine and
the calibration pipeline open spans at their stage boundaries and count
their work there; ``runtime.fault.EventLog`` hands every structured event
to it as an instant.  Turn it on (``TRACER.enabled = True``, or for a
block ``with TRACER.recording(path):``), run, then read
``TRACER.record()`` or write it with ``TRACER.export(path)``.

* **Spans** (``with TRACER.span("engine.round"):``) carry a name under the
  one :data:`PREFIX`, a start and an end on the host clock
  (``time.time``, the clock of ``EventLog`` and of ``Engine._now``), the id
  of the enclosing span on the same thread, and their arguments (``rid``,
  ``layer``, ``batch``, ``chunk``).  Each is also a
  ``jax.profiler.TraceAnnotation`` of the same name with the same
  arguments, so a profiler trace shows the program's stages on the
  device trace's clock.  Spans time host work only: none reads a device
  value or waits for the device (that would change the dispatch
  timeline it measures); device time comes from the profiler trace.
* **Counters** are plain integers, bumped at the same boundaries.
* **Request lifecycles** are not nested, so they are kept as phases per
  request id on the engine's own clock: ``queued`` from submit to
  admission (again from a preemption to the re-admission), ``ingest``
  from admission to token 0 (to the armed replay after a re-admission),
  ``decode`` from token 0 to the finish.  The phases before token 0 add
  up to ``first_token_time - submit_time`` exactly.  A submit opens a
  fresh lifecycle, so a later engine's request replaces an earlier one's
  of the same id.

When off, a span costs one attribute check and returns a shared no-op
context: no record, no annotation object, no lock.  The record grows
while tracing is on; ``clear()`` empties it.
"""
from __future__ import annotations

import contextlib
import itertools
import json
import threading
import time

import jax

PREFIX = "repro."


class _NoSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NO_SPAN = _NoSpan()


class _Span:
    __slots__ = ("tracer", "name", "args", "id", "parent", "start", "ann")

    def __init__(self, tracer, name, args):
        self.tracer, self.name, self.args = tracer, name, args

    def __enter__(self):
        tr = self.tracer
        stack = tr._stack()
        self.parent = stack[-1].id if stack else None
        self.id = next(tr._ids)
        stack.append(self)
        self.ann = jax.profiler.TraceAnnotation(self.name, **self.args)
        self.ann.__enter__()
        self.start = time.time()
        return self

    def __exit__(self, *exc):
        end = time.time()
        self.ann.__exit__(*exc)
        tr = self.tracer
        tr._stack().pop()
        with tr._lock:
            tr._spans.append({"id": self.id, "name": self.name,
                              "start": self.start, "end": end,
                              "parent": self.parent, **self.args})
        return False


class Tracer:
    """Spans, counters, request lifecycles and events of one process."""

    def __init__(self):
        self.enabled = False
        self._lock = threading.Lock()
        self._local = threading.local()
        self._ids = itertools.count()
        self.clear()

    def clear(self) -> None:
        with self._lock:
            self._spans: list[dict] = []
            self._counters: dict[str, int] = {}
            self._requests: dict[int, dict] = {}
            self._events: list[dict] = []

    def _stack(self) -> list:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    # ------------------------------------------------------------ recording
    def span(self, name: str, **args):
        """Context manager timing one stage, ``PREFIX + name``."""
        if not self.enabled:
            return _NO_SPAN
        return _Span(self, PREFIX + name, args)

    def count(self, name: str, n: int = 1) -> None:
        if not self.enabled:
            return
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + int(n)

    def phase(self, rid: int, phase: str, t: float, **info) -> None:
        """Close request ``rid``'s open phase at ``t`` and open ``phase``
        there; ``info`` (the prompt length, given at submit) starts a
        fresh lifecycle."""
        if not self.enabled:
            return
        with self._lock:
            rec = self._requests.get(rid)
            if rec is None or info:
                rec = self._requests[rid] = {"phases": [], **info}
            ph = rec["phases"]
            if ph and ph[-1][2] is None:
                ph[-1][2] = t
            ph.append([phase, t, None])

    def finish(self, rid: int, t: float, status: str) -> None:
        """Close request ``rid``'s open phase at its finish time."""
        if not self.enabled:
            return
        with self._lock:
            rec = self._requests.get(rid)
            if rec is None:
                return
            ph = rec["phases"]
            if ph and ph[-1][2] is None:
                ph[-1][2] = t
            rec["status"] = status

    def instant(self, event: dict) -> None:
        """One ``EventLog`` event (``kind``, ``time`` and its fields)."""
        if not self.enabled:
            return
        with self._lock:
            self._events.append(dict(event))

    # -------------------------------------------------------------- reading
    def record(self) -> dict:
        """A copy of everything recorded: ``spans`` (closed, in the order
        they closed), ``counters``, ``requests`` (one lifecycle per
        request id: ``rid``, ``prompt``, ``phases`` as ``[phase, start,
        end]``, ``status`` once finished) and ``events``."""
        with self._lock:
            return {"prefix": PREFIX,
                    "spans": [dict(s) for s in self._spans],
                    "counters": dict(self._counters),
                    "requests": [{"rid": rid, **r,
                                  "phases": [list(p) for p in r["phases"]]}
                                 for rid, r in self._requests.items()],
                    "events": [dict(e) for e in self._events]}

    def export(self, path) -> None:
        """Write ``record()`` as one JSON file."""
        with open(path, "w") as f:
            json.dump(self.record(), f, default=str)

    @contextlib.contextmanager
    def recording(self, path=None):
        """Keep tracing on while the block runs, then put it back as it
        was and, given a ``path``, write the record there."""
        was, self.enabled = self.enabled, True
        try:
            yield self
        finally:
            self.enabled = was
            if path is not None:
                self.export(path)


TRACER = Tracer()


# ---------------------------------------------------- readings of a record
def _phase_s(rec: dict, phase: str) -> float:
    return sum(t1 - t0 for p, t0, t1 in rec["phases"]
               if p == phase and t1 is not None)


def _finished(record: dict, rids=None) -> list:
    """Finished lifecycles seen from their submit on, of ``rids`` only
    where given."""
    keep = None if rids is None else set(rids)
    return [r for r in record["requests"]
            if (keep is None or r["rid"] in keep) and "status" in r
            and r["phases"] and r["phases"][0][0] == "queued"]


def queue_waits(record: dict, rids=None) -> list[float]:
    """Seconds each finished request (of ``rids``, else every one with a
    whole lifecycle) spent in ``queued``, summed over its phases."""
    return [_phase_s(r, "queued") for r in _finished(record, rids)]


def ingest_s_per_ktok(record: dict, rids=None):
    """``ingest`` seconds of the finished requests (of ``rids``), summed,
    over their prompt tokens / 1000; None without any."""
    done = [r for r in _finished(record, rids) if r.get("prompt")]
    toks = sum(r["prompt"] for r in done)
    if not toks:
        return None
    return sum(_phase_s(r, "ingest") for r in done) / (toks / 1000.0)


def span_s(record: dict, *names) -> float:
    """Host seconds of the spans named ``PREFIX + name``, summed."""
    want = {record["prefix"] + n for n in names}
    return sum(s["end"] - s["start"] for s in record["spans"]
               if s["name"] in want)


def prep_s_per_layer(record: dict):
    """Host seconds of a pass's preparation (``pipeline.prepare`` and
    ``pipeline.prewarm``) per layer the pipeline quantized; None without
    a layer."""
    layers = record["counters"].get("pipeline.layers", 0)
    if not layers:
        return None
    return span_s(record, "pipeline.prepare", "pipeline.prewarm") / layers
