"""Reduce a profiler trace to device busy and idle time, kernel time by
the names the trace prints, and idle gaps by what the host was doing.

Events are plain dicts ``{"plane", "line", "name", "start_ns",
"dur_ns"}``: ``read_xplane`` makes them from the ``.xplane.pb`` that
``jax.profiler`` writes, keeping the device planes' op and module lines
and the host spans that the benchmark itself opens (names starting with
``bench:``); ``reduce`` works on that list alone, so a small recorded
trace tests it (``testdata/``).

* busy: the union of the intervals of the device's ops inside the
  window span ``bench:window``; idle share = 1 - busy / window.
* device ops: seconds per op name, the longest ten.
* idle gaps: each gap between busy intervals is charged to the innermost
  ``bench:`` host span that covers its middle (``host:unspanned`` where
  none does); seconds per span name, the longest ten.
"""
from __future__ import annotations

import bisect
import collections
import glob
import json
import re
from pathlib import Path

WINDOW = "bench:window"
SPAN_PREFIX = "bench:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# ops whose events span the ops of their bodies: busy, but not kernels
CONTAINERS = ("while", "conditional", "call")


def op_name(raw: str) -> str:
    """The trace prints an op as its HLO instruction (``%quant_matmul_
    pallas.82 = f32[...] custom-call(...)``): keep the name, without the
    instruction's number."""
    return re.sub(r"\.\d+$", "", raw.split(" = ", 1)[0].lstrip("%"))


def module_name(raw: str) -> str:
    """``jit_run(3189721660645829562)`` -> ``jit_run``."""
    return re.sub(r"\(\d+\)$", "", raw)


def read_xplane(directory) -> list[dict]:
    from jax.profiler import ProfileData

    paths = glob.glob(str(Path(directory) / "**" / "*.xplane.pb"),
                      recursive=True)
    if not paths:
        return []
    pd = ProfileData.from_file(paths[0])
    out = []
    for plane in pd.planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            keep_line = device and line.name in (OPS_LINE, MODULES_LINE)
            norm = op_name if line.name == OPS_LINE else module_name
            for ev in line.events:
                if not (keep_line or ev.name.startswith(SPAN_PREFIX)):
                    continue
                out.append({"plane": plane.name, "line": line.name,
                            "name": norm(ev.name) if keep_line else ev.name,
                            "start_ns": float(ev.start_ns),
                            "dur_ns": float(ev.duration_ns)})
    return out


def save(events: list[dict], path) -> None:
    import gzip

    with gzip.open(path, "wt") as f:
        json.dump(events, f)


def load(path) -> list[dict]:
    import gzip

    with gzip.open(path, "rt") as f:
        return json.load(f)


def whole_window(events: list[dict]) -> dict:
    """A window span from the first to the last device event."""
    dev = [e for e in events if e["plane"].startswith("/device:")]
    t0 = min(e["start_ns"] for e in dev)
    t1 = max(e["start_ns"] + e["dur_ns"] for e in dev)
    return {"plane": "/host:CPU", "line": "bench", "name": WINDOW,
            "start_ns": t0, "dur_ns": t1 - t0}


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def device_planes(events) -> list[str]:
    return sorted({e["plane"] for e in events
                   if e["plane"].startswith("/device:")
                   and e["line"] == OPS_LINE})


def reduce(events: list[dict]) -> dict:
    """busy_s, window_s, device_ops, idle_gaps, op_s (seconds per op
    name), module_s (seconds per module name) of the traced window;
    busy time is averaged over the device planes."""
    win = [e for e in events if e["name"] == WINDOW]
    planes = device_planes(events)
    if not win or not planes:
        return {"busy_s": 0.0, "window_s": 0.0, "device_ops": [],
                "idle_gaps": [], "op_s": {}, "module_s": {}, "module_n": {},
                "n_ops": 0}
    w0 = win[0]["start_ns"]
    w1 = w0 + win[0]["dur_ns"]

    def clip(e):
        s = max(e["start_ns"], w0)
        t = min(e["start_ns"] + e["dur_ns"], w1)
        return (s, t) if t > s else None

    op_s, module_s = collections.Counter(), collections.Counter()
    module_n = collections.Counter()
    busy, n_ops = 0.0, 0
    spans = sorted((e["start_ns"], e["start_ns"] + e["dur_ns"], e["name"])
                   for e in events
                   if e["name"].startswith(SPAN_PREFIX)
                   and e["name"] != WINDOW)
    starts = [s for s, _, _ in spans]

    def innermost(mid):
        # host spans nest: the innermost cover is the latest-starting one
        i = bisect.bisect_right(starts, mid) - 1
        while i >= 0:
            if spans[i][1] >= mid:
                return spans[i][2]
            i -= 1
        return "host:unspanned"

    gaps = collections.Counter()
    for plane in planes:
        ivs = []
        for e in events:
            if e["plane"] != plane:
                continue
            c = clip(e)
            if c is None:
                continue
            if e["line"] == OPS_LINE:
                ivs.append(c)
                if e["name"] not in CONTAINERS:
                    op_s[e["name"]] += (c[1] - c[0]) * 1e-9 / len(planes)
                    n_ops += 1
            elif e["line"] == MODULES_LINE:
                module_s[e["name"]] += (c[1] - c[0]) * 1e-9 / len(planes)
                module_n[e["name"]] += 1
        merged = _union(ivs)
        busy += sum(t - s for s, t in merged) * 1e-9 / len(planes)
        edges = [w0] + [x for s, t in merged for x in (s, t)] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b <= a:
                continue
            gaps[innermost(0.5 * (a + b))] += (b - a) * 1e-9 / len(planes)
    window_s = (w1 - w0) * 1e-9
    return {"busy_s": busy, "window_s": window_s,
            "device_ops": [[k, v] for k, v in op_s.most_common(10)],
            "idle_gaps": [[k, v] for k, v in gaps.most_common(10)],
            "op_s": dict(op_s), "module_s": dict(module_s),
            "module_n": dict(module_n), "n_ops": n_ops}


def time_of(red: dict, names) -> float:
    """Seconds of the device ops whose name contains one of ``names``."""
    return sum(v for k, v in red["op_s"].items()
               if any(n in k for n in names))


def module_time(red: dict, names) -> float:
    """Seconds of the device modules whose name contains one of
    ``names``."""
    return sum(v for k, v in red["module_s"].items()
               if any(n in k for n in names))


def module_count(red: dict, names) -> int:
    """Runs of the device modules whose name contains one of ``names``."""
    return sum(v for k, v in red["module_n"].items()
               if any(n in k for n in names))
