"""One run of one cell: the benchmark's entry point.

    python3 benchmarks/tpu/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

In order: load the cell's files by name (``spec``); refuse to run
without the chips the cell asks for; turn on JAX's persistent compilation
cache at a fixed path in the checkout (or ``JAX_COMPILATION_CACHE_DIR``);
hand the run to the cell's driver (``jobs/<job>.py``), which builds
weights and traffic from ``--seed``, warms up, measures and checks its
outputs against the plain reference; reduce a ``--trace 1`` run's
profiler trace to the per-layer metrics (``metrics/*.py``); print the
invariants and every compared number on earlier lines, the compared
numbers again as the last lines of standard error, and one JSON line
last on standard output.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import shutil
import sys
import time
from pathlib import Path

from benchmarks.tpu import spec, trace_reduce


class NoChip(SystemExit):
    pass


def log(*parts) -> None:
    print(*parts, flush=True)


def device_info(chips: int, require_chip: bool = True) -> dict:
    """Platform, kind and count of JAX's devices; exits non-zero without
    the chips the cell asks for."""
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    log(f"device: platform={info['platform']} kind={info['kind']} "
        f"count={info['count']}")
    if require_chip and info["platform"] != "tpu":
        raise NoChip(f"benchmark: no TPU (JAX found {info['platform']!r}); "
                     f"there is no fallback")
    if require_chip and len(devs) < chips:
        raise NoChip(f"benchmark: the cell needs {chips} chips, JAX found "
                     f"{len(devs)}")
    return info


def peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest device."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def enable_compile_cache(root: Path) -> str:
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts XLA compilations (cache loads included) and persistent-cache
    misses, so a run can show that none happened inside its window."""

    def __init__(self):
        import jax.monitoring as mon

        self.names: list[str] = []
        self.misses = 0

        def on_duration(event, duration, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.names.append(str(kw.get("fun_name", "?")))

        def on_event(event, **kw):
            if event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        mon.register_event_duration_secs_listener(on_duration)
        mon.register_event_listener(on_event)

    def snapshot(self) -> tuple[int, int]:
        return len(self.names), self.misses

    def since(self, a, b) -> str:
        """The compiles between two snapshots, for an invariant line."""
        names = self.names[a[0]:b[0]]
        return (f"{len(names)} (persistent-cache misses {b[1] - a[1]})"
                + (f": {sorted(set(names))}" if names else ""))


class Tracer:
    """A profiler trace of one short window, reduced to events."""

    def __init__(self, root: Path, cell: str, enabled: bool):
        self.enabled = enabled
        self.dir = root / ".bench_trace" / cell
        self.events = None

    @contextlib.contextmanager
    def window(self):
        import jax

        if not self.enabled:
            yield
            return
        self.start()
        try:
            with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
                yield
        finally:
            self.stop()

    def start(self):
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        jax.profiler.start_trace(str(self.dir))

    def stop(self, whole: bool = False):
        """End the trace and read it; ``whole`` makes the window the whole
        trace (for a trace stopped from another thread, where no window
        span can close)."""
        import jax

        jax.profiler.stop_trace()
        self.events = trace_reduce.read_xplane(self.dir)
        if whole:
            self.events.append(trace_reduce.whole_window(self.events))
        shutil.rmtree(self.dir, ignore_errors=True)


class Run:
    """What a driver gets: the cell, the run's arguments, the clock of its
    set-up, a compile counter, a tracer and a place for invariants."""

    def __init__(self, cell, args, t_start):
        self.cell = cell
        self.seed = args.seed
        self.seconds = float(args.seconds)
        self.trace = bool(args.trace)
        self.control = bool(getattr(args, "control", 0))
        self.t_start = t_start
        self.compiles = CompileCounter()
        self.tracer = Tracer(cell.root, cell.name, self.trace)
        self.invariants: dict = {}

    def setup_done(self, ahead: float = 0.0) -> float:
        """Set-up so far, plus ``ahead`` seconds of it still to come at a
        fixed length (a pre-roll of traffic before the window)."""
        s = time.perf_counter() - self.t_start + ahead
        log(f"setup_s {s!r}")
        return s


def result_line(cell, res: dict, device: dict, trace: bool) -> dict:
    metrics = {}
    wanted = cell.per_layer if trace else cell.end_to_end
    for m in wanted:
        v = res["metrics"].get(m["name"])
        if v is None:
            continue
        if not math.isfinite(v):
            raise ValueError(f"metric {m['name']} is {v}")
        metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    out = {"correct": bool(res["correct"]), "attempted": res["attempted"],
           "failed": res["failed"], "metrics": metrics, "device": device}
    if trace and res.get("breakdown"):
        out["breakdown"] = res["breakdown"]
    out["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                     for c in res["checks"]}
    return out


def per_layer_metrics(cell, readings: dict) -> dict:
    out = {}
    for m in cell.per_layer:
        v = spec.metric_reader(cell, m["name"])(m["name"], readings)
        if v is not None:
            out[m["name"]] = float(v)
        log(f"per-layer {m['name']} = {v!r} ({m['unit']})")
    return out


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    # the control of the correctness comparison (see PERF.md): the
    # reference at one precision step lower takes the program's place in
    # the comparison, so ``correct`` is the control's; not run by checks
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def run_cell(argv=None, *, root: Path = spec.ROOT, require_chip: bool = True,
             t_start: float | None = None, compile_cache: bool = True):
    """One run; returns (result line, the driver's whole result)."""
    t_start = time.perf_counter() if t_start is None else t_start
    args = parse(argv)
    cell = spec.load_cell(args.workload, root)
    device = device_info(cell.chips, require_chip)
    if compile_cache:
        log(f"compile cache: {enable_compile_cache(root)}")
    run = Run(cell, args, t_start)
    res = spec.job_module(cell).run(run)
    device = dict(device, memory_peak_bytes=res.get("memory_peak_bytes", 0))
    if run.trace:
        red = trace_reduce.reduce(run.tracer.events or [])
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        res["breakdown"] = {"device_ops": red["device_ops"],
                            "idle_gaps": red["idle_gaps"]}
        res["metrics"] = per_layer_metrics(
            cell, dict(res.get("readings", {}), trace=red, cell=cell,
                       device_kind=device["kind"]))
        log(f"trace: busy_s {red['busy_s']!r} window_s {red['window_s']!r}")
    for k, v in run.invariants.items():
        log(f"invariant {k}: {v}")
    for k, v in res.get("extra", {}).items():
        log(f"check {k}: {v!r}")
    line = result_line(cell, res, device, run.trace)
    for c in res["checks"]:
        print(f"check {c['name']}: {c['value']!r} limit {c['limit']!r} "
              f"({c['rule']})", file=sys.stderr, flush=True)
    return line, res


def main(argv=None, **kw) -> dict:
    line, _ = run_cell(argv, **kw)
    print(json.dumps(line), flush=True)
    return line
