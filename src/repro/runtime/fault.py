"""Fault-tolerant step running: checkpoint/restart, failure injection,
retry policy, straggler watchdog.

On a real cluster the failure signal is a lost host / NCCL-equivalent
timeout; here failures are injected as exceptions so the recovery path
(restore latest checkpoint -> reseek the data iterator -> continue) is
exercised end-to-end in tests.  Data is host-local + deterministic in
(seed, step) (see data/loader.py), so recovery needs no data service.

Shared policy objects (used by both the training ``StepRunner`` and the
quantize-path ``core.resume.QuantizeRunner``):

  * :class:`RetryPolicy` — which exception types are recoverable, how many
    restarts are allowed, and the exponential backoff between them.
  * :class:`FaultPlan` — stage-level failure injection for the quantize
    pipeline: arm a failure at any ``(layer, stage)`` point with
    ``stage in {"capture", "solve", "apply", "pack"}`` (optionally down to
    a batch index for the per-batch stages).  The schedulers
    (``core/scheduler``) call ``engine.stage_point`` at every stage
    dispatch point and the pipeline routes that into ``FaultPlan.check``.
  * :class:`EventLog` — structured events (restarts, stragglers,
    checkpoints) instead of bare prints: each event is a dict with a
    ``kind`` plus payload fields, collected on the runner and optionally
    forwarded to an ``on_event`` callback (a metrics hook on a real pod).
    While ``runtime.tracing.TRACER`` is on, each event also lands in its
    record as an instant, on the clock of its spans.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Optional

import jax

from repro.checkpoint import CheckpointManager
from repro.runtime.tracing import TRACER


class InjectedFailure(RuntimeError):
    pass


STAGES = ("capture", "solve", "apply", "pack")

# Serving-engine stage points (repro.serving.engine): one scheduling round
# visits admit -> ingest -> burst -> retire, and the engine calls
# ``FaultPlan.check(round, stage)`` at each — same plan object, same CLI
# spec format (``ROUND:STAGE[:COUNT]`` via ``--fail-at-round``), so the
# quantize-side and serve-side fault matrices share one vocabulary.
SERVE_STAGES = ("admit", "ingest", "burst", "retire")


@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    """Which failures are survivable, and how to pace the restarts.

    ``recoverable`` is the exception-type tuple a runner treats as
    transient (preemption, injected failure, flaky collective); anything
    else propagates immediately.  Restart ``n`` (1-based) sleeps
    ``backoff_s * backoff_factor**(n-1)`` seconds, capped at
    ``max_backoff_s`` — exponential backoff so a persistently failing
    stage doesn't hot-loop the stack."""

    recoverable: tuple = (InjectedFailure,)
    max_restarts: int = 3
    backoff_s: float = 0.05
    backoff_factor: float = 2.0
    max_backoff_s: float = 30.0

    def is_recoverable(self, e: BaseException) -> bool:
        return isinstance(e, tuple(self.recoverable))

    def backoff(self, attempt: int) -> float:
        """Seconds to sleep before restart ``attempt`` (1-based)."""
        if self.backoff_s <= 0:
            return 0.0
        return min(self.backoff_s * self.backoff_factor ** max(attempt - 1, 0),
                   self.max_backoff_s)


class EventLog:
    """Structured runner events: appended dicts, optional sink callback."""

    def __init__(self, on_event: Optional[Callable[[dict], None]] = None,
                 verbose: bool = True):
        self.events: list[dict] = []
        self.on_event = on_event
        self.verbose = verbose

    def emit(self, kind: str, **fields) -> dict:
        ev = {"kind": kind, "time": time.time(), **fields}
        self.events.append(ev)
        TRACER.instant(ev)
        if self.on_event is not None:
            self.on_event(ev)
        if self.verbose:
            body = " ".join(f"{k}={v}" for k, v in fields.items())
            print(f"[{kind}] {body}", flush=True)
        return ev

    def __iter__(self):
        return iter(self.events)

    def kinds(self) -> list[str]:
        return [e["kind"] for e in self.events]


@dataclasses.dataclass
class FaultPlan:
    """Stage-level failure injection for the quantize pipeline.

    ``fail_at`` maps an injection point to how many times it should fire:
    keys are ``(layer, stage)`` or — for the per-batch ``capture`` /
    ``apply`` stages — ``(layer, stage, batch)``.  ``check`` is called by
    ``RSQPipeline.stage_point`` right before the stage's device work is
    dispatched; an armed point raises ``exc`` (default
    :class:`InjectedFailure`) and records the firing in ``fired``.

    The serving engine reuses the same plan with ``layer`` meaning the
    scheduling *round* and ``stage`` one of :data:`SERVE_STAGES` — every
    check happens host-side before the stage's device dispatch, so state
    (pools, slot rows) is untouched when an injected failure fires and a
    retry re-runs the stage from identical inputs."""

    fail_at: dict
    exc: type = InjectedFailure
    fired: list = dataclasses.field(default_factory=list)

    def __post_init__(self):
        self.fail_at = dict(self.fail_at)
        for key in self.fail_at:
            stage = key[1]
            if stage not in STAGES + SERVE_STAGES:
                raise ValueError(f"unknown stage {stage!r}; one of "
                                 f"{STAGES + SERVE_STAGES}")

    def check(self, layer: int, stage: str, batch: Optional[int] = None
              ) -> None:
        keys = [(layer, stage)]
        if batch is not None:
            keys.insert(0, (layer, stage, batch))
        for key in keys:
            if self.fail_at.get(key, 0) > 0:
                self.fail_at[key] -= 1
                self.fired.append(
                    {"layer": layer, "stage": stage, "batch": batch})
                raise self.exc(
                    f"injected failure at layer {layer} stage {stage}"
                    + (f" batch {batch}" if batch is not None else ""))

    @classmethod
    def parse(cls, specs: list[str], **kw) -> "FaultPlan":
        """Build a plan from CLI specs ``LAYER:STAGE[:COUNT]``."""
        fail_at: dict = {}
        for s in specs:
            parts = s.split(":")
            if len(parts) not in (2, 3):
                raise ValueError(f"--fail-at wants LAYER:STAGE[:COUNT], "
                                 f"got {s!r}")
            layer, stage = int(parts[0]), parts[1]
            count = int(parts[2]) if len(parts) == 3 else 1
            fail_at[(layer, stage)] = count
        return cls(fail_at, **kw)


@dataclasses.dataclass
class StepRunner:
    """Wraps a jitted train step with checkpointing + crash recovery.

    Recovery policy is configurable: ``recoverable`` names the exception
    types that trigger a restore-latest-checkpoint restart (anything else
    propagates), with exponential backoff between restarts — the same
    policy object the quantize-path ``QuantizeRunner`` reuses.  The
    straggler watchdog emits a structured ``straggler`` event (see
    :class:`EventLog`) instead of a bare print."""

    step_fn: Callable  # (params, opt_state, batch, step) -> (p, s, loss)
    ckpt: CheckpointManager
    save_every: int = 50
    max_restarts: int = 3
    straggler_factor: float = 3.0  # warn when a step takes 3x the median
    recoverable: tuple = (InjectedFailure,)
    backoff_s: float = 0.0  # 0: restart immediately (test-friendly default)
    on_event: Optional[Callable[[dict], None]] = None

    def __post_init__(self):
        self.policy = RetryPolicy(recoverable=tuple(self.recoverable),
                                  max_restarts=self.max_restarts,
                                  backoff_s=self.backoff_s)
        self.events = EventLog(self.on_event, verbose=True)

    def run(self, params, opt_state, loader, n_steps: int,
            fail_at: Optional[dict[int, int]] = None,
            log_every: int = 10) -> dict:
        """fail_at: {step: how_many_times_to_fail} — failure injection."""
        fail_at = dict(fail_at or {})
        restarts = 0
        losses = []
        times = []
        step = loader.step
        while step < n_steps:
            try:
                batch = next(loader)
                if fail_at.get(step, 0) > 0:
                    fail_at[step] -= 1
                    raise InjectedFailure(f"injected failure at step {step}")
                t0 = time.time()
                params, opt_state, loss = self.step_fn(
                    params, opt_state, batch, jax.numpy.int32(step))
                jax.block_until_ready(loss)
                dt = time.time() - t0
                times.append(dt)
                med = sorted(times)[len(times) // 2]
                if len(times) > 5 and dt > self.straggler_factor * med:
                    self.events.emit("straggler", step=step,
                                     seconds=round(dt, 4),
                                     median_s=round(med, 4),
                                     factor=self.straggler_factor)
                losses.append(float(loss))
                if step % log_every == 0:
                    print(f"step {step}: loss {float(loss):.4f}", flush=True)
                step += 1
                loader.step = step
                if step % self.save_every == 0:
                    self.ckpt.save(step, {"params": params,
                                          "opt_state": opt_state},
                                   extra={"loader": loader.state()})
            except Exception as e:
                if not self.policy.is_recoverable(e):
                    raise
                restarts += 1
                if restarts > self.policy.max_restarts:
                    raise
                self.events.emit("restart", step=step, error=repr(e),
                                 attempt=restarts,
                                 backoff_s=self.policy.backoff(restarts))
                b = self.policy.backoff(restarts)
                if b:
                    time.sleep(b)
                self.ckpt.wait()
                latest = self.ckpt.latest_step()
                if latest is None:
                    # no checkpoint yet -> restart from the initial state is
                    # the caller's job; here we simply retry the step
                    continue
                _, state, extra = self.ckpt.restore()
                params, opt_state = state["params"], state["opt_state"]
                loader.restore(extra["loader"])
                step = loader.step
        self.ckpt.wait()
        self.ckpt.save(n_steps, {"params": params, "opt_state": opt_state},
                       extra={"loader": loader.state()}, blocking=True)
        return {"params": params, "opt_state": opt_state,
                "losses": losses, "restarts": restarts,
                "events": list(self.events)}
