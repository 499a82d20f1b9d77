"""Fault-tolerant quantization: kill-and-resume parity, in-process retry,
artifact corruption rejection.

The contract under test (core/resume.QuantizeRunner + the scheduler stage
hooks): a quantize run killed at ANY stage dispatch point — mid-capture,
mid-solve, mid-pack, under either scheduler — and resumed from its latest
layer-solve checkpoint by a *fresh* pipeline/runner (a new process, as far
as jax is concerned) produces a packed serving artifact whose files are
**byte-identical** to a run that never died.  File-level sha256 is the
strongest form of the claim: it covers codes, scales, zeros, the residual
tree, entry order inside the npz, and the meta.json checksums.

The fake-8-device mesh variant (subprocess, like test_distributed) repeats
the kill/resume under sharded calibration + streaming sharded Hessians +
sharded write-back on a (2 data x 4 model) mesh.

Artifact durability: a bit-flipped payload file fails its recorded sha256
at load with ArtifactCorruptError (``verify=False`` opts out) — the
serve-side gate against silently serving corrupt codes.
"""
import hashlib
import json
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.checkpoint import ArtifactCorruptError, CheckpointManager
from repro.checkpoint import packed as cp
from repro.core import RSQConfig, RSQPipeline
from repro.core.resume import QuantizeRunner
from repro.data.calibration import calibration_set
from repro.runtime.fault import FaultPlan, InjectedFailure, RetryPolicy

REPO = Path(__file__).resolve().parent.parent

N_CALIB, SEQ, BATCH = 8, 32, 4
# the injection layer: > 0 so a layer-solve checkpoint exists to resume
# from.  Mid-capture kills need scheduler-specific layers: the overlapped
# schedule interleaves layer i+1's capture into layer i's sweep *before*
# layer i's commit, so the first capture point that lands after a durable
# commit is layer 2's (during layer 1's sweep) — hence the 3-layer stack.
FAIL_LAYER = 1
STAGES = [("capture", 1), ("solve", None), ("pack", None)]
CAPTURE_LAYER = {"sequential": 1, "overlapped": 2}


def _rsq(scheduler):
    return RSQConfig(bits=4, group_size=32, scheduler=scheduler,
                     pack_output=True)


def _calib(cfg):
    return calibration_set(cfg.vocab_size, N_CALIB, SEQ, seed=0)


@pytest.fixture(scope="module")
def mp(tiny_cfg):
    """3-layer variant of the tiny model (see CAPTURE_LAYER note)."""
    import dataclasses

    from repro.models import build_model

    cfg = dataclasses.replace(tiny_cfg, n_layers=3)
    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.key(0))
    return cfg, model, params


def _sha_dir(d: Path) -> dict:
    return {p.name: hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(Path(d).iterdir())}


@pytest.fixture(scope="module")
def baselines(tmp_path_factory, mp):
    """Uninterrupted-run artifacts, one per scheduler: {sched: (dir, shas)}."""
    cfg, model, params = mp
    out = {}
    for sched in ("sequential", "overlapped"):
        d = tmp_path_factory.mktemp(f"clean_{sched}")
        pipe = RSQPipeline(model, _rsq(sched))
        qp, _ = pipe.run(params, _calib(cfg), batch_size=BATCH)
        cp.save_packed_artifact(d, pipe.artifact, params=qp)
        out[sched] = (d, _sha_dir(d))
    return out


def _kill_then_resume(model, params, calib, sched, tmp, fault_key):
    """One killed 'process' (max_restarts=0 so the fault propagates), then
    a fresh pipeline+runner over the same progress dir — returns the
    resumed runner and its saved artifact dir."""
    prog = tmp / "progress"
    r1 = QuantizeRunner(RSQPipeline(model, _rsq(sched)),
                        CheckpointManager(prog),
                        policy=RetryPolicy(max_restarts=0))
    fault = FaultPlan({fault_key: 1})
    with pytest.raises(InjectedFailure):
        r1.run(params, calib, fault=fault, batch_size=BATCH)
    assert fault.fired and fault.fired[0]["layer"] == fault_key[0]
    # a layer-solve checkpoint must exist for the resume to pick up
    assert CheckpointManager(prog).latest_step() is not None

    pipe2 = RSQPipeline(model, _rsq(sched))
    r2 = QuantizeRunner(pipe2, CheckpointManager(prog),
                        policy=RetryPolicy(max_restarts=0))
    qp, report = r2.run(params, calib, batch_size=BATCH)
    art = tmp / "artifact"
    cp.save_packed_artifact(art, pipe2.artifact, params=qp)
    return r2, art, report


@pytest.mark.parametrize("sched", ["sequential", "overlapped"])
@pytest.mark.parametrize("stage,batch", STAGES,
                         ids=[s for s, _ in STAGES])
def test_kill_resume_byte_identical(tmp_path, mp, baselines, sched, stage,
                                    batch):
    cfg, model, params = mp
    layer = CAPTURE_LAYER[sched] if stage == "capture" else FAIL_LAYER
    key = (layer, stage) if batch is None else (layer, stage, batch)
    r2, art, report = _kill_then_resume(
        model, params, _calib(cfg), sched, tmp_path, key)
    assert "resume" in r2.events.kinds()
    # the solved prefix was skipped, not recomputed
    assert report["layers"]["layer0"].get("resumed") is True
    assert _sha_dir(art) == baselines[sched][1]


@pytest.mark.parametrize("sched", ["sequential", "overlapped"])
def test_in_process_retry_recovers(tmp_path, mp, baselines, sched):
    """With restarts allowed, one runner survives the injected failure by
    itself: restore -> mid-stack re-entry -> identical artifact."""
    cfg, model, params = mp
    pipe = RSQPipeline(model, _rsq(sched))
    runner = QuantizeRunner(pipe, CheckpointManager(tmp_path / "progress"),
                            policy=RetryPolicy(max_restarts=2,
                                               backoff_s=0.001))
    qp, _ = runner.run(params, _calib(cfg),
                       fault=FaultPlan({(FAIL_LAYER, "solve"): 1}),
                       batch_size=BATCH)
    assert runner.restarts == 1
    kinds = runner.events.kinds()
    assert "restart" in kinds and "resume" in kinds
    restart = next(e for e in runner.events if e["kind"] == "restart")
    assert restart["attempt"] == 1 and "backoff_s" in restart
    art = tmp_path / "artifact"
    cp.save_packed_artifact(art, pipe.artifact, params=qp)
    assert _sha_dir(art) == baselines[sched][1]


def test_unrecoverable_exception_propagates(tmp_path, mp):
    """A failure outside the policy's recoverable tuple is not retried."""
    cfg, model, params = mp
    runner = QuantizeRunner(RSQPipeline(model, _rsq("sequential")),
                            CheckpointManager(tmp_path / "p"),
                            policy=RetryPolicy(recoverable=(KeyError,),
                                               max_restarts=5))
    with pytest.raises(InjectedFailure):
        runner.run(params, _calib(cfg),
                   fault=FaultPlan({(0, "solve"): 1}), batch_size=BATCH)
    assert runner.restarts == 0


def _flip_member_byte(path: Path) -> None:
    """Flip one byte inside the first zip member's *data* region: the zip
    container stays parseable, the stored array bytes do not match the
    recorded sha256 — the silent-corruption case checksums exist for."""
    import struct
    import zipfile

    with zipfile.ZipFile(path) as zf:
        zi = zf.infolist()[0]
    raw = bytearray(path.read_bytes())
    ho = zi.header_offset
    name_len, extra_len = struct.unpack("<HH", raw[ho + 26 : ho + 30])
    data_off = ho + 30 + name_len + extra_len
    raw[data_off + zi.file_size // 2] ^= 0xFF
    path.write_bytes(bytes(raw))


def test_corrupt_artifact_rejected(tmp_path, baselines):
    """A bit-flipped payload fails its sha256 at load; verify=False and
    pre-v3 artifacts opt out."""
    src, _ = baselines["sequential"]
    d = tmp_path / "corrupt"
    d.mkdir()
    for p in Path(src).iterdir():
        (d / p.name).write_bytes(p.read_bytes())
    _flip_member_byte(d / "packed.npz")

    with pytest.raises(ArtifactCorruptError, match="re-run"):
        cp.load_packed_artifact(d)
    with pytest.raises(ArtifactCorruptError):
        cp.load_packed_forward_params(d)
    # explicit opt-out (launch.serve --no-verify) skips the sha256 gate:
    # the failure becomes whatever the deserializer hits (here zipfile's
    # member CRC, deep inside np.load) instead of the actionable
    # ArtifactCorruptError raised up front
    import zipfile
    with pytest.raises(zipfile.BadZipFile):
        cp.load_packed_artifact(d, verify=False)
    # and a pristine artifact loads fine with verification off
    entries, meta = cp.load_packed_artifact(src, verify=False)
    assert len(entries) == len(meta["entries"])

    # the residual payload is covered too
    d2 = tmp_path / "corrupt_res"
    d2.mkdir()
    for p in Path(src).iterdir():
        (d2 / p.name).write_bytes(p.read_bytes())
    _flip_member_byte(d2 / "residual.npz")
    with pytest.raises(ArtifactCorruptError):
        cp.load_packed_params(d2)


def test_artifact_checksums_recorded(baselines):
    """v3 artifacts carry a sha256 per payload file, and it matches."""
    d, shas = baselines["overlapped"]
    meta = json.loads((Path(d) / "meta.json").read_text())
    assert meta["format"] == cp.FORMAT
    for fname in ("packed.npz", "residual.npz"):
        assert meta["checksums"][fname] == shas[fname]


def test_loader_geometry_mismatch_rejected():
    from repro.data.loader import CalibrationLoader
    from repro.data.synthetic import SyntheticCorpus

    c = SyntheticCorpus(vocab_size=101, seed=1)
    ld = CalibrationLoader(c, 8, 16, batch_size=4, seed=1)
    st = ld.state()
    assert st["n_samples"] == 8 and st["batch_size"] == 4
    with pytest.raises(ValueError, match="n_samples"):
        ld.restore({**st, "n_samples": 16})
    with pytest.raises(ValueError, match="seed"):
        ld.restore({**st, "seed": 2})
    ld.restore({**st, "step": 1})
    assert ld.step == 1


# ------------------------------------------------------- fake 8-device mesh


def _run(code: str) -> dict:
    import os
    env = {"XLA_FLAGS": "--xla_force_host_platform_device_count=8",
           "PYTHONPATH": str(REPO / "src"), "PATH": "/usr/bin:/bin"}
    env.update({k: v for k, v in os.environ.items()
                if k not in env and k != "XLA_FLAGS"})
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_kill_resume_byte_identical_on_mesh():
    """(2 data x 4 model) mesh, sharded calibration, streaming sharded
    Hessians, sharded write-back: kill+resume parity holds under both
    schedulers, including a mid-capture kill whose resume restores the
    overlapped schedule's checkpointed accumulators."""
    out = _run("""
    import dataclasses, hashlib, json, pathlib, tempfile
    import jax
    from repro.configs import get_config
    from repro.models import build_model
    from repro.core import RSQConfig, RSQPipeline
    from repro.core.resume import QuantizeRunner
    from repro.checkpoint import CheckpointManager
    from repro.checkpoint import packed as cp
    from repro.data import SyntheticCorpus, CalibrationLoader
    from repro.runtime.fault import FaultPlan, InjectedFailure, RetryPolicy
    from repro.runtime.sharding import ParallelCtx
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((2, 4), ("data", "model"))
    ctx = ParallelCtx(mesh=mesh, dp=("data",), tp="model")
    cfg = dataclasses.replace(get_config("llama3-8b").reduced(),
                              dtype="float32", n_layers=2, d_model=64,
                              vocab_size=256)
    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.key(0))
    corpus = SyntheticCorpus(vocab_size=256, seed=0)
    N, T, B = 16, 16, 8

    def rsq(s):
        return RSQConfig(bits=4, rotate=False, scheduler=s,
                         shard_hessians=True, pack_output=True)

    def loader():
        return CalibrationLoader(corpus, N, T, ctx=ctx, batch_size=B, seed=0)

    def sha_dir(d):
        return {p.name: hashlib.sha256(p.read_bytes()).hexdigest()
                for p in sorted(pathlib.Path(d).iterdir())}

    td = pathlib.Path(tempfile.mkdtemp())
    results = {}
    for sched in ("sequential", "overlapped"):
        pipe = RSQPipeline(model, rsq(sched), ctx=ctx)
        qp, _ = pipe.run(params, loader().dataset(), batch_size=B)
        cp.save_packed_artifact(td / f"clean_{sched}", pipe.artifact,
                                params=qp)
        base = sha_dir(td / f"clean_{sched}")
        kills = [("solve", None)]
        if sched == "overlapped":
            kills.append(("capture", 1))
        for stage, batch in kills:
            key = (1, stage) if batch is None else (1, stage, batch)
            prog = td / f"prog_{sched}_{stage}"
            ld = loader()
            r1 = QuantizeRunner(RSQPipeline(model, rsq(sched), ctx=ctx),
                                CheckpointManager(prog), loader=ld,
                                policy=RetryPolicy(max_restarts=0))
            try:
                r1.run(params, ld.dataset(), fault=FaultPlan({key: 1}),
                       batch_size=B)
                raise SystemExit("fault did not fire")
            except InjectedFailure:
                pass
            ld2 = loader()
            pipe2 = RSQPipeline(model, rsq(sched), ctx=ctx)
            r2 = QuantizeRunner(pipe2, CheckpointManager(prog), loader=ld2,
                                policy=RetryPolicy(max_restarts=0))
            qp2, _ = r2.run(params, ld2.dataset(), batch_size=B)
            cp.save_packed_artifact(td / f"res_{sched}_{stage}",
                                    pipe2.artifact, params=qp2)
            results[f"{sched}_{stage}"] = (
                sha_dir(td / f"res_{sched}_{stage}") == base)
    print(json.dumps(results))
    """)
    assert out == {"sequential_solve": True, "overlapped_solve": True,
                   "overlapped_capture": True}, out
