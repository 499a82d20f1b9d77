"""Sharded calibration→serving data-path contracts.

Host side: per-shard calibration slices are disjoint, cover the full set,
match the global draw bit-for-bit, and resume exactly under (seed, step).

Fake 8-device mesh (subprocess, like test_distributed): a quantize run
with sharded calib + sharded write-back produces a packed serving artifact
bit-identical to the sequential host-gather baseline, with *no* host-side
materialization of an unsharded per-layer (q, scales) tensor on the
sharded path — asserted by instrumenting the module's single host-gather
routine (checkpoint.packed._host_gather).
"""
import json
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.data import (
    CalibrationLoader,
    CalibShard,
    SyntheticCorpus,
    calibration_set,
    calibration_shard,
    shard_bounds,
)

REPO = Path(__file__).resolve().parent.parent


# ------------------------------------------------------------- host-side


def test_shard_bounds_partition():
    for n, s in [(16, 8), (10, 3), (7, 7), (5, 8), (1, 1)]:
        spans = [shard_bounds(n, s, i) for i in range(s)]
        # contiguous, disjoint, covering
        assert spans[0][0] == 0 and spans[-1][1] == n
        for (a, b), (c, d) in zip(spans, spans[1:]):
            assert b == c and a <= b and c <= d


def test_shards_disjoint_cover_and_match_global():
    g = calibration_set(131, 12, 16, seed=5)
    parts = [calibration_shard(131, 12, 16, shard=s, n_shards=4, seed=5)
             for s in range(4)]
    assert sum(p.shape[0] for p in parts) == 12
    assert bool(jnp.all(jnp.concatenate(parts) == g))
    # per-shard slices equal the global rows they claim (not just the union)
    for s, p in enumerate(parts):
        lo, hi = shard_bounds(12, 4, s)
        assert bool(jnp.all(p == g[lo:hi]))
    # deterministic in (seed, shard)
    again = calibration_shard(131, 12, 16, shard=2, n_shards=4, seed=5)
    assert bool(jnp.all(again == parts[2]))


def test_calib_shard_iterator_resume_exact():
    c = SyntheticCorpus(vocab_size=101, seed=1)
    sh = CalibShard(c, 12, 8, shard=1, n_shards=2, batch_size=4, seed=1)
    batches = list(sh)
    sh2 = CalibShard(c, 12, 8, shard=1, n_shards=2, batch_size=4, seed=1)
    sh2.restore({"step": 2, "shard": 1})
    assert bool(jnp.all(next(sh2) == batches[2]))
    # the shard iterator yields exactly its slice of each global batch
    g = calibration_set(101, 12, 8, seed=1, corpus=c)
    lo, hi = shard_bounds(12, 2, 1)
    got = jnp.concatenate([b for b in batches if b.shape[0]])
    assert bool(jnp.all(got == g[lo:hi]))


def test_calibration_loader_local_degenerates_to_global():
    c = SyntheticCorpus(vocab_size=101, seed=2)
    ld = CalibrationLoader(c, 10, 8, batch_size=4, seed=2)
    g = calibration_set(101, 10, 8, seed=2, corpus=c)
    assert bool(jnp.all(ld.dataset() == g))
    batches = list(ld)
    assert bool(jnp.all(jnp.concatenate(batches) == g))
    ld.restore({"step": 1})
    assert bool(jnp.all(next(ld) == batches[1]))


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


def test_sharded_calib_dataset_on_mesh():
    """Each device generates exactly its disjoint slice; the assembled
    array equals the host global draw bit-for-bit."""
    out = _run("""
    import json, jax, jax.numpy as jnp
    from repro.data import SyntheticCorpus, CalibrationLoader, calibration_set
    from repro.runtime.sharding import ParallelCtx
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((8,), ("data",))
    ctx = ParallelCtx(mesh=mesh, dp=("data",))
    c = SyntheticCorpus(vocab_size=211, seed=4)
    ld = CalibrationLoader(c, 16, 8, ctx=ctx, batch_size=8, seed=4)
    ds = ld.dataset()
    g = calibration_set(211, 16, 8, seed=4, corpus=c)
    b0 = next(ld)
    print(json.dumps({
        "spec": str(ds.sharding.spec),
        "shard_shapes": sorted({tuple(s.data.shape)
                                for s in ds.addressable_shards}),
        "equal": bool(jnp.all(ds == g)),
        "batch_equal": bool(jnp.all(b0 == g[:8])),
        "batch_spec": str(b0.sharding.spec),
    }))
    """)
    assert out["equal"] and out["batch_equal"]
    assert "data" in out["spec"] and "data" in out["batch_spec"]
    assert out["shard_shapes"] == [[2, 8]]  # 16 rows / 8 devices


def test_sharded_writeback_bit_identical_to_host_gather():
    """The acceptance contract of the sharded data path: on a (2 data x 4
    model) mesh, sharded calib + streaming Hessians + ring reduce + sharded
    write-back produce a packed serving artifact bit-identical to the
    sequential host-gather baseline; the sharded run never calls the host
    gather, its artifact stays model-axis sharded on device, and the
    reconstructed serving params equal the quantized tree exactly."""
    out = _run("""
    import dataclasses, json, tempfile
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import get_config
    from repro.core import RSQConfig, RSQPipeline
    from repro.data import SyntheticCorpus, CalibrationLoader, calibration_set
    from repro.models import build_model
    from repro.runtime.sharding import ParallelCtx
    from repro.checkpoint import packed as cp
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

    loader = CalibrationLoader(corpus, N, T, ctx=ctx, batch_size=B, seed=0)
    calib_sharded = loader.dataset()
    calib_host = calibration_set(256, N, T, seed=0, corpus=corpus)
    tokens_equal = bool(jnp.all(calib_sharded == calib_host))

    # instrument THE host-gather: the sharded path must never call it
    gathers = []
    orig_gather = cp._host_gather
    cp._host_gather = lambda x: (gathers.append(tuple(np.shape(x))),
                                 orig_gather(x))[1]

    rsq_a = RSQConfig(bits=4, rotate=False, importance="attn_con",
                      scheduler="overlapped", shard_hessians=True,
                      pack_output=True, pack_writeback="sharded")
    pipe_a = RSQPipeline(model, rsq_a, ctx=ctx)
    qa, _ = pipe_a.run(params, calib_sharded, batch_size=B)
    jax.block_until_ready(jax.tree.leaves(qa))
    sharded_gathers = list(gathers)

    n_sharded = 0
    for e in pipe_a.artifact["entries"].values():
        idx = {tuple(s.indices(d)[:2]
                     for s, d in zip(sh.index, e["codes"].shape))
               for sh in e["codes"].addressable_shards}
        n_sharded += len(idx) > 1
    da, db = tempfile.mkdtemp(), tempfile.mkdtemp()
    cp.save_packed_artifact(da, pipe_a.artifact, params=qa)
    post_save_gathers = list(gathers)

    rsq_b = dataclasses.replace(rsq_a, scheduler="sequential",
                                pack_writeback="host")
    pipe_b = RSQPipeline(model, rsq_b, ctx=ctx)
    calib_b = jax.device_put(calib_host,
                             NamedSharding(mesh, P("data", None)))
    qb, _ = pipe_b.run(params, calib_b, batch_size=B)
    cp.save_packed_artifact(db, pipe_b.artifact, params=qb)
    cp._host_gather = orig_gather

    ea, ma = cp.load_packed_artifact(da)
    eb, mb = cp.load_packed_artifact(db)
    bit_identical = (sorted(ea) == sorted(eb)) and all(
        np.array_equal(ea[n][f], eb[n][f])
        for n in ea for f in ("codes", "scale", "zero"))
    params_equal = all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(jax.tree.leaves(qa), jax.tree.leaves(qb)))

    recon, _ = cp.load_packed_params(da)
    recon_equal = all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(jax.tree.leaves(qa), jax.tree.leaves(recon)))

    print(json.dumps({
        "tokens_equal": tokens_equal,
        "sharded_run_gathers": sharded_gathers,
        "post_save_gathers": post_save_gathers,
        "n_entries": len(ea),
        "n_model_sharded_codes": n_sharded,
        "bit_identical": bit_identical,
        "params_equal": params_equal,
        "recon_equal": recon_equal,
        "baseline_gathered": len(gathers) > len(post_save_gathers),
    }))
    """)
    # the sharded loader reproduces the global token set exactly
    assert out["tokens_equal"]
    # no unsharded (q, scales, zeros) ever crossed to host on the sharded
    # path — neither during the run nor during the per-shard artifact save
    assert out["sharded_run_gathers"] == []
    assert out["post_save_gathers"] == []
    # ... while the host-gather baseline did gather (the path it retires)
    assert out["baseline_gathered"]
    # write-back really lands model-axis sharded on device
    assert out["n_model_sharded_codes"] > 0
    # and the two artifacts are bit-identical, as are the quantized params
    # and the serving-side reconstruction
    assert out["n_entries"] > 0
    assert out["bit_identical"]
    assert out["params_equal"]
    assert out["recon_equal"]
