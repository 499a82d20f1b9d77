"""Scheduler + streaming-Hessian contracts.

The OverlappedScheduler only reorders *dispatch* — it must produce
bit-identical quantized parameters to the SequentialScheduler.  The
streaming sharded Hessian accumulators must (a) match the dense
accumulation numerically and (b) stay sharded on a mesh — no device ever
holds an unsharded per-layer Hessian during accumulation.
"""
import dataclasses
import json
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import (
    OverlappedScheduler,
    RSQConfig,
    RSQPipeline,
    SequentialScheduler,
    get_scheduler,
)
from repro.core.hessian import accumulate, reduce_shards
from repro.core.scheduler import resolve_hessian_shards

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def toy4():
    """4-layer homogeneous toy model."""
    cfg = dataclasses.replace(
        get_config("llama3-8b").reduced(), dtype="float32",
        n_layers=4, d_model=64, vocab_size=256)
    from repro.models import build_model

    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.key(0))
    calib = jax.random.randint(jax.random.key(1), (8, 32), 0, cfg.vocab_size)
    return model, params, calib


def _quantize(toy4, **kw):
    model, params, calib = toy4
    rsq = RSQConfig(bits=4, rotate=False, importance="attn_con", **kw)
    pipe = RSQPipeline(model, rsq)
    qparams, report = pipe.run(params, calib, batch_size=4)
    return qparams, report, pipe


def test_overlapped_bit_identical_to_sequential(toy4):
    q_seq, rep_seq, _ = _quantize(toy4, scheduler="sequential")
    q_ovl, rep_ovl, _ = _quantize(toy4, scheduler="overlapped")
    assert rep_seq["scheduler"] == "sequential"
    assert rep_ovl["scheduler"] == "overlapped"
    for a, b in zip(jax.tree.leaves(q_seq), jax.tree.leaves(q_ovl)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    # per-weight error reports agree too (same solves, deferred sync)
    for tag, rep in rep_seq["layers"].items():
        assert rep["weights"] == rep_ovl["layers"][tag]["weights"]


def test_overlapped_bit_identical_ldlq(toy4):
    q_seq, _, _ = _quantize(toy4, scheduler="sequential", method="ldlq")
    q_ovl, _, _ = _quantize(toy4, scheduler="overlapped", method="ldlq")
    for a, b in zip(jax.tree.leaves(q_seq), jax.tree.leaves(q_ovl)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_overlapped_adds_no_compiles(toy4):
    """Pipelined dispatch reuses the per-meta trace cache: still 1/1."""
    _, _, pipe = _quantize(toy4, scheduler="overlapped")
    assert pipe.trace_counts == {"capture": 1, "apply": 1}


def test_overlapped_prewarm_heterogeneous_stack():
    """A stack with >1 distinct meta takes the concurrent-prewarm path
    (background-thread compiles): results stay bit-identical to the
    lock-step schedule and the compile accounting stays exact."""
    cfg = dataclasses.replace(
        get_config("deepseek-v2-236b").reduced(), dtype="float32",
        n_routed_experts=4, d_model=64)
    from repro.models import build_model

    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.key(0))
    calib = jax.random.randint(jax.random.key(1), (4, 32), 0, cfg.vocab_size)
    outs, traces = {}, {}
    for sched in ("sequential", "overlapped"):
        rsq = RSQConfig(bits=4, rotate=False, importance="attn_con",
                        scheduler=sched)
        pipe = RSQPipeline(model, rsq)
        outs[sched], _ = pipe.run(params, calib, batch_size=4)
        traces[sched] = dict(pipe.trace_counts)
    assert traces["sequential"]["capture"] > 1  # really heterogeneous
    assert traces["overlapped"] == traces["sequential"]
    for a, b in zip(jax.tree.leaves(outs["sequential"]),
                    jax.tree.leaves(outs["overlapped"])):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_scheduler_registry_and_auto():
    assert isinstance(get_scheduler("sequential"), SequentialScheduler)
    assert isinstance(get_scheduler("overlapped"), OverlappedScheduler)
    auto = get_scheduler(None)
    if jax.default_backend() == "cpu":
        assert isinstance(auto, SequentialScheduler)
    with pytest.raises(ValueError):
        get_scheduler("warp-speed")


def test_resolve_hessian_shards():
    from repro.runtime.sharding import LOCAL

    assert resolve_hessian_shards(False, LOCAL) == 1
    assert resolve_hessian_shards(True, LOCAL) == 1  # no mesh -> dense
    assert resolve_hessian_shards(4, LOCAL) == 4
    assert resolve_hessian_shards(0, None) == 1


# ------------------------------------------------------- streaming hessians


def test_streaming_accumulate_matches_dense():
    x = jax.random.normal(jax.random.key(0), (96, 32))
    r = jax.random.uniform(jax.random.key(1), (96,))
    dense = accumulate(None, x, r)
    for s in (2, 3, 4):
        sharded = accumulate(None, x, r, n_shards=s)
        assert sharded.shape == (s, 32, 32)
        np.testing.assert_allclose(np.asarray(reduce_shards(sharded)),
                                   np.asarray(dense), rtol=1e-5, atol=1e-4)


def test_streaming_accumulate_pads_ragged_rows():
    """Rows that don't divide by S are zero-padded — exactly gram-neutral."""
    x = jax.random.normal(jax.random.key(2), (50, 16))  # 50 % 4 != 0
    dense = accumulate(None, x)
    sharded = accumulate(None, x, n_shards=4)
    np.testing.assert_allclose(np.asarray(reduce_shards(sharded)),
                               np.asarray(dense), rtol=1e-5, atol=1e-4)


def test_streaming_accumulate_expert_stacks():
    xe = jax.random.normal(jax.random.key(3), (4, 32, 16))
    re = jax.random.uniform(jax.random.key(4), (4, 32))
    dense = accumulate(None, xe, re)
    sharded = accumulate(None, xe, re, n_shards=2)
    assert sharded.shape == (2, 4, 16, 16)
    np.testing.assert_allclose(np.asarray(reduce_shards(sharded)),
                               np.asarray(dense), rtol=1e-5, atol=1e-4)


def test_pipeline_shard_hessians_close_to_dense(toy4):
    """Single-host streaming (int shard count): same model quality; only
    float summation order differs from the dense accumulators."""
    q_dense, _, _ = _quantize(toy4, scheduler="sequential")
    q_shard, rep, _ = _quantize(toy4, scheduler="overlapped",
                                shard_hessians=2)
    for a, b in zip(jax.tree.leaves(q_dense), jax.tree.leaves(q_shard)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=0.05)


def _run_sub(code: str) -> dict:
    import os
    env = {"XLA_FLAGS": "--xla_force_host_platform_device_count=2",
           "PYTHONPATH": str(REPO / "src"), "PATH": "/usr/bin:/bin"}
    env.update({k: v for k, v in os.environ.items()
                if k not in env and k != "XLA_FLAGS"})
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_sharded_vs_dense_hessian_on_fake_mesh():
    """2-device mesh: the streaming accumulator stays sharded end to end
    (each device only ever holds its own partial) and the single solve-time
    reduction matches the dense per-batch-psum path."""
    out = _run_sub("""
    import json, jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.runtime.sharding import ParallelCtx
    from repro.core.distributed import make_sharded_hessian_fn
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((2,), ("data",))
    ctx = ParallelCtx(mesh=mesh, dp=("data",))
    acc, reduce_fn = make_sharded_hessian_fn(ctx, streaming=True)
    dense = make_sharded_hessian_fn(ctx)

    h, hd = None, jnp.zeros((32, 32))
    shard_shapes = []
    for s in range(3):
        x = jax.device_put(jax.random.normal(jax.random.key(s), (4, 8, 32)),
                           NamedSharding(mesh, P("data", None, None)))
        r = jax.device_put(jax.random.uniform(jax.random.key(10 + s), (4, 8)),
                           NamedSharding(mesh, P("data", None)))
        h = acc(h, x, r)
        hd = dense(hd, x, r)
        shard_shapes.append(
            [list(sh.data.shape) for sh in h.addressable_shards])
    hr = reduce_fn(h)
    rep = all(np.array_equal(np.asarray(s.data),
                             np.asarray(hr.addressable_shards[0].data))
              for s in hr.addressable_shards)
    print(json.dumps({
        "spec": str(h.sharding.spec),
        "shard_shapes": shard_shapes,
        "rel_diff": float(jnp.abs(hr - hd).max() / jnp.abs(hd).max()),
        "replicated": bool(rep),
    }))
    """)
    # every per-batch accumulator state is the (1, 32, 32) local partial —
    # the unsharded (32, 32) Hessian never exists on a device pre-reduce
    for shapes in out["shard_shapes"]:
        assert shapes == [[1, 32, 32], [1, 32, 32]]
    assert "data" in out["spec"]
    assert out["rel_diff"] < 1e-5
    assert out["replicated"]


def test_gram_kernel_runs_per_device_on_data_mesh():
    """The Pallas gram kernel on a 2-device data mesh runs per device under
    shard_map, each device on its own token shard, and the streaming
    partials reduce to the one-device Hessian."""
    out = _run_sub("""
    import dataclasses, json
    import jax, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import get_config
    from repro.core import RSQConfig, RSQPipeline
    from repro.data.synthetic import SyntheticCorpus
    from repro.launch.mesh import make_mesh
    from repro.models import build_model
    from repro.runtime.sharding import LOCAL, ParallelCtx

    cfg = dataclasses.replace(get_config("llama3-8b").reduced(),
                              dtype="float32", n_layers=1, d_model=128,
                              vocab_size=256)
    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.key(0))
    calib = SyntheticCorpus(vocab_size=256, seed=0).sample(
        jax.random.key(1), 8, 32)
    seen, local = {}, []
    solve, acc_local = RSQPipeline.layer_solve, RSQPipeline._accumulate_local

    def spy(self, state):
        h = state["hessians"]["mixer/wq"]
        seen[self.ctx.enabled] = np.asarray(h.sum(0) if h.ndim == 3 else h)
        return solve(self, state)

    RSQPipeline.layer_solve = spy
    RSQPipeline._accumulate_local = (
        lambda self, *a: (local.append(1), acc_local(self, *a))[1])
    rsq = RSQConfig(bits=4, rotate=False, use_gram_kernel=True,
                    shard_hessians=True)
    RSQPipeline(model, rsq, ctx=LOCAL).run(params, calib, batch_size=4)
    mesh = make_mesh((2,), ("data",))
    ctx = ParallelCtx(mesh=mesh, dp=("data",))
    RSQPipeline(model, rsq, ctx=ctx).run(
        params, jax.device_put(calib, NamedSharding(mesh, P("data", None))),
        batch_size=4)
    print(json.dumps({
        "local_traces": len(local),
        "rel": float(np.abs(seen[True] - seen[False]).max()
                     / np.abs(seen[False]).max()),
    }))
    """)
    assert out["local_traces"] > 0
    assert out["rel"] < 1e-5
