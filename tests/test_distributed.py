"""Distributed behaviour on 8 fake host devices (subprocess-isolated so the
rest of the suite keeps a single device).  Every mesh is built with
``Auto`` axes through ``launch.mesh.make_mesh``."""
import json
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


def _run(code: str) -> dict:
    env = {"XLA_FLAGS": "--xla_force_host_platform_device_count=8",
           "PYTHONPATH": str(REPO / "src"), "PATH": "/usr/bin:/bin"}
    import os
    env.update({k: v for k, v in os.environ.items()
                if k not in env and k != "XLA_FLAGS"})
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


PREAMBLE = """
import json
import jax, jax.numpy as jnp, dataclasses
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.configs import get_config
from repro.models import build_model
from repro.launch.mesh import make_mesh
from repro.runtime.sharding import ParallelCtx, param_shardings
mesh = make_mesh((2, 4), ("data", "model"))
ctx = ParallelCtx(mesh=mesh, dp=("data",), tp="model", ep=True)
"""


@pytest.mark.slow
def test_sharded_loss_matches_local():
    out = _run(PREAMBLE + """
cfg = dataclasses.replace(get_config("qwen1.5-4b").reduced(),
                          dtype="float32", n_layers=2)
m = build_model(cfg, ctx)
sh = param_shardings(m.param_shapes(), ctx)
with jax.set_mesh(mesh):
    params = jax.jit(m.init, out_shardings=sh)(jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (4, 32), 0, cfg.vocab_size)
    batch = jax.device_put({"tokens": toks, "labels": toks},
                           NamedSharding(mesh, P("data", None)))
    loss = float(jax.jit(m.loss)(params, batch))
m_local = build_model(cfg)
p_local = jax.tree.map(jnp.asarray, jax.device_get(params))
loss_local = float(m_local.loss(p_local, jax.device_get(batch)))
print(json.dumps({"diff": abs(loss - loss_local), "loss": loss}))
""")
    assert out["diff"] < 1e-4


@pytest.mark.slow
def test_moe_ep_grads_flow():
    out = _run(PREAMBLE + """
cfg = dataclasses.replace(get_config("deepseek-v2-236b").reduced(),
                          dtype="float32", n_routed_experts=8, d_model=64)
m = build_model(cfg, ctx)
sh = param_shardings(m.param_shapes(), ctx)
with jax.set_mesh(mesh):
    params = jax.jit(m.init, out_shardings=sh)(jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (4, 32), 0, cfg.vocab_size)
    batch = jax.device_put({"tokens": toks, "labels": toks},
                           NamedSharding(mesh, P("data", None)))
    loss, grads = jax.jit(jax.value_and_grad(m.loss))(params, batch)
    g = jax.tree.leaves(grads)
    gn = float(sum(jnp.sum(jnp.abs(x)) for x in g))
print(json.dumps({"loss": float(loss), "grad_norm": gn}))
""")
    assert out["grad_norm"] > 0


@pytest.mark.slow
def test_elastic_restart_different_mesh(tmp_path):
    """Save on a (2,4) mesh, restore & step on (4,2) — elastic scaling."""
    out = _run(PREAMBLE + f"""
from repro.checkpoint import CheckpointManager
cfg = dataclasses.replace(get_config("qwen1.5-4b").reduced(),
                          dtype="float32", n_layers=2)
m = build_model(cfg, ctx)
sh = param_shardings(m.param_shapes(), ctx)
with jax.set_mesh(mesh):
    params = jax.jit(m.init, out_shardings=sh)(jax.random.key(0))
cm = CheckpointManager(r"{tmp_path}")
cm.save(1, {{"params": params}}, blocking=True)

mesh2 = make_mesh((4, 2), ("data", "model"))
ctx2 = ParallelCtx(mesh=mesh2, dp=("data",), tp="model", ep=True)
m2 = build_model(cfg, ctx2)
sh2 = param_shardings(m2.param_shapes(), ctx2)
_, state, _ = cm.restore(shardings={{"params": sh2}})
with jax.set_mesh(mesh2):
    toks = jax.random.randint(jax.random.key(1), (4, 32), 0, cfg.vocab_size)
    batch = jax.device_put({{"tokens": toks, "labels": toks}},
                           NamedSharding(mesh2, P("data", None)))
    loss = float(jax.jit(m2.loss)(state["params"], batch))
print(json.dumps({{"loss": loss}}))
""")
    assert out["loss"] > 0


@pytest.mark.slow
def test_overlap_collectives_and_pp():
    out = _run("""
import json, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.runtime.collectives import (allgather_matmul,
                                       matmul_reducescatter,
                                       ring_allreduce_int8)
from repro.runtime.pipeline_parallel import pipeline_apply
from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ("x",))
x = jax.random.normal(jax.random.key(0), (64, 32))
w = jax.random.normal(jax.random.key(1), (32, 48))
f = jax.jit(jax.shard_map(lambda a, b: allgather_matmul(a, b, "x"),
    mesh=mesh, in_specs=(P("x", None), P(None, "x")), out_specs=P(None, "x")))
e1 = float(jnp.abs(f(x, w) - x @ w).max())
g = jax.jit(jax.shard_map(lambda a, b: matmul_reducescatter(a, b, "x"),
    mesh=mesh, in_specs=(P(None, "x"), P("x", None)), out_specs=P("x", None)))
e2 = float(jnp.abs(g(x, w) - x @ w).max())
v = jax.random.normal(jax.random.key(2), (8, 64, 16))
h = jax.jit(jax.shard_map(lambda vs: ring_allreduce_int8(vs[0], "x"),
    mesh=mesh, in_specs=(P("x", None, None),), out_specs=P(None, None),
    check_vma=False))
ref = v.sum(0)
e3 = float(jnp.abs(h(v) - ref).max() / jnp.abs(ref).max())
S, M = 8, 4
ws = jax.random.normal(jax.random.key(3), (S, 16, 16)) * 0.3
mb = jax.random.normal(jax.random.key(4), (M, 4, 16))
stage = lambda w_, x_: jnp.tanh(x_ @ w_)
pf = jax.jit(jax.shard_map(lambda w_, x_: pipeline_apply(stage, w_[0], x_, "x"),
    mesh=mesh, in_specs=(P("x", None, None), P(None, None, None)),
    out_specs=P(None, None, None), check_vma=False))
out = pf(ws, mb); refp = mb
for s in range(S): refp = jnp.tanh(refp @ ws[s])
e4 = float(jnp.abs(out - refp).max())
print(json.dumps({"ag_mm": e1, "mm_rs": e2, "ar_int8": e3, "pp": e4}))
""")
    assert out["ag_mm"] < 1e-5 and out["mm_rs"] < 1e-4
    assert out["ar_int8"] < 0.05 and out["pp"] < 1e-5


@pytest.mark.slow
def test_dryrun_minicell():
    """The dry-run machinery on a small mesh: lower+compile+analyze."""
    out = _run("""
import json, jax, dataclasses, jax.numpy as jnp
from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.models import build_model
from repro.runtime.sharding import ParallelCtx
from repro.launch.steps import make_train_step, sharded_args_train
from repro.launch.specs import batch_inputs
from repro.optim import make_optimizer
from repro.runtime.hlo_analysis import analyze_hlo
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
ctx = ParallelCtx(mesh=mesh, dp=("data",), tp="model", ep=True)
cfg = dataclasses.replace(get_config("qwen1.5-4b").reduced(), n_layers=2)
model = build_model(cfg, ctx)
opt = make_optimizer("adamw", 1e-3)
shape = ShapeConfig("t", "train", 64, 4)
batch = batch_inputs(cfg, shape, ctx)
args = sharded_args_train(model, opt, batch, ctx)
with jax.set_mesh(mesh):
    compiled = jax.jit(make_train_step(model, opt),
                       donate_argnums=(0, 1)).lower(*args).compile()
ma = compiled.memory_analysis()
hlo = analyze_hlo(compiled.as_text())
print(json.dumps({"temp": ma.temp_size_in_bytes,
                  "flops": hlo["dot_flops_per_device"],
                  "coll": hlo["collective_link_bytes_per_device"]}))
""")
    assert out["flops"] > 0 and out["coll"] > 0
