"""Packed-in-HBM serving forward: parity + zero-dequant contracts.

The keep-packed serving path (``checkpoint.packed.load_packed_forward_params``
-> ``PackedWeight`` pytree nodes -> ``models.layers.linear`` ->
``quant_matmul``) must produce exactly the greedy tokens of the legacy
dequantize-at-load path, while never creating an fp array of any quantized
weight's full shape: the guard instruments ``quantizer.dequantize_packed``
and ``checkpoint.packed.dequantize_entry`` and pins both to zero calls
during ``generate`` — since PR 5 with *no* exceptions: MLA's absorbed
decode contracts packed codes through the latent-layout kernels instead
of transiently dequantizing ``wkv_b`` per step.  Runs on the single local
device here and on the fake 8-device (2 data x 4 model) mesh in
subprocesses (like test_distributed): one mesh test pins the GSPMD-ref
serving semantics (sharded codes, sharded residual write-back), a second
pins the shard_map'd Pallas route — with the kernel forced, mesh-sharded
serving must take the per-shard fused kernel with *zero* ref-GEMM
fallbacks and bit-identical outputs vs the GSPMD ref.

This test also *replaces* ``launch.serve._kernel_check`` (one projection
driven through the kernel): every 2-D artifact entry is cross-checked
against its dequantized matmul, and the full forward covers the rest.
"""
import json
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import packed as cp
from repro.core import RSQConfig, RSQPipeline
from repro.data.synthetic import SyntheticCorpus
from repro.kernels.quant_matmul.ops import (PackedWeight,
                                            packed_weight_from_artifact,
                                            quant_matmul)
from repro.launch.serve import generate, resident_weight_bytes

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def artifact_dir(tmp_path_factory, tiny_model_params):
    model, params = tiny_model_params
    corpus = SyntheticCorpus(vocab_size=model.cfg.vocab_size, seed=0)
    calib = corpus.sample(jax.random.key(1), 8, 32)
    rsq = RSQConfig(bits=4, rotate=False, importance="attn_con",
                    pack_output=True)
    pipe = RSQPipeline(model, rsq)
    qparams, _ = pipe.run(params, calib, batch_size=4)
    d = tmp_path_factory.mktemp("packed_artifact")
    cp.save_packed_artifact(d, pipe.artifact, params=qparams,
                            extra={"arch": model.cfg.name})
    return d


class _Guard:
    """Counts every fp materialization of a packed weight — and, since
    PR 7, of the quantized KV cache (kv_dequantize / kv_log_decode are
    debug-only materializers; serving attends on codes directly through
    kernels.flash_decode)."""

    def __init__(self, monkeypatch):
        self.calls: list[str] = []
        import repro.core.quantizer as qz
        import repro.models.attention as att

        def wrap(tag, fn):
            return lambda *a, **k: (self.calls.append(tag), fn(*a, **k))[1]

        deq = wrap("dequantize_packed", qz.dequantize_packed)
        monkeypatch.setattr(qz, "dequantize_packed", deq)
        # every module-level import of the symbol
        monkeypatch.setattr(cp, "dequantize_packed", deq)
        monkeypatch.setattr(att, "dequantize_packed", deq)
        monkeypatch.setattr(cp, "dequantize_entry",
                            wrap("dequantize_entry", cp.dequantize_entry))
        monkeypatch.setattr(att, "kv_dequantize",
                            wrap("kv_dequantize", att.kv_dequantize))
        monkeypatch.setattr(att, "kv_log_decode",
                            wrap("kv_log_decode", att.kv_log_decode))


@pytest.mark.parametrize("arch", ["deepseek-v2-236b", "jamba-v0.1-52b"])
def test_packed_forward_parity_other_families(arch, tmp_path, monkeypatch):
    """Pin the non-GQA dispatch branches: deepseek-v2 smoke exercises the
    expert-stack vmapped quant_matmul (3-D PackedWeight) *and* MLA's
    absorbed decode — since PR 5 that path contracts the packed codes
    through the latent-layout ``quant_matmul_t``/``quant_matmul``
    (``mla_latent_weights`` per-head views), so the zero-dequant guard
    now covers MLA too: not a single ``dequantize_packed`` anywhere in
    the decode trace (``_materialize`` used to be the one documented
    exception); jamba smoke exercises the mamba projections."""
    import dataclasses

    from repro.configs import get_config
    from repro.models import build_model

    cfg = dataclasses.replace(get_config(arch).reduced(), dtype="float32")
    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.key(0))
    corpus = SyntheticCorpus(vocab_size=cfg.vocab_size, seed=0)
    calib = corpus.sample(jax.random.key(1), 8, 32)
    pipe = RSQPipeline(model, RSQConfig(bits=4, rotate=False,
                                        importance="attn_con",
                                        pack_output=True))
    qparams, _ = pipe.run(params, calib, batch_size=4)
    d = tmp_path / "artifact"
    cp.save_packed_artifact(d, pipe.artifact, params=qparams)

    deq_params, _ = cp.load_packed_params(d)
    pk_params, _ = cp.load_packed_forward_params(d)
    if arch.startswith("deepseek"):
        assert any(isinstance(w, PackedWeight) and w.w_packed.ndim >= 3
                   for w in jax.tree.leaves(
                       pk_params,
                       is_leaf=lambda x: isinstance(x, PackedWeight)))
    prompts = corpus.sample(jax.random.key(2), 2, 16)
    ref_tokens = generate(model, deq_params, prompts, 6)
    guard = _Guard(monkeypatch)
    pk_tokens = generate(model, pk_params, prompts, 6)
    assert guard.calls == [], guard.calls
    assert bool(jnp.all(ref_tokens == pk_tokens))


def test_packed_forward_parity_and_zero_dequant(artifact_dir,
                                                tiny_model_params,
                                                monkeypatch):
    model, _ = tiny_model_params
    deq_params, meta = cp.load_packed_params(artifact_dir)
    pk_params, _ = cp.load_packed_forward_params(artifact_dir)

    # every artifact entry became a PackedWeight node; nothing of a
    # quantized weight's fp footprint is resident in the tree
    pw_leaves = [x for x in jax.tree.leaves(
        pk_params, is_leaf=lambda x: isinstance(x, PackedWeight))
        if isinstance(x, PackedWeight)]
    assert pw_leaves and all(
        w.w_packed.dtype == jnp.uint32 for w in pw_leaves)
    packed_b, _ = resident_weight_bytes(pk_params)
    fp_equiv = sum(w.d_in * w.w_packed.shape[-1] *
                   int(np.prod(w.w_packed.shape[:-2], initial=1)) * 4
                   for w in pw_leaves)
    # codes ~= bits/32 of fp32 (+ group params); 4-bit -> well under half
    assert packed_b < 0.5 * fp_equiv

    corpus = SyntheticCorpus(vocab_size=model.cfg.vocab_size, seed=0)
    prompts = corpus.sample(jax.random.key(2), 2, 16)
    ref_tokens = generate(model, deq_params, prompts, 8)

    guard = _Guard(monkeypatch)
    pk_tokens = generate(model, pk_params, prompts, 8)
    assert guard.calls == [], guard.calls
    assert bool(jnp.all(ref_tokens == pk_tokens))


def test_artifact_entries_drive_quant_matmul(artifact_dir):
    """The folded-in kernel check (ex launch.serve._kernel_check): every
    dense 2-D entry's packed codes feed quant_matmul directly and match
    the on-device dequantized matmul — at a decode-ish m=5 so that any
    kernel-eligible entry also exercises the sublane padding
    (``use_kernel=True`` opts into interpret-mode Pallas on CPU for
    aligned shapes; the smoke artifact's d=64 entries take the ref).
    Entries load one at a time through ``load_packed_entry`` (the
    spot-check API _kernel_check used)."""
    meta = json.loads((Path(artifact_dir) / "meta.json").read_text())
    checked = 0
    for name, em in meta["entries"].items():
        if len(em["fields"]["codes"]["shape"]) != 2:
            continue
        entry = cp.load_packed_entry(artifact_dir, name)
        pw = packed_weight_from_artifact(entry, em, meta["spec"])
        x = jax.random.normal(jax.random.key(checked), (5, pw.d_in),
                              jnp.float32)
        y = quant_matmul(x, pw, use_kernel=True)
        ref = x @ cp.dequantize_entry(entry, em, meta["spec"])
        err = float(jnp.max(jnp.abs(y - ref)) /
                    (jnp.max(jnp.abs(ref)) + 1e-9))
        assert err < 1e-5, (name, err)
        checked += 1
    assert checked > 0


def test_v1_artifact_still_loads(artifact_dir, tmp_path):
    """Pre-PR-4 artifacts (rsq-packed-v1: whole-leaf residual, no shard
    index) must keep loading — their packed-entries section is
    byte-identical to v2."""
    import shutil
    d = tmp_path / "v1"
    shutil.copytree(artifact_dir, d)
    meta = json.loads((d / "meta.json").read_text())
    with np.load(d / "residual.npz") as z:
        whole = {f"leaf_{i}": cp._assemble_field(z, f"leaf_{i}", fm)
                 for i, fm in enumerate(meta["residual_leaves"])}
    np.savez(d / "residual.npz", **whole)
    del meta["residual_leaves"]
    meta["format"] = "rsq-packed-v1"
    (d / "meta.json").write_text(json.dumps(meta))

    v2_params, _ = cp.load_packed_params(artifact_dir)
    v1_params, _ = cp.load_packed_params(d)
    assert all(np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in zip(jax.tree.leaves(v2_params),
                               jax.tree.leaves(v1_params)))


def test_residual_written_per_shard(artifact_dir):
    meta = json.loads((Path(artifact_dir) / "meta.json").read_text())
    assert meta["format"] == cp.FORMAT
    assert meta["residual_leaves"], "residual shard index missing"
    with np.load(Path(artifact_dir) / "residual.npz") as z:
        assert all("@" in k for k in z.files)
    for fm in meta["residual_leaves"]:
        assert fm["shards"], fm


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


def test_packed_forward_parity_on_mesh():
    """Keep-packed serving on a (2 data x 4 model) mesh: codes load
    d_out-sharded onto the model axis, the jitted prefill+decode runs
    through the packed pytree under GSPMD, greedy tokens match the local
    dequantized forward, zero dequant calls, and the artifact save never
    gathers a full residual leaf on the controller."""
    out = _run("""
    import dataclasses, json, tempfile
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import get_config
    from repro.core import RSQConfig, RSQPipeline
    from repro.data.synthetic import SyntheticCorpus
    from repro.models import build_model
    from repro.runtime.sharding import ParallelCtx
    from repro.checkpoint import packed as cp
    from repro.launch.serve import generate
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((2, 4), ("data", "model"))
    ctx = ParallelCtx(mesh=mesh, dp=("data",), tp="model")
    cfg = dataclasses.replace(get_config("llama3-8b").reduced(),
                              dtype="float32", n_layers=2, d_model=64,
                              vocab_size=256)
    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.key(0))
    corpus = SyntheticCorpus(vocab_size=256, seed=0)
    calib = corpus.sample(jax.random.key(1), 8, 32)
    rsq = RSQConfig(bits=4, rotate=False, importance="attn_con",
                    pack_output=True, pack_writeback="sharded")
    pipe = RSQPipeline(model, rsq, ctx=ctx)
    qa, _ = pipe.run(params, calib, batch_size=4)

    # shard one residual leaf so the per-shard residual writer is exercised
    qa["embed"] = jax.device_put(qa["embed"],
                                 NamedSharding(mesh, P("model", None)))
    gathers = []
    orig = cp._host_gather
    cp._host_gather = lambda x: (gathers.append(tuple(np.shape(x))),
                                 orig(x))[1]
    d = tempfile.mkdtemp()
    cp.save_packed_artifact(d, pipe.artifact, params=qa)
    cp._host_gather = orig
    meta = json.loads((__import__("pathlib").Path(d) / "meta.json"
                       ).read_text())
    residual_max_shards = max(len(fm["shards"])
                              for fm in meta["residual_leaves"])

    deq_params, _ = cp.load_packed_params(d)
    ref_tokens = generate(model, deq_params, prompts := corpus.sample(
        jax.random.key(2), 2, 16), 8)

    import repro.core.quantizer as qz
    import repro.models.attention as att
    calls = []
    wrap = lambda f: (lambda *a, **k: (calls.append(1), f(*a, **k))[1])
    qz.dequantize_packed = wrap(qz.dequantize_packed)
    cp.dequantize_packed = qz.dequantize_packed
    att.dequantize_packed = qz.dequantize_packed
    cp.dequantize_entry = wrap(cp.dequantize_entry)

    model_m = build_model(cfg, ctx)
    pk_params, _ = cp.load_packed_forward_params(d, ctx=ctx)
    from repro.kernels.quant_matmul.ops import PackedWeight
    n_model_sharded = 0
    flags = []
    for w in jax.tree.leaves(pk_params,
                             is_leaf=lambda x: isinstance(x, PackedWeight)):
        if isinstance(w, PackedWeight):
            idx = {tuple(s.indices(dd)[:2]
                         for s, dd in zip(sh.index, w.w_packed.shape))
                   for sh in w.w_packed.addressable_shards}
            n_model_sharded += len(idx) > 1
            flags.append(w.mesh_sharded)
    pk_tokens = generate(model_m, pk_params, prompts, 8)

    print(json.dumps({
        "save_gathers": gathers,
        "residual_max_shards": residual_max_shards,
        "n_model_sharded_codes": n_model_sharded,
        "mesh_sharded_flags_set": all(flags) and len(flags) > 0,
        "dequant_calls": len(calls),
        "tokens_equal": bool(jnp.all(ref_tokens == pk_tokens)),
    }))
    """)
    assert out["save_gathers"] == []
    assert out["residual_max_shards"] > 1
    assert out["n_model_sharded_codes"] > 0
    # partitioned codes are marked so quant_matmul keeps them off the
    # opaque Pallas call (GSPMD would all-gather it) even on TPU
    assert out["mesh_sharded_flags_set"]
    assert out["dequant_calls"] == 0
    assert out["tokens_equal"]


def test_shard_map_kernel_route_on_mesh():
    """Mesh-sharded packed serving on the shard_map'd Pallas kernel.

    Two layers of pinning on the fake (2 data x 4 model) mesh, kernel
    forced via REPRO_QMM_KERNEL=1 (interpret-mode Pallas on CPU — the
    correctness tool; on TPU the same route runs compiled):

      * unit: a synthetic d_out-sharded PackedWeight through the
        shard_map kernel route is BIT-identical to the GSPMD ref GEMM,
        stays d_out-sharded on the model axis, and triggers zero ref
        calls.
      * serving: a kernel-aligned smoke model (every quantized d_out
        splits into 128-aligned local tiles over the 4-way model axis)
        generates keep-packed with zero ref-GEMM fallbacks — mesh-sharded
        codes no longer demote to the ref when the kernel policy allows —
        and greedy tokens equal to the local dequantized forward.
    """
    out = _run("""
    import dataclasses, functools, json, os, tempfile
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    os.environ["REPRO_QMM_KERNEL"] = "1"

    from repro.configs import get_config
    from repro.core import RSQConfig, RSQPipeline
    from repro.core.quantizer import QuantSpec, quantize_weight_rtn, pack_codes
    from repro.data.synthetic import SyntheticCorpus
    from repro.kernels.quant_matmul import ops
    from repro.models import build_model
    from repro.runtime.sharding import ParallelCtx
    from repro.checkpoint import packed as cp
    from repro.launch.serve import generate
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((2, 4), ("data", "model"))
    ctx = ParallelCtx(mesh=mesh, dp=("data",), tp="model")

    # ---- unit: synthetic sharded weight, kernel route vs GSPMD ref
    k, n = 256, 1024
    w = jax.random.normal(jax.random.key(0), (k, n), jnp.float32)
    deq, q, scale, zero = quantize_weight_rtn(
        w, QuantSpec(bits=4, group_size=128, sym=False))
    sh = NamedSharding(mesh, P(None, "model"))
    pw = ops.PackedWeight(
        jax.device_put(pack_codes(q, 4), sh), jax.device_put(scale, sh),
        jax.device_put(zero, sh), 4, 128, k,
        mesh_sharded=True, mesh=mesh, mesh_axis="model")
    x = jax.random.normal(jax.random.key(1), (5, k), jnp.float32)
    y_ref = ops.quant_matmul(x, pw, use_kernel=False)
    ref_calls, pallas_calls = [], []
    orig_ref, orig_pal = ops.quant_matmul_ref, ops.quant_matmul_pallas
    ops.quant_matmul_ref = lambda *a, **kw: (ref_calls.append(1),
                                             orig_ref(*a, **kw))[1]
    ops.quant_matmul_pallas = lambda *a, **kw: (pallas_calls.append(1),
                                                orig_pal(*a, **kw))[1]
    y_kernel = ops.quant_matmul(x, pw)  # policy: forced kernel + shard_map
    unit = {
        "unit_ref_calls": len(ref_calls),
        "unit_pallas_called": len(pallas_calls) > 0,
        "unit_bit_identical": bool(jnp.all(y_kernel == y_ref)),
        "unit_out_model_sharded": "model" in str(y_kernel.sharding.spec),
    }

    # ---- serving: kernel-aligned smoke model end to end
    cfg = dataclasses.replace(
        get_config("llama3-8b").reduced(), dtype="float32", n_layers=2,
        d_model=512, n_heads=8, n_kv_heads=8, d_head=0, d_ff=512,
        vocab_size=256)
    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.key(0))
    corpus = SyntheticCorpus(vocab_size=256, seed=0)
    calib = corpus.sample(jax.random.key(1), 8, 32)
    pipe = RSQPipeline(model, RSQConfig(bits=4, rotate=False,
                                        importance="attn_con",
                                        pack_output=True,
                                        pack_writeback="sharded"), ctx=ctx)
    qa, _ = pipe.run(params, calib, batch_size=4)
    d = tempfile.mkdtemp()
    cp.save_packed_artifact(d, pipe.artifact, params=qa)
    deq_params, _ = cp.load_packed_params(d)
    prompts = corpus.sample(jax.random.key(2), 2, 16)
    ref_tokens = generate(model, deq_params, prompts, 8)

    model_m = build_model(cfg, ctx)
    pk_params, _ = cp.load_packed_forward_params(d, ctx=ctx)
    ref_calls.clear(); pallas_calls.clear()
    pk_tokens = generate(model_m, pk_params, prompts, 8)
    ops.quant_matmul_ref, ops.quant_matmul_pallas = orig_ref, orig_pal

    print(json.dumps({**unit,
        "serve_ref_fallbacks": len(ref_calls),
        "serve_pallas_traces": len(pallas_calls),
        "tokens_equal": bool(jnp.all(ref_tokens == pk_tokens)),
    }))
    """)
    assert out["unit_ref_calls"] == 0
    assert out["unit_pallas_called"]
    assert out["unit_bit_identical"]
    assert out["unit_out_model_sharded"]
    # the whole serving forward rides the shard_map'd kernel: zero ref
    # GEMMs traced during keep-packed generate
    assert out["serve_ref_fallbacks"] == 0
    assert out["serve_pallas_traces"] > 0
    assert out["tokens_equal"]


def test_depth_cut_recorded_and_enforced(tmp_path):
    """``--n-layers`` keeps the published widths, lands in the artifact's
    metadata, and serving an artifact at another depth is refused."""
    from repro.launch import quantize, serve

    art = tmp_path / "art"
    out = quantize.main([
        "--arch", "llama3-8b-smoke", "--n-layers", "1", "--bits", "4",
        "--n-calib", "4", "--calib-seq", "16", "--batch", "2",
        "--pack-out", str(art)])
    assert out["summary"]["n_layers"] == 1
    _, meta = cp.load_packed_artifact(art)
    assert meta["extra"]["n_layers"] == 1
    serve_args = ["--arch", "llama3-8b-smoke", "--packed", str(art),
                  "--batch", "1", "--prompt-len", "8", "--gen", "2"]
    serve.main(serve_args + ["--n-layers", "1"])
    with pytest.raises(SystemExit):
        serve.main(serve_args)  # full depth: 2 layers
    with pytest.raises(SystemExit):
        quantize.main(["--arch", "llama3-8b-smoke", "--n-layers", "3"])


def test_compile_cache_dir(monkeypatch):
    """``$JAX_COMPILATION_CACHE_DIR`` is left to JAX; otherwise the cache
    goes to one fixed directory in the checkout."""
    from repro.launch import compile_cache

    updates = []
    monkeypatch.setattr(compile_cache.jax.config, "update",
                        lambda *a: updates.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/cache/from/env")
    assert compile_cache.enable_compile_cache() == "/cache/from/env"
    assert updates == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    path = compile_cache.enable_compile_cache()
    assert path == str(REPO / ".jax_cache")
    assert updates == [("jax_compilation_cache_dir", path)]
