"""Quantized KV cache + flash decode: codecs, kernel parity, serving guards.

Pins the PR-7 contract end to end:
  * int8 (kv_bits=8) and packed 2-bit log (kv_bits=2) cache codecs —
    roundtrips, scale shapes, code monotonicity, chunk-leader updates;
  * the Pallas flash-decode kernel is bit-identical to the grouped-einsum
    ref on the same codes (GQA and MLA, both bit widths, edge positions)
    and both match a dense softmax-on-dequantized oracle;
  * serving never materializes the cache in fp: with kv_bits in {8, 2},
    ``generate`` runs with the debug materializers (``kv_dequantize`` /
    ``kv_log_decode``) monkeypatched to count — zero calls;
  * long-context (>= 2k cached tokens) fp-vs-quantized decode parity:
    prefill logits bit-identical (prefill attends in fp), kv8 greedy
    tokens match for several steps, kv2 stays directionally aligned;
  * the fake-8-device mesh leg: split-KV shard_map produces bit-identical
    greedy tokens to the meshless run with zero ref fallbacks.
"""
import dataclasses
import functools
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.kernels.flash_decode.ops as ops
from repro.configs import get_config
from repro.kernels.flash_decode.kernel import (flash_decode_pallas,
                                               mla_flash_decode_pallas)
from repro.kernels.flash_decode.ops import _s_tile
from repro.kernels.flash_decode.ref import (flash_decode_ref,
                                            mla_flash_decode_ref)
from repro.models import attention as att, build_model

REPO = Path(__file__).resolve().parent.parent


def _tiny(arch: str, kv_bits: int = 0):
    return dataclasses.replace(
        get_config(arch).reduced(), dtype="float32", n_layers=2,
        d_model=64, vocab_size=256, kv_bits=kv_bits)


def _randn(rng, *shape):
    return jnp.asarray(rng.normal(size=shape), jnp.float32)


# ------------------------------------------------------------------ codecs


def test_kv8_roundtrip_and_scale_shape():
    rng = np.random.default_rng(0)
    x = _randn(rng, 2, 96, 2, 16)
    codes, scales = att.kv_quantize(x)
    assert codes.shape == x.shape and codes.dtype == jnp.int8
    assert scales.shape == x.shape[:-1] and scales.dtype == jnp.bfloat16
    y = att.kv_dequantize(codes, scales, jnp.float32)
    amax = np.abs(np.asarray(x)).max(-1, keepdims=True)
    # half-step of the int8 grid plus the bf16 scale rounding
    assert float(jnp.max(jnp.abs(y - x) / amax)) < 1.0 / 127


def test_kv_pack_unpack_roundtrip_ragged():
    rng = np.random.default_rng(1)
    for d in (16, 17, 31, 48):
        codes = jnp.asarray(rng.integers(0, 4, size=(2, 70, d)), jnp.int32)
        words = att.kv_pack(codes)
        assert words.dtype == jnp.uint32
        assert words.shape == (2, 70, -(-d // 16))
        assert bool(jnp.all(att.kv_unpack(words, d) == codes))


def test_kv_log_codes_monotone_and_max():
    # levels (-1, -1/4, 1/4, 1): codes order by value, extremes saturate
    scale = jnp.full((1, 8, 1), 2.0, jnp.float32)
    x = jnp.linspace(-3.0, 3.0, 8, dtype=jnp.float32)[None, :, None]
    codes = att._kv_log_codes(x, scale[..., 0])
    seq = np.asarray(codes)[0, :, 0]
    assert (np.diff(seq) >= 0).all()
    assert seq.min() >= 0 and seq.max() <= 3
    assert seq[0] == 0 and seq[-1] == 3          # |x| > s/2 -> outer level
    near_zero = att._kv_log_codes(
        jnp.asarray([[[0.01], [-0.01]]], jnp.float32), scale[:, :2, 0])
    assert np.asarray(near_zero).ravel().tolist() == [2, 1]  # inner levels


def test_kv_log_roundtrip_levels_and_scale_shape():
    rng = np.random.default_rng(2)
    x = _randn(rng, 2, 130, 3, 16)
    scales = att.kv_log_scales(x, 64)
    assert scales.shape == (2, 3, 3) and scales.dtype == jnp.bfloat16
    packed = att.kv_log_encode(x, scales, 64)
    y = att.kv_log_decode(packed, scales, d=16, chunk=64)
    sx, sy = np.sign(np.asarray(x)), np.sign(np.asarray(y))
    assert (sx[sx != 0] == sy[sx != 0]).all()    # signs always survive
    # every decoded value is scale * level for a level in the log grid
    s_tok = np.repeat(np.asarray(scales, np.float32), 64, axis=1)[:, :130]
    ratio = np.abs(np.asarray(y)) / s_tok[..., None]
    assert np.allclose(np.minimum(np.abs(ratio - 0.25), np.abs(ratio - 1.0)),
                       0.0, atol=1e-2)


def test_kv_cache_update_chunk_leader():
    rng = np.random.default_rng(3)
    x = _randn(rng, 2, 192, 2, 16)
    codes, scales = att.kv_cache_quantize(x[:, :128], kv_bits=2, chunk=64)
    codes = jnp.concatenate([codes, jnp.zeros_like(codes[:, :64])], 1)
    scales = jnp.concatenate([scales, jnp.zeros_like(scales[:, :1])], 1)
    for t in range(128, 140):
        codes, scales = att.kv_cache_update(
            codes, scales, x[:, t:t + 1], jnp.int32(t), kv_bits=2, chunk=64)
    full_c, full_s = att.kv_cache_quantize(x, kv_bits=2, chunk=64)
    # prefill rows and whole-chunk scales are untouched by appends
    assert bool(jnp.all(codes[:, :128] == full_c[:, :128]))
    assert bool(jnp.all(scales[:, :2] == full_s[:, :2]))
    # the appended chunk's leader stamped its own amax as the scale
    lead = jnp.max(jnp.abs(x[:, 128].astype(jnp.float32)), -1)
    assert bool(jnp.all(scales[:, 2] == lead.astype(jnp.bfloat16)))
    # appended rows round-trip signs against the stamped scale
    y = att.kv_log_decode(codes, scales, d=16, chunk=64)[:, 128:140]
    sx = np.sign(np.asarray(x[:, 128:140]))
    assert (sx[sx != 0] == np.sign(np.asarray(y))[sx != 0]).all()


def test_model_rejects_unsupported_kv_bits():
    with pytest.raises(ValueError, match="kv_bits"):
        build_model(_tiny("llama3-8b", kv_bits=4))


# -------------------------------------------------- kernel == ref (bitwise)


@pytest.mark.parametrize("kv_bits,chunk", [(8, 1), (2, 64)])
@pytest.mark.parametrize("pos", [0, 150, 191])
def test_gqa_kernel_bitwise_matches_ref(kv_bits, chunk, pos):
    rng = np.random.default_rng(4)
    B, S, KV, G, DH = 2, 192, 2, 4, 16
    k, v = _randn(rng, B, S, KV, DH), _randn(rng, B, S, KV, DH)
    q = _randn(rng, B, KV, G, DH)
    kq, ks = att.kv_cache_quantize(k, kv_bits=kv_bits, chunk=chunk)
    vq, vs = att.kv_cache_quantize(v, kv_bits=kv_bits, chunk=chunk)
    p = jnp.full((1, 1), pos, jnp.int32)
    s_blk = _s_tile(S, chunk)
    kw = dict(kv_bits=kv_bits, chunk=chunk, dh=DH, dv=DH, s_blk=s_blk)
    a = flash_decode_pallas(q, kq, ks, vq, vs, p, **kw)
    b = flash_decode_ref(q, kq, ks, vq, vs, p, **kw)
    for x, y in zip(a, b):  # (acc, m, l) partials, bit-for-bit
        assert bool(jnp.all(x == y))


@pytest.mark.parametrize("kv_bits,chunk", [(8, 1), (2, 64)])
@pytest.mark.parametrize("pos", [0, 150, 191])
def test_mla_kernel_bitwise_matches_ref(kv_bits, chunk, pos):
    rng = np.random.default_rng(5)
    B, S, H, DL, DR = 2, 192, 4, 24, 8
    ql, qr = _randn(rng, B, H, DL), _randn(rng, B, H, DR)
    c, r = _randn(rng, B, S, DL), _randn(rng, B, S, DR)
    cq, cs = att.kv_cache_quantize(c, kv_bits=kv_bits, chunk=chunk)
    rq, rs = att.kv_cache_quantize(r, kv_bits=kv_bits, chunk=chunk)
    p = jnp.full((1, 1), pos, jnp.int32)
    kw = dict(kv_bits=kv_bits, chunk=chunk, dl=DL, dr=DR,
              s_blk=_s_tile(S, chunk))
    a = mla_flash_decode_pallas(ql, qr, cq, cs, rq, rs, p, **kw)
    b = mla_flash_decode_ref(ql, qr, cq, cs, rq, rs, p, **kw)
    for x, y in zip(a, b):
        assert bool(jnp.all(x == y))


@pytest.mark.parametrize("kv_bits,chunk", [(8, 1), (2, 64)])
def test_flash_decode_matches_dense_oracle(kv_bits, chunk):
    rng = np.random.default_rng(6)
    B, S, KV, G, DH, pos = 2, 192, 2, 4, 16, 150
    k, v = _randn(rng, B, S, KV, DH), _randn(rng, B, S, KV, DH)
    q = _randn(rng, B, KV, G, DH)
    kq, ks = att.kv_cache_quantize(k, kv_bits=kv_bits, chunk=chunk)
    vq, vs = att.kv_cache_quantize(v, kv_bits=kv_bits, chunk=chunk)
    if kv_bits == 8:
        kd = att.kv_dequantize(kq, ks, jnp.float32)
        vd = att.kv_dequantize(vq, vs, jnp.float32)
    else:
        kd = att.kv_log_decode(kq, ks, d=DH, chunk=chunk)
        vd = att.kv_log_decode(vq, vs, d=DH, chunk=chunk)
    s = jnp.einsum("bkgd,bskd->bkgs", q, kd.astype(jnp.float32))
    s = jnp.where(jnp.arange(S)[None, None, None, :] <= pos, s, -1e30)
    oracle = jnp.einsum("bkgs,bskd->bkgd", jax.nn.softmax(s, axis=-1),
                        vd.astype(jnp.float32))
    for use_kernel in (False, True):
        out = ops.flash_decode(q, kq, ks, vq, vs, jnp.int32(pos),
                               kv_bits=kv_bits, chunk=chunk, dv=DH,
                               use_kernel=use_kernel)
        # kv8's debug dequant multiplies in bf16; the kernel stays f32
        assert float(jnp.max(jnp.abs(out - oracle))) < (
            0.05 if kv_bits == 8 else 1e-4)


# ------------------------------------------------------- serving, no-fp pin


def test_cache_layout_dtypes_and_rounding():
    for arch, keys in (("llama3-8b", ("k", "ks", "v", "vs")),
                       ("deepseek-v2-236b", ("c", "cs", "r", "rs"))):
        for bits, code_dt, rows in ((8, jnp.int8, 100), (2, jnp.uint32, 2)):
            model = build_model(_tiny(arch, kv_bits=bits))
            assert model._cache_len(100) == 128  # rounds up to kv_chunk
            cache = jax.eval_shape(lambda m=model: m.init_cache(2, 100))
            entry = cache["groups"]["b0"]
            assert set(keys) <= set(entry)
            for key in keys:
                leaf = entry[key]
                want = code_dt if len(key) == 1 else jnp.bfloat16
                assert leaf.dtype == want, (arch, bits, key)
                n = leaf.shape[2]  # (n_groups, batch, rows, ...)
                assert n == (128 if len(key) == 1 else
                             128 if bits == 8 else rows), (arch, bits, key)


@pytest.mark.parametrize("arch,kv_bits", [("llama3-8b", 8), ("llama3-8b", 2),
                                          ("deepseek-v2-236b", 8),
                                          ("deepseek-v2-236b", 2)])
def test_generate_never_materializes_fp_cache(arch, kv_bits, monkeypatch):
    from repro.launch.serve import generate

    calls = []

    def wrap(tag, fn):
        return lambda *a, **k: (calls.append(tag), fn(*a, **k))[1]

    monkeypatch.setattr(att, "kv_dequantize",
                        wrap("kv_dequantize", att.kv_dequantize))
    monkeypatch.setattr(att, "kv_log_decode",
                        wrap("kv_log_decode", att.kv_log_decode))
    model = build_model(_tiny(arch, kv_bits=kv_bits))
    params = jax.jit(model.init)(jax.random.key(0))
    rng = np.random.default_rng(7)
    prompts = jnp.asarray(rng.integers(0, 256, size=(2, 33)), jnp.int32)
    toks = generate(model, params, prompts, 5)
    assert toks.shape == (2, 5)
    assert calls == []  # the cache is attended in codes, never in fp


# ------------------------------------------- long-context decode parity


@functools.lru_cache(maxsize=None)
def _long_ctx_logits(arch: str, t: int, n_steps: int = 3):
    """{kv_bits: [prefill_logits, step0, step1, ...]} greedy decode."""
    rng = np.random.default_rng(8)
    toks = jnp.asarray(rng.integers(0, 256, size=(2, t)), jnp.int32)
    out = {}
    for bits in (0, 8, 2):
        model = build_model(_tiny(arch, kv_bits=bits))
        params = jax.jit(model.init)(jax.random.key(0))
        logits, cache = jax.jit(
            lambda p, tk, m=model: m.prefill(p, tk, cache_len=t + n_steps)
        )(params, toks)
        seq = [logits]
        step = jax.jit(model.decode_step)
        for i in range(n_steps):
            tok = jnp.argmax(seq[-1], -1).astype(jnp.int32)[:, None]
            logits, cache = step(params, cache, tok, jnp.int32(t + i))
            seq.append(logits)
        out[bits] = seq
    return out


@pytest.mark.parametrize("arch,t", [("llama3-8b", 2040),
                                    ("deepseek-v2-236b", 2040),
                                    ("qwen1.5-4b", 120)])
def test_long_context_decode_parity(arch, t):
    out = _long_ctx_logits(arch, t)
    fp, kv8, kv2 = out[0], out[8], out[2]
    # prefill attends in fp: logits bit-identical for every kv_bits
    assert bool(jnp.all(fp[0] == kv8[0])) and bool(jnp.all(fp[0] == kv2[0]))
    # int8 KV: greedy tokens match step for step, logits stay tight
    for a, b in zip(fp[1:], kv8[1:]):
        assert bool(jnp.all(jnp.argmax(a, -1) == jnp.argmax(b, -1)))
        assert float(jnp.max(jnp.abs(a - b))) < 0.15
    # 2-bit KV is coarse (random-init weights, near-uniform logits): pin
    # directional alignment of the first decode step, not token identity
    a, b = fp[1], kv2[1]
    ac = a - a.mean(-1, keepdims=True)
    bc = b - b.mean(-1, keepdims=True)
    cos = jnp.sum(ac * bc, -1) / (jnp.linalg.norm(ac, axis=-1)
                                  * jnp.linalg.norm(bc, axis=-1))
    assert float(jnp.mean(cos)) > 0.5 and float(jnp.min(cos)) > 0.2


def test_long_context_cache_stays_quantized():
    # >= 2k cached tokens end to end through generate, cache dtypes pinned
    out = _long_ctx_logits("llama3-8b", 2040)
    assert len(out[8]) == 4  # prefill + 3 decode steps actually ran
    model = build_model(_tiny("llama3-8b", kv_bits=2))
    cache = jax.eval_shape(lambda: model.init_cache(1, 2048))
    entry = cache["groups"]["b0"]
    assert entry["k"].dtype == jnp.uint32 and entry["k"].shape[2] == 2048
    assert entry["ks"].dtype == jnp.bfloat16 and entry["ks"].shape[2] == 32


# ---------------------------------------------------- fake-8-device mesh


def _run(code: str) -> dict:
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=str(REPO / "src"), REPRO_FD_KERNEL="1")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=560,
                         env=env)
    assert out.returncode == 0, out.stderr
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_mesh_split_kv_zero_ref_fallbacks():
    """Aligned long-context decode on a (2, 4) mesh: the split-KV kernel
    serves every step (zero scan-ref fallbacks) and greedy tokens are
    bit-identical to the meshless run."""
    res = _run("""
        import dataclasses, json
        import numpy as np
        import jax, jax.numpy as jnp
        import repro.kernels.flash_decode.ops as ops
        from repro.configs import get_config
        from repro.models import build_model
        from repro.runtime.sharding import ParallelCtx
        from repro.launch.mesh import make_mesh
        from repro.launch.serve import generate

        calls = []
        for name in ("flash_decode_ref", "mla_flash_decode_ref"):
            orig = getattr(ops, name)
            setattr(ops, name, (lambda o: lambda *a, **k:
                                (calls.append(1), o(*a, **k))[1])(orig))

        cfg = dataclasses.replace(
            get_config("llama3-8b").reduced(), dtype="float32",
            n_layers=2, d_model=64, vocab_size=256, kv_bits=8)
        rng = np.random.default_rng(0)
        prompts = jnp.asarray(rng.integers(0, 256, size=(2, 1020)),
                              jnp.int32)
        model0 = build_model(cfg)
        params = jax.jit(model0.init)(jax.random.key(0))
        local = np.asarray(generate(model0, params, prompts, 4))
        n0 = len(calls)

        mesh = make_mesh((2, 4), ("data", "model"))
        ctx = ParallelCtx(mesh=mesh, dp=("data",), tp="model")
        meshed = np.asarray(generate(build_model(cfg, ctx), params,
                                     prompts, 4))
        print(json.dumps({
            "match": bool((local == meshed).all()),
            "ref_calls_local": n0,
            "ref_calls_mesh": len(calls) - n0,
        }))
    """)
    assert res["match"] is True
    assert res["ref_calls_local"] == 0  # REPRO_FD_KERNEL=1 forces the kernel
    assert res["ref_calls_mesh"] == 0   # aligned split-KV never demotes


def test_mesh_misaligned_takes_gspmd_ref():
    """A sequence the model axis can't split cleanly demotes to the
    GSPMD-partitionable scan ref — counted, and still correct."""
    res = _run("""
        import json
        import numpy as np
        import jax, jax.numpy as jnp
        import repro.kernels.flash_decode.ops as ops
        from repro.launch.mesh import make_mesh
        from repro.models import attention as att

        calls = []
        orig = ops.flash_decode_ref
        ops.flash_decode_ref = (
            lambda *a, **k: (calls.append(1), orig(*a, **k))[1])

        mesh = make_mesh((2, 4), ("data", "model"))
        rng = np.random.default_rng(0)
        B, S, KV, G, DH = 2, 192, 2, 4, 16  # s_loc = 48: chunk straddles
        k = jnp.asarray(rng.normal(size=(B, S, KV, DH)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, S, KV, DH)), jnp.float32)
        q = jnp.asarray(rng.normal(size=(B, KV, G, DH)), jnp.float32)
        kq, ks = att.kv_cache_quantize(k, kv_bits=2, chunk=64)
        vq, vs = att.kv_cache_quantize(v, kv_bits=2, chunk=64)
        args = (q, kq, ks, vq, vs, jnp.int32(100))
        kw = dict(kv_bits=2, chunk=64, dv=DH)
        ref = ops.flash_decode(*args, **kw, use_kernel=False)
        n0 = len(calls)
        out = ops.flash_decode(*args, **kw, mesh=mesh, axis="model",
                               dp="data")
        from repro.kernels import demotions
        print(json.dumps({
            "ref_calls": len(calls) - n0,
            "maxdiff": float(jnp.max(jnp.abs(out - ref))),
            "demotions": demotions.counts(),
        }))
    """)
    assert res["ref_calls"] == 1
    assert res["maxdiff"] < 1e-5
    # the kernel was asked for (REPRO_FD_KERNEL=1): the demotion is counted
    assert res["demotions"] == {
        "flash_decode: untileable (S=192, chunk=64, mesh=True)": 1}
