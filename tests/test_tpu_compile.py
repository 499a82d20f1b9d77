"""Compile rehearsals: the Pallas kernels of the serving and quantizing
paths, lowered for a described TPU v5e at llama3-8b widths.

Interpret mode (every other kernel test) never runs Mosaic, so it cannot
see a block shape the chip's compiler refuses or a tile that overflows
VMEM; these compiles can, with no chip attached.  Each test drives the
public wrapper with the kernel forced and interpret mode off, so the
wrapper's own tile choice is what gets compiled, and checks that the
program holds the kernel (``tpu_custom_call``) rather than a demotion to
the reference.

The topology is described only inside the module fixture, never at
import: loading the TPU compiler library takes a process-wide lock, and
only the test worker that is given this file may take it.  Keep every
such compile in this one file for the same reason.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import demotions
from repro.kernels.flash_decode import ops as fd_ops
from repro.kernels.gram import ops as gram_ops
from repro.kernels.quant_matmul import ops as qmm_ops

D_MODEL, D_FF, N_HEADS, N_KV, D_HEAD = 4096, 14336, 32, 8, 128  # llama3-8b
GROUP, PAGE, CHUNK2 = 128, 64, 64


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent
        # cache but can never be read back without one: keep it off
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture
def on_chip(monkeypatch):
    """Wrappers pick interpret mode from the host backend (CPU here):
    steer them to Mosaic for the compile."""
    for mod in (qmm_ops, fd_ops, gram_ops):
        monkeypatch.setattr(mod, "_interpret", lambda: False)
    demotions.reset()
    yield
    assert demotions.counts() == {}


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo, "the kernel was not in the program"


# ------------------------------------------------------------ quant_matmul


@pytest.mark.parametrize("k,n", [(D_MODEL, D_MODEL), (D_MODEL, 1024),
                                 (D_MODEL, D_FF), (D_FF, D_MODEL)],
                         ids=["q_o", "k_v", "gate_up", "down"])
@pytest.mark.parametrize("m", [4096, 8], ids=["prefill", "decode"])
def test_quant_matmul_lowers(one_chip, on_chip, k, n, m):
    def fn(x, wq, sc, zr):
        pw = qmm_ops.PackedWeight(wq, sc, zr, bits=4, group_size=GROUP,
                                  d_in=k)
        return qmm_ops.quant_matmul(x, pw, use_kernel=True)

    _compile(fn, one_chip, ((m, k), jnp.bfloat16), ((k // 8, n), jnp.uint32),
             ((k // GROUP, n), jnp.float32), ((k // GROUP, n), jnp.float32))


def test_quant_matmul_t_lowers(one_chip, on_chip):
    """The latent layout of MLA's absorbed decode (kv_lora_rank 512
    packed, one 128-wide head of ``wkv_b``)."""
    kvr, dn = 512, 128

    def fn(x, wq, sc, zr):
        pw = qmm_ops.PackedWeight(wq, sc, zr, bits=4, group_size=GROUP,
                                  d_in=kvr)
        return qmm_ops.quant_matmul_t(x, pw, use_kernel=True)

    _compile(fn, one_chip, ((8, dn), jnp.bfloat16),
             ((kvr // 8, dn), jnp.uint32), ((kvr // GROUP, dn), jnp.float32),
             ((kvr // GROUP, dn), jnp.float32))


# ----------------------------------------------------------- flash decode


def _kv_shapes(kv_bits, lead, d=D_HEAD, heads=(N_KV,)):
    """(codes, scales) shapes of a cache with leading dims ``lead``."""
    chunk = 1 if kv_bits == 8 else CHUNK2
    w, dt = (d, jnp.int8) if kv_bits == 8 else (-(-d // 16), jnp.uint32)
    *outer, rows = lead
    return ((*lead, *heads, w), dt), \
        ((*outer, rows // chunk, *heads), jnp.bfloat16), chunk


@pytest.mark.parametrize("kv_bits", [8, 2])
def test_flash_decode_lowers(one_chip, on_chip, kv_bits):
    b, s = 8, 2048
    codes, scales, chunk = _kv_shapes(kv_bits, (b, s))
    fn = functools.partial(fd_ops.flash_decode, kv_bits=kv_bits, chunk=chunk,
                           dv=D_HEAD, use_kernel=True)
    _compile(lambda q, kq, ks, vq, vs, p: fn(q, kq, ks, vq, vs, p), one_chip,
             ((b, N_KV, N_HEADS // N_KV, D_HEAD), jnp.float32),
             codes, scales, codes, scales, ((), jnp.int32))


@pytest.mark.parametrize(
    "kv_bits,b,n_pages,n_tiles",
    [(8, 8, 256, 32), (2, 8, 256, 32),
     # the chat cell: 24 slots, 72-entry tables, 420 pages (444 items)
     (8, 24, 420, 72), (2, 24, 420, 72)],
    ids=["8", "2", "chat-8", "chat-2"])
def test_paged_flash_decode_lowers(one_chip, on_chip, kv_bits, b, n_pages,
                                   n_tiles):
    codes, scales, chunk = _kv_shapes(kv_bits, (n_pages, PAGE))
    fn = functools.partial(fd_ops.paged_flash_decode, kv_bits=kv_bits,
                           chunk=chunk, dv=D_HEAD, page=PAGE,
                           use_kernel=True)
    _compile(fn, one_chip, ((b, n_tiles), jnp.int32), ((b,), jnp.int32),
             ((b, N_KV, N_HEADS // N_KV, D_HEAD), jnp.float32),
             codes, scales, codes, scales)


@pytest.mark.parametrize("kv_bits", [8, 2])
def test_paged_flash_extend_lowers(one_chip, on_chip, kv_bits):
    """A 256-token prefill chunk over 4 past pages."""
    n_pages, n_past, L = 256, 4, 256
    codes, scales, chunk = _kv_shapes(kv_bits, (n_pages, PAGE))

    def fn(tbl, q, kn, vn, kq, ks, vq, vs):
        return fd_ops.paged_flash_extend(
            tbl, q, kn, vn, kq, ks, vq, vs, n_past * PAGE, kv_bits=kv_bits,
            chunk=chunk, dh=D_HEAD, dv=D_HEAD, page=PAGE, use_kernel=True)

    _compile(fn, one_chip, ((n_past,), jnp.int32),
             ((1, L, N_HEADS, D_HEAD), jnp.bfloat16),
             ((1, L, N_KV, D_HEAD), jnp.bfloat16),
             ((1, L, N_KV, D_HEAD), jnp.bfloat16),
             codes, scales, codes, scales)


# MLA at deepseek-v2 latent widths (kv_lora_rank 512, rope 64), 16 heads.
MLA_H, MLA_DL, MLA_DR = 16, 512, 64


def _mla_cache(kv_bits, lead):
    c, cs, chunk = _kv_shapes(kv_bits, lead, d=MLA_DL, heads=())
    r, rs, _ = _kv_shapes(kv_bits, lead, d=MLA_DR, heads=())
    return (c, cs, r, rs), chunk


@pytest.mark.parametrize("kv_bits", [8, 2])
def test_mla_flash_decode_lowers(one_chip, on_chip, kv_bits):
    b, s = 8, 2048
    cache, chunk = _mla_cache(kv_bits, (b, s))
    fn = functools.partial(fd_ops.mla_flash_decode, kv_bits=kv_bits,
                           chunk=chunk, dl=MLA_DL, dr=MLA_DR,
                           use_kernel=True)
    _compile(lambda ql, qr, cq, cs, rq, rs, p: fn(ql, qr, cq, cs, rq, rs, p),
             one_chip, ((b, MLA_H, MLA_DL), jnp.float32),
             ((b, MLA_H, MLA_DR), jnp.float32), *cache, ((), jnp.int32))


@pytest.mark.parametrize("kv_bits", [8, 2])
def test_paged_mla_flash_decode_lowers(one_chip, on_chip, kv_bits):
    b, n_pages, n_tiles = 8, 256, 32
    cache, chunk = _mla_cache(kv_bits, (n_pages, PAGE))
    fn = functools.partial(fd_ops.paged_mla_flash_decode, kv_bits=kv_bits,
                           chunk=chunk, dl=MLA_DL, dr=MLA_DR, page=PAGE,
                           use_kernel=True)
    _compile(fn, one_chip, ((b, n_tiles), jnp.int32), ((b,), jnp.int32),
             ((b, MLA_H, MLA_DL), jnp.float32),
             ((b, MLA_H, MLA_DR), jnp.float32), *cache)


@pytest.mark.parametrize("kv_bits", [8, 2])
def test_paged_mla_flash_extend_lowers(one_chip, on_chip, kv_bits):
    n_pages, n_past, L = 256, 4, 64
    cache, chunk = _mla_cache(kv_bits, (n_pages, PAGE))

    def fn(tbl, ql, qr, cn, rn, cq, cs, rq, rs):
        return fd_ops.paged_mla_flash_extend(
            tbl, ql, qr, cn, rn, cq, cs, rq, rs, n_past * PAGE,
            kv_bits=kv_bits, chunk=chunk, dl=MLA_DL, dr=MLA_DR, page=PAGE,
            use_kernel=True)

    _compile(fn, one_chip, ((n_past,), jnp.int32),
             ((L, MLA_H, MLA_DL), jnp.float32),
             ((L, MLA_H, MLA_DR), jnp.float32),
             ((L, MLA_DL), jnp.bfloat16), ((L, MLA_DR), jnp.bfloat16),
             *cache)


# ------------------------------------------------------------------ gram


@pytest.mark.parametrize("d", [D_MODEL, D_FF], ids=["d_model", "d_ff"])
def test_weighted_gram_lowers(one_chip, on_chip, d):
    """Hessian accumulation over one calibration batch (2 x 2048 tokens)."""
    _compile(gram_ops.weighted_gram, one_chip, ((4096, d), jnp.float32))
