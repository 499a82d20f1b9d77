"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.quantizer import QuantSpec, quantize_weight_rtn
from repro.core.rotation import random_orthogonal
from repro.kernels.attn_colsum.ops import attn_colsum
from repro.kernels.attn_colsum.ref import attn_colsum_ref
from repro.kernels.gram.ops import weighted_gram
from repro.kernels.gram.ref import weighted_gram_ref
from repro.kernels.hadamard.ops import fwht, hadamard_transform
from repro.kernels.hadamard.ref import fwht_ref, hadamard_matrix
from repro.kernels.quant_matmul.ops import pack_weight, quant_matmul
from repro.kernels.quant_matmul.ref import quant_matmul_ref


@pytest.mark.parametrize("shape", [(8, 64), (16, 128), (4, 512), (3, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fwht_vs_ref(shape, dtype):
    x = jax.random.normal(jax.random.key(sum(shape)), shape).astype(dtype)
    a = fwht(x).astype(jnp.float32)
    b = fwht_ref(x).astype(jnp.float32)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=tol,
                               rtol=tol)


def test_fwht_involution_and_kron():
    x = jax.random.normal(jax.random.key(0), (6, 256))
    np.testing.assert_allclose(np.asarray(fwht(fwht(x))), np.asarray(x),
                               atol=1e-5)
    d, m = 384, 3
    x = jax.random.normal(jax.random.key(1), (5, d))
    qm = random_orthogonal(jax.random.key(2), m)
    y = hadamard_transform(x, qm)
    ref = x @ jnp.asarray(np.kron(np.asarray(hadamard_matrix(128)),
                                  np.asarray(qm)))
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-4)


@pytest.mark.parametrize("n,d", [(512, 128), (1024, 256), (256, 512),
                                 (100, 96)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gram_vs_ref(n, d, dtype):
    x = jax.random.normal(jax.random.key(n + d), (n, d)).astype(dtype)
    r = jax.random.uniform(jax.random.key(d), (n,))
    a = weighted_gram(x, r)
    b = weighted_gram_ref(x, r)
    rel = float(jnp.abs(a - b).max() / jnp.abs(b).max())
    assert rel < (1e-5 if dtype == jnp.float32 else 3e-2)


@pytest.mark.parametrize("bits", [2, 4, 8])
@pytest.mark.parametrize("k,n,m,gs", [(512, 256, 16, 128), (256, 128, 8, 64),
                                      (1024, 512, 32, 128)])
def test_quant_matmul_vs_ref(bits, k, n, m, gs):
    w = jax.random.normal(jax.random.key(bits + k), (k, n)) * 0.4
    spec = QuantSpec(bits=bits, group_size=gs, sym=False)
    deq, q, s, z = quantize_weight_rtn(w, spec)
    pw = pack_weight(q, s, z, spec)
    x = jax.random.normal(jax.random.key(m), (m, k))
    a = quant_matmul(x, pw, use_kernel=True)  # interpret-mode Pallas off-TPU
    b = quant_matmul_ref(x, pw.w_packed, s, z, bits=bits, group_size=gs)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-3,
                               rtol=1e-3)
    np.testing.assert_allclose(np.asarray(a), np.asarray(x @ deq), atol=1e-2,
                               rtol=1e-2)


@pytest.mark.parametrize("bits", [2, 4, 8])
@pytest.mark.parametrize("k,d,m,gs", [(512, 256, 16, 128), (256, 128, 5, 64),
                                      (128, 512, 3, 128)])
def test_quant_matmul_t_vs_ref(bits, k, d, m, gs):
    """Latent layout (y = x @ dequant(W)ᵀ, MLA absorbed decode): kernel
    (interpret) vs ref vs the dense transposed product.  m covers decode
    shapes below the sublane tile (padded inside the wrapper)."""
    from repro.kernels.quant_matmul.ops import quant_matmul_t
    from repro.kernels.quant_matmul.ref import quant_matmul_t_ref

    w = jax.random.normal(jax.random.key(bits + k), (k, d)) * 0.4
    spec = QuantSpec(bits=bits, group_size=gs, sym=False)
    deq, q, s, z = quantize_weight_rtn(w, spec)
    pw = pack_weight(q, s, z, spec)
    x = jax.random.normal(jax.random.key(m), (m, d))
    a = quant_matmul_t(x, pw, use_kernel=True)
    b = quant_matmul_t_ref(x, pw.w_packed, s, z, bits=bits, group_size=gs,
                           d_in=k)
    assert a.shape == (m, k)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-3,
                               rtol=1e-3)
    np.testing.assert_allclose(np.asarray(a), np.asarray(x @ deq.T),
                               atol=1e-2, rtol=1e-2)


@pytest.mark.parametrize("m", [1, 2, 5, 7])
def test_quant_matmul_decode_shapes_stay_on_kernel(m, monkeypatch):
    """Decode-time m (batch of generating sequences, not a sublane
    multiple of 8) must pad up inside the wrapper and stay on the Pallas
    kernel — never bounce to the unfused ref path."""
    import repro.kernels.quant_matmul.ops as ops

    k, n = 256, 128
    spec = QuantSpec(bits=4, group_size=64, sym=False)
    w = jax.random.normal(jax.random.key(m), (k, n)) * 0.4
    deq, q, s, z = quantize_weight_rtn(w, spec)
    pw = pack_weight(q, s, z, spec)

    def boom(*a, **kw):
        raise AssertionError("decode shape fell back to quant_matmul_ref")

    monkeypatch.setattr(ops, "quant_matmul_ref", boom)
    x = jax.random.normal(jax.random.key(m + 100), (m, k))
    y = quant_matmul(x, pw, use_kernel=True)
    assert y.shape == (m, n)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x @ deq),
                               atol=1e-2, rtol=1e-2)


def test_quant_matmul_per_tensor_groups_fall_back():
    """group_size=-1 (one group spanning d_in) with d_in > 2048: no k tile
    can hold a whole group, so the wrapper must serve via ref instead of
    looping its block size down to zero — and count the demotion."""
    from repro.kernels import demotions

    k, n = 4096, 128
    spec = QuantSpec(bits=4, group_size=-1, sym=True)
    w = jax.random.normal(jax.random.key(5), (k, n)) * 0.4
    deq, q, s, z = quantize_weight_rtn(w, spec)
    pw = pack_weight(q, s, z, spec)
    assert pw.group_size == k
    x = jax.random.normal(jax.random.key(6), (8, k))
    demotions.reset()
    np.testing.assert_allclose(
        np.asarray(quant_matmul(x, pw, use_kernel=True)),
        np.asarray(x @ deq), atol=1e-2, rtol=1e-2)
    assert list(demotions.counts()) == [
        "quant_matmul: untileable (k=4096, n=128, bits=4, group=4096)"]


def test_quant_matmul_3bit_falls_back():
    k, n = 256, 128
    spec = QuantSpec(bits=3, group_size=64, sym=True)
    w = jax.random.normal(jax.random.key(3), (k, n)) * 0.4
    deq, q, s, z = quantize_weight_rtn(w, spec)
    pw = pack_weight(q, s, z, spec)
    x = jax.random.normal(jax.random.key(4), (8, k))
    np.testing.assert_allclose(np.asarray(quant_matmul(x, pw, use_kernel=True)),
                               np.asarray(x @ deq), atol=1e-2, rtol=1e-2)


@pytest.mark.parametrize("b,t,h,dh,causal", [
    (2, 128, 4, 32, True), (1, 256, 2, 64, True), (2, 64, 4, 16, False),
    (1, 96, 2, 32, True)])
def test_attn_colsum_vs_ref(b, t, h, dh, causal):
    q = jax.random.normal(jax.random.key(t), (b, t, h, dh))
    k = jax.random.normal(jax.random.key(t + 1), (b, t, h, dh))
    col = attn_colsum(q, k, causal=causal)
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, t, dh)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, t, dh)
    ref = attn_colsum_ref(qf, kf, causal=causal).reshape(b, h, t).sum(1)
    rel = float(jnp.abs(col - ref).max() / jnp.abs(ref).max())
    assert rel < 1e-5
    # column mass conserves: sum_j R_j == queries x heads
    np.testing.assert_allclose(float(col.sum()), b * t * h, rtol=1e-4)


def test_attn_colsum_gqa():
    b, t, h, kvh, dh = 1, 64, 4, 2, 16
    q = jax.random.normal(jax.random.key(0), (b, t, h, dh))
    k = jax.random.normal(jax.random.key(1), (b, t, kvh, dh))
    col = attn_colsum(q, k)
    assert col.shape == (b, t)
    np.testing.assert_allclose(float(col.sum()), b * t * h, rtol=1e-4)
