"""``quant_matmul``: x (m, k) bf16 times a packed (k, n) weight of
``bits``-bit codes with float32 scale and zero per ``group_size`` rows.
Operations: the bf16 product, 2 m k n.  Bytes: codes, scales and zeros
once, activations in and out once."""

# the kernel's ops in a device trace (``trace_reduce.op_name``): its
# Pallas call, named after the kernel's wrapper
TRACE_NAMES = ("quant_matmul_pallas",)


def cost(m: int, k: int, n: int, bits: int = 4, group_size: int = 128):
    flops = 2.0 * m * k * n
    nbytes = (k * n * bits / 8 + 2 * (k / group_size) * n * 4
              + 2 * m * k + 2 * m * n)
    return flops, nbytes
