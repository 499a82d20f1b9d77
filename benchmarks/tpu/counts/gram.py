"""``gram``: H += 2 (R X)^T (R X) for n float32 rows of width d.
Operations: the product 2 n d^2, counted once (the passes that full
float32 precision takes on a bf16 unit are not counted).  Bytes: the
rows in, the (d, d) float32 accumulator in and out."""

# the kernel's ops in a device trace (``trace_reduce.op_name``): its
# Pallas call, named after the kernel's wrapper
TRACE_NAMES = ("weighted_gram_pallas",)


def cost(n: int, d: int):
    return 2.0 * n * d * d, 4.0 * n * d + 8.0 * d * d
