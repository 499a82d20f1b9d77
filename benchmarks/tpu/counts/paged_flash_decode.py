"""``paged_flash_decode``: one query token per slot against its paged
int8 KV cache.  ``ctx`` lists the cache length of each live slot.
Operations: q.k and p.v, 4 H Dh per cached token.  Bytes: int8 codes of
K and V (2 KV Dh per token), their bf16 scales (2 KV x 2 bytes per token),
the float32 query and output."""

# the kernel's ops in a device trace (``trace_reduce.op_name``): its
# Pallas call, named after the kernel's wrapper
TRACE_NAMES = ("paged_flash_decode_pallas",)


def cost(ctx, n_heads: int, n_kv: int, head_dim: int, kv_bits: int = 8):
    toks = float(sum(ctx))
    flops = 4.0 * n_heads * head_dim * toks
    nbytes = (toks * 2 * n_kv * head_dim * kv_bits / 8 + toks * 2 * n_kv * 2
              + len(ctx) * 2 * n_heads * head_dim * 4)
    return flops, nbytes
