"""Model FLOPs of a whole step, for ``mfu``: the operations the model
needs, never what a kernel recomputes.

Serving: per token, 2 N_linear for the projections plus attention
(4 H Dh per cached token it attends to, per layer); the head, 2 d V, for
each sampled token.  Quantizer, per layer and calibration token: two
layer forwards (capture and propagation), the Hessian grams (2 d_in^2
per captured input) and the GPTQ solves (2 d_in^2 d_out per weight, the
error feedback), counted once each."""
from benchmarks.tpu import modelcfg


def serve_flops(config: dict, prompt_chunks, decode_ctx) -> float:
    """prompt_chunks: (chunk length, tokens before it) per prefill chunk;
    decode_ctx: cache length per decoded token."""
    d = modelcfg.hf_dims(config)
    lin = 2.0 * modelcfg.linear_params(config)
    att = 4.0 * d["n_heads"] * d["head_dim"] * d["n_layers"]
    head = 2.0 * d["d_model"] * d["vocab"]
    f = 0.0
    for L, past in prompt_chunks:
        f += L * lin + att * (L * past + L * (L + 1) / 2)
    for ctx in decode_ctx:
        f += lin + att * ctx + head
    return f + head * len(prompt_chunks)


def layer_flops(config: dict, n_tokens: int, seq: int) -> float:
    d = modelcfg.hf_dims(config)
    s = modelcfg.layer_shapes(config)
    lin = sum(s[k][0] * s[k][1] for k in modelcfg.LINEARS)
    fwd = n_tokens * (2.0 * lin + 2.0 * d["n_heads"] * d["head_dim"] * seq)
    inputs = {"h": d["d_model"], "att": d["n_heads"] * d["head_dim"],
              "h2": d["d_model"], "hid": d["d_ff"]}
    grams = sum(2.0 * n_tokens * x * x for x in inputs.values())
    solves = sum(2.0 * s[k][0] ** 2 * s[k][1] for k in modelcfg.LINEARS)
    return 2 * fwd + grams + solves
