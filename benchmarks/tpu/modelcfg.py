"""The program's model configuration, built from a configuration file.

Configuration files keep the published ``config.json`` keys; this maps
the keys of a dense decoder with grouped-query attention onto the
program's ``ModelConfig``.  A family with other keys adds its own mapping
beside this one, named by the file's ``model_type``.
"""
from __future__ import annotations

def hf_dims(c: dict) -> dict:
    """Sizes of a dense GQA decoder, by the published key names."""
    heads = c["num_attention_heads"]
    return {
        "n_layers": c["num_hidden_layers"],
        "d_model": c["hidden_size"],
        "n_heads": heads,
        "n_kv_heads": c.get("num_key_value_heads", heads),
        "head_dim": c.get("head_dim") or c["hidden_size"] // heads,
        "d_ff": c["intermediate_size"],
        "vocab": c["vocab_size"],
        "rope_theta": float(c["rope_theta"]),
        "norm_eps": float(c["rms_norm_eps"]),
        "qkv_bias": bool(c.get("attention_bias", False)),
        "tied": bool(c.get("tie_word_embeddings", False)),
    }


def model_config(c: dict, name: str, **serving):
    """``ModelConfig`` of the program; ``serving`` sets e.g. ``kv_bits``."""
    from repro.configs.base import ModelConfig

    d = hf_dims(c)
    return ModelConfig(
        name=name, family="dense", n_layers=d["n_layers"],
        d_model=d["d_model"], n_heads=d["n_heads"],
        n_kv_heads=d["n_kv_heads"], d_ff=d["d_ff"], vocab_size=d["vocab"],
        d_head=d["head_dim"], qkv_bias=d["qkv_bias"],
        rope_theta=d["rope_theta"], norm_eps=d["norm_eps"],
        tie_embeddings=d["tied"], dtype="bfloat16", **serving)


def layer_shapes(c: dict) -> dict:
    """Parameter paths of one decoder layer (below ``groups/b0/``) and
    their shapes: the layout of the program's parameter tree."""
    d = hf_dims(c)
    dm, h, kv, dh, ff = (d["d_model"], d["n_heads"], d["n_kv_heads"],
                         d["head_dim"], d["d_ff"])
    s = {"mixer_norm": (dm,), "mixer/wq": (dm, h * dh),
         "mixer/wk": (dm, kv * dh), "mixer/wv": (dm, kv * dh),
         "mixer/wo": (h * dh, dm), "ffn_norm": (dm,),
         "ffn/wi": (dm, ff), "ffn/wu": (dm, ff), "ffn/wd": (ff, dm)}
    if d["qkv_bias"]:
        s.update({"mixer/bq": (h * dh,), "mixer/bk": (kv * dh,),
                  "mixer/bv": (kv * dh,)})
    return s


LINEARS = ("mixer/wq", "mixer/wk", "mixer/wv", "mixer/wo",
           "ffn/wi", "ffn/wu", "ffn/wd")


def linear_params(c: dict) -> int:
    """Parameters of the quantized projections of the whole model."""
    s = layer_shapes(c)
    return hf_dims(c)["n_layers"] * sum(s[k][0] * s[k][1] for k in LINEARS)
