"""Shared arithmetic of the quantizer readers: the device modules of the
calibration engine as the trace names them, and the captured inputs of
one layer."""
from benchmarks.tpu import modelcfg

# jitted programs of core/pipeline.py and core/gptq.py, by module name
CAPTURE = ("jit__fused",)
SOLVE = ("jit_gptq_quantize",)
# shape groups solved per layer of a dense GQA block: (q, k, v, o) when
# square and alike, (gate, up), down; see quantize_layer_weights
SOLVES_PER_LAYER = 3


def batches_per_layer(r) -> int:
    job = r["job"]
    return -(-job["n_calib"] // job["batch"])


def gram_inputs(r):
    """Width of the captured input of each quantized projection."""
    s = modelcfg.layer_shapes(r["config"])
    return [s[w][0] for w in modelcfg.LINEARS]
