"""Device seconds of the fused capture program (layer forward, AttnCon
importance, Hessian accumulation) per layer: its mean time per
calibration batch in the traced window, times the batches of a layer."""
from benchmarks.tpu import trace_reduce
from benchmarks.tpu.metrics import _quant


def read(name, r):
    n = trace_reduce.module_count(r["trace"], _quant.CAPTURE)
    t = trace_reduce.module_time(r["trace"], _quant.CAPTURE)
    if not n:
        # every traced stretch of a pass holds these programs
        raise RuntimeError(f"{name}: no device module named "
                           f"{_quant.CAPTURE} in the traced window")
    return t / n * _quant.batches_per_layer(r)
