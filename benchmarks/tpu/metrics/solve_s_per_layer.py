"""Device seconds of the GPTQ solve programs per layer: their mean time
per run in the traced window, times the solve programs of a layer."""
from benchmarks.tpu import trace_reduce
from benchmarks.tpu.metrics import _quant


def read(name, r):
    n = trace_reduce.module_count(r["trace"], _quant.SOLVE)
    t = trace_reduce.module_time(r["trace"], _quant.SOLVE)
    if not n:
        # every traced stretch of a pass holds these programs
        raise RuntimeError(f"{name}: no device module named "
                           f"{_quant.SOLVE} in the traced window")
    return t / n * _quant.SOLVES_PER_LAYER
