"""Share of its roofline that ``quant_matmul`` reached in the traced
window: the summed least time of its calls (``counts/quant_matmul``, one
per projection, layer and decode step or prompt chunk) over its summed
kernel time in the trace."""
from benchmarks.tpu.metrics import _serve


def read(name, r):
    return _serve.least(r, "quant_matmul", _serve.qmm_calls(r))
