"""Share of its roofline that ``paged_flash_decode`` reached in the
traced window: summed least time of its calls (``counts/
paged_flash_decode``, one per layer and decode step, over the live
slots' cache lengths) over its summed kernel time in the trace."""
from benchmarks.tpu.metrics import _serve


def read(name, r):
    return _serve.least(r, "paged_flash_decode", _serve.decode_calls(r))
