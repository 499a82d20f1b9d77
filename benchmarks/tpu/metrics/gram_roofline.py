"""Share of its roofline that the ``gram`` kernel reached in the traced
window: the least time of the Hessian grams of every fused capture call
in the window (``counts/gram``, one per quantized projection, over the
batch's tokens) over the kernel's summed time in the trace."""
from benchmarks.tpu import counts, harness, trace_reduce
from benchmarks.tpu.counts import gram
from benchmarks.tpu.metrics import _quant


def read(name, r):
    t = trace_reduce.time_of(r["trace"], gram.TRACE_NAMES)
    calls = trace_reduce.module_count(r["trace"], _quant.CAPTURE)
    if not t or not calls:
        return None
    job = r["job"]
    rows = job["batch"] * job["calib_seq"]
    pk = counts.peaks(r["device_kind"])
    per_call = [counts.least_time(*gram.cost(rows, d), pk)
                for d in _quant.gram_inputs(r)]
    least = calls * sum(lt for lt, _ in per_call)
    harness.log(f"gram: kernel {t!r} s over {calls} capture calls, least "
                f"{least!r} s, bounds {sorted({b for _, b in per_call})}")
    return 100.0 * least / t
