"""Shared arithmetic of the serving readers: the calls that the traced
window made, from the benchmark's own counts of bursts and prompt
chunks, and their least time on the chip."""
import importlib

from benchmarks.tpu import counts, harness, modelcfg
from benchmarks.tpu.counts import paged_flash_decode, quant_matmul


def decode_steps(r):
    """Cache length of every live slot at every decode step, per step."""
    steps = []
    for ctx in r["bursts"]:
        for j in range(r["burst_steps"]):
            steps.append([c + j for c in ctx])
    return steps


def least(r, name, calls) -> float | None:
    """Summed least time of ``calls`` ((flops, bytes) pairs) over the
    summed trace time of kernel ``name`` (its ``TRACE_NAMES`` in
    ``counts/<name>.py``), in percent; logs which bound held."""
    from benchmarks.tpu import trace_reduce

    mod = importlib.import_module(f"benchmarks.tpu.counts.{name}")
    t = trace_reduce.time_of(r["trace"], mod.TRACE_NAMES)
    if not t or not calls:
        return None
    pk = counts.peaks(r["device_kind"])
    total, by = 0.0, {"compute": 0.0, "memory": 0.0}
    for f, b in calls:
        lt, bound = counts.least_time(f, b, pk)
        total += lt
        by[bound] += lt
    harness.log(f"{name}: kernel {t!r} s, least {total!r} s "
                f"(memory-bound {by['memory']!r} s, compute-bound "
                f"{by['compute']!r} s)")
    return 100.0 * total / t


def qmm_calls(r):
    c = r["config"]
    s = modelcfg.layer_shapes(c)
    n_layers = modelcfg.hf_dims(c)["n_layers"]
    p = r["params"]
    ms = [r["max_slots"]] * (len(r["bursts"]) * r["burst_steps"])
    ms += [n for n, _ in r["prefill"]]
    return [quant_matmul.cost(m, *s[w], p["bits"], p["group_size"])
            for m in ms for w in modelcfg.LINEARS for _ in range(n_layers)]


def decode_calls(r):
    d = modelcfg.hf_dims(r["config"])
    return [paged_flash_decode.cost(ctx, d["n_heads"], d["n_kv_heads"],
                                    d["head_dim"], r["params"]["kv_bits"])
            for ctx in decode_steps(r) if ctx
            for _ in range(d["n_layers"])]
