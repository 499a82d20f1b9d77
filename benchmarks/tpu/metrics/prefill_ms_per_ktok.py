"""Device time of the prompt programs (whole-prompt prefill and the
chunked-prefill extend steps) per 1000 prompt tokens, in the traced
window."""
from benchmarks.tpu import trace_reduce

# the device modules of the engine's prompt programs (jitted lambdas)
MODULES = ("jit__lambda",)


def read(name, r):
    toks = sum(n for n, _ in r["prefill"])
    if not toks:
        return None
    t = trace_reduce.module_time(r["trace"], MODULES)
    if not t:
        raise RuntimeError(f"{name}: {toks} prompt tokens in the traced "
                           f"window but no device module named {MODULES}")
    return 1e3 * t / (toks / 1e3)
