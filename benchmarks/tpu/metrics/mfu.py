"""The whole step's share of the chip's bf16 peak: model FLOPs
(``counts/model``) per second over the peak, in percent.  Serving: the
prompt chunks and decode steps of the traced window over its length.
Quantizer: one layer's calibration work over the window's seconds per
layer."""
from benchmarks.tpu import counts
from benchmarks.tpu.counts import model
from benchmarks.tpu.metrics import _serve


def read(name, r):
    peak = counts.peaks(r["device_kind"])["bf16_flops_per_s"]
    if "bursts" in r:
        w = r["trace"]["window_s"]
        if not w:
            return None
        steps = _serve.decode_steps(r)
        f = model.serve_flops(r["config"], r["prefill"],
                              [c for ctx in steps for c in ctx])
        return 100.0 * f / w / peak
    job = r["job"]
    per_layer = sum(r["pass_s"]) / (len(r["pass_s"]) * r["n_layers"])
    f = model.layer_flops(r["config"], job["n_calib"] * job["calib_seq"],
                          job["calib_seq"])
    return 100.0 * f / per_layer / peak
