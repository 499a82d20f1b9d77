"""A tiny quantizer cell on the CPU: correct as it stands; not correct
when a layer's solve returns its state unchanged, when half of each
calibration batch is left out, or when a quantized weight is altered
where it is produced; and the control reads above the limit."""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)


from benchmarks.tpu import harness, tiny  # noqa: E402

ARGS = ["--workload", "quant.tiny.attncon", "--seed", "5", "--seconds",
        "0.5", "--trace", "0"]
LIMITS = tiny.QUANT_PARAMS["limits"]


def run_tiny(tmp_path, args=ARGS):
    root = tiny.write_root(tmp_path)
    return harness.run_cell(args, root=root, require_chip=False,
                            compile_cache=False)


def test_tiny_quant_cell_is_correct(tmp_path):
    line, res = run_tiny(tmp_path)
    assert line["correct"]
    assert all(line["checks"][k]["value"] <= lim for k, lim in LIMITS.items())
    assert set(line["metrics"]) == {"quant_layer_s", "setup_s"}
    assert len(res["extra"]["per_weight"]) == 14
    assert list(line["checks"]) == ["hessian_rel_layer0", "loss_rel_layer0",
                                    "loss_rel_layer1"]


def test_unchanged_state_is_not_correct(tmp_path, monkeypatch):
    from repro.core import pipeline

    monkeypatch.setattr(pipeline, "quantize_layer_weights",
                        lambda p, h, rsq, **kw: (p, {}))
    line, _ = run_tiny(tmp_path)
    assert not line["correct"]


def test_half_batch_is_not_correct(tmp_path, monkeypatch):
    from repro.core.pipeline import RSQPipeline

    capture = RSQPipeline.layer_capture

    def half(self, state, bi, x_b):
        return capture(self, state, bi, x_b[: x_b.shape[0] // 2])

    monkeypatch.setattr(RSQPipeline, "layer_capture", half)
    line, _ = run_tiny(tmp_path)
    assert not line["correct"]


def test_altered_answer_is_not_correct(tmp_path, monkeypatch):
    from repro.core import pipeline

    solve = pipeline.quantize_layer_weights

    def altered(p, h, rsq, **kw):
        new, rep = solve(p, h, rsq, **kw)
        w = new["mixer"]["wo"]
        new["mixer"]["wo"] = w.at[:8].multiply(-1.0)
        return new, rep

    monkeypatch.setattr(pipeline, "quantize_layer_weights", altered)
    line, _ = run_tiny(tmp_path)
    assert not line["correct"]


def test_control_fails_the_limit(tmp_path):
    """The reference at float8 activations, one step below bf16, put in
    the program's place, comes out not correct."""
    line, res = run_tiny(tmp_path, ARGS + ["--control", "1"])
    assert not line["correct"]
    assert any(line["checks"][k]["value"] > lim for k, lim in LIMITS.items())
    assert res["extra"]["answers"].startswith("the control's")
