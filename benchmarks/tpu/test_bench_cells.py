"""Cells are data: a cell file written here loads by name; a run without
a TPU exits non-zero with no result; a tiny serving cell runs end to end
on the CPU and is correct, and comes out not correct with a token
altered where the engine produces it, or under its control."""
import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)


from benchmarks.tpu import harness, spec, tiny  # noqa: E402

ARGS = ["--workload", "serve.tiny.chat", "--seed", str(2 ** 31 + 9),
        "--seconds", "1", "--trace", "0"]


def run_tiny(tmp_path, args=ARGS):
    root = tiny.write_root(tmp_path)
    return harness.run_cell(args, root=root, require_chip=False,
                            compile_cache=False)


def test_a_new_cell_is_files_and_entries(tmp_path):
    extra = {"name": "serve.tiny.burst", "config": "tiny",
             "traffic": "burst-tiny", "chips": 1, "why": "written by a test"}
    root = tiny.write_root(tmp_path, extra_cell=extra)
    base = root / "benchmarks" / "tpu"
    (base / "traffic" / "burst-tiny.json").write_text(
        json.dumps(dict(tiny.CHAT, pool_seed=99)))
    (base / "workloads" / "serve.tiny.burst.json").write_text(
        json.dumps(dict(tiny.SERVE_PARAMS, rate=80.0)))
    cell = spec.load_cell("serve.tiny.burst", root)
    assert cell.traffic["pool_seed"] == 99 and cell.params["rate"] == 80.0
    assert cell.config["hidden_size"] == 64 and cell.job == "serve"
    assert [m["name"] for m in cell.end_to_end] == ["setup_s"]
    reader = spec.metric_reader(cell, "round_ms_p50.burst")
    assert reader("round_ms_p50.burst", {"rounds_s": [0.1, 0.3]}) == 200.0


def test_every_benchmark_cell_loads():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.job in ("serve", "quantize")
        for m in cell.per_layer:
            assert spec.metric_reader(cell, m["name"])
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cell = json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"][0]
    p = subprocess.run(
        [sys.executable, "benchmarks/tpu/run.py", "--workload", cell["name"],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not any(ln.startswith("{") for ln in p.stdout.splitlines())


def test_tiny_serving_cell_is_correct(tmp_path):
    line, res = run_tiny(tmp_path)
    assert line["correct"] and line["failed"] == 0
    assert set(line["metrics"]) == {"ttft_p90_s", "tpot_p90_ms", "setup_s"}
    assert list(line)[-1] == "checks"
    lim = tiny.SERVE_PARAMS["limits"]
    assert all(line["checks"][k]["value"] <= v for k, v in lim.items())
    assert res["extra"]["compared_tokens"] > 0


def test_altered_token_is_not_correct(tmp_path, monkeypatch):
    from repro.serving.engine import Engine

    burst = Engine._burst

    def altered(self):
        burst(self)
        for toks in self._slot_tokens:
            if toks:
                toks[-1] = (toks[-1] + 1) % self.model.cfg.vocab_size

    monkeypatch.setattr(Engine, "_burst", altered)
    line, _ = run_tiny(tmp_path)
    assert not line["correct"]


def test_control_fails_the_limit(tmp_path):
    """The reference at float8 activations, one step below the bfloat16
    that the configuration states, put in the program's place, comes out
    not correct through the run's own comparison."""
    line, res = run_tiny(tmp_path, ARGS + ["--control", "1"])
    assert not line["correct"]
    lim = tiny.SERVE_PARAMS["limits"]
    assert any(line["checks"][k]["value"] > v for k, v in lim.items())
    assert res["extra"]["answers"].startswith("the control's")


def test_tiny_backlog_cell(tmp_path):
    line, res = run_tiny(tmp_path, ["--workload", "serve.tiny.docs", "--seed",
                                    "4", "--seconds", "1", "--trace", "0"])
    assert line["correct"]
    assert set(line["metrics"]) == {"out_tok_s", "setup_s"}
    assert line["metrics"]["out_tok_s"]["value"] > 0
