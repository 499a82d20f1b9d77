"""A tiny copy of the benchmark's files, for tests on the CPU.

``write_root(tmp)`` lays out ``BENCHMARK.json`` and the files of two
small cells under ``tmp`` (a serving cell and a quantizer cell, at widths
a test run can hold), with the benchmark's code found through this
package.  Tests drive ``harness.main(..., root=tmp, require_chip=False)``
on them; the chip runs use the real files.
"""
from __future__ import annotations

import json
from pathlib import Path

CONFIG = {"model_type": "mistral", "hidden_size": 64,
          "num_hidden_layers": 2, "num_attention_heads": 4,
          "num_key_value_heads": 2, "head_dim": 16,
          "intermediate_size": 128, "vocab_size": 512,
          "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
          "tie_word_embeddings": False, "reference": "dense_gqa"}

QCONFIG = dict(CONFIG, model_type="qwen2", hidden_size=128,
               num_attention_heads=4, num_key_value_heads=4, head_dim=32,
               intermediate_size=256, attention_bias=True)

CHAT = {"job": "serve", "arrivals": "open", "pool_seed": 7,
        "prompt": {"median": 24, "sigma": 0.8, "min": 8, "max": 64},
        "prompt_grid": [8, 16, 32, 64],
        "output": {"median": 6, "sigma": 0.5, "min": 3, "max": 12}}

DOCS = dict(CHAT, arrivals="backlog", pool_seed=8,
            prompt={"median": 40, "sigma": 0.5, "min": 16, "max": 64},
            prompt_grid=[16, 32, 64])

CALIB = {"job": "quantize", "n_calib": 8, "calib_seq": 64,
         "importance": "attn_con", "bits": 4, "group_size": 32,
         "damp": 0.01, "r_min": 0.01, "r_max": 1.0, "batch": 4,
         "scheduler": None}

SERVE_PARAMS = {"bits": 4, "group_size": 32, "kv_bits": 8, "max_slots": 4,
                "n_pages": 64, "prefill_chunk": 16, "rate": 40.0,
                "preroll_s": 0.2, "trace_offset_s": 0.1, "trace_s": 0.3,
                "drain_limit_s": 60.0, "check_requests": 3,
                "limits": {"first_logits_rel": 0.05, "flip_share_median": 0.1}}

DOCS_PARAMS = dict(SERVE_PARAMS, prefill_chunk=None, backlog_per_slot=2,
                   pool=32)

QUANT_PARAMS = {"check_layers": 2,
                "limits": {"hessian_rel_layer0": 0.02, "loss_rel_layer0": 0.05,
                           "loss_rel_layer1": 0.05},
                "trace_offset_s": 0.0, "trace_s": 1.0}

E2E = [{"name": "ttft_p90_s", "unit": "s", "better": "lower", "bound": 0.1,
        "source": "host_clock", "workloads": ["serve.tiny.chat"]},
       {"name": "tpot_p90_ms", "unit": "ms", "better": "lower",
        "bound": 0.1, "source": "host_clock",
        "workloads": ["serve.tiny.chat"]},
       {"name": "out_tok_s", "unit": "tokens/s", "better": "higher",
        "bound": 0.1, "source": "host_clock",
        "workloads": ["serve.tiny.docs"]},
       {"name": "quant_layer_s", "unit": "s/layer", "better": "lower",
        "bound": 0.1, "source": "host_clock",
        "workloads": ["quant.tiny.attncon"]},
       {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.25,
        "source": "host_clock"}]

PER_LAYER = [{"name": "round_ms_p50.chat", "unit": "ms", "better": "lower",
              "source": "host_clock", "layer": "engine",
              "moves": "tpot_p90_ms", "workloads": ["serve.tiny.chat"]},
             {"name": "decode_slots_mean.chat", "unit": "slots",
              "better": "higher", "source": "program_counter",
              "layer": "engine", "moves": "tpot_p90_ms",
              "workloads": ["serve.tiny.chat"]}]


def write_root(tmp: Path, *, cells=("serve.tiny.chat", "serve.tiny.docs",
                                    "quant.tiny.attncon"),
               extra_cell: dict | None = None) -> Path:
    tmp = Path(tmp)
    base = tmp / "benchmarks" / "tpu"
    for d in ("configs", "traffic", "workloads"):
        (base / d).mkdir(parents=True, exist_ok=True)
    src = Path(__file__).resolve().parent
    for d in ("metrics", "jobs", "reference"):
        if not (base / d).exists():
            (base / d).symlink_to(src / d)
    files = {"configs/tiny.json": CONFIG, "configs/tiny-q.json": QCONFIG,
             "traffic/chat-tiny.json": CHAT,
             "traffic/calib-tiny.json": CALIB,
             "traffic/docs-tiny.json": DOCS,
             "workloads/serve.tiny.docs.json": DOCS_PARAMS,
             "workloads/serve.tiny.chat.json": SERVE_PARAMS,
             "workloads/quant.tiny.attncon.json": QUANT_PARAMS}
    for rel, obj in files.items():
        (base / rel).write_text(json.dumps(obj))
    workloads = [
        {"name": "serve.tiny.chat", "config": "tiny", "traffic": "chat-tiny",
         "chips": 1, "why": "tiny serving cell for the CPU tests"},
        {"name": "serve.tiny.docs", "config": "tiny", "traffic": "docs-tiny",
         "chips": 1, "why": "tiny closed-backlog cell for the CPU tests"},
        {"name": "quant.tiny.attncon", "config": "tiny-q",
         "traffic": "calib-tiny", "chips": 1,
         "why": "tiny quantizer cell for the CPU tests"}]
    workloads = [w for w in workloads if w["name"] in cells]
    if extra_cell:
        workloads.append(extra_cell)
    bench = {"command": ["python3", "benchmarks/tpu/run.py"],
             "paths": ["benchmarks/tpu"], "run_seconds": 1,
             "configs": [
                 {"name": "tiny", "source": "test",
                  "file": "benchmarks/tpu/configs/tiny.json", "reduced": [],
                  "why": "test"},
                 {"name": "tiny-q", "source": "test",
                  "file": "benchmarks/tpu/configs/tiny-q.json",
                  "reduced": [], "why": "test"}],
             "workloads": workloads, "end_to_end": E2E,
             "per_layer": PER_LAYER}
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp
