"""Operation and byte counts pinned at hand-worked shapes; the peaks
table refuses an unknown device."""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import pytest  # noqa: E402

from benchmarks.tpu import counts  # noqa: E402
from benchmarks.tpu.counts import (gram, model,  # noqa: E402
                                   paged_flash_decode, quant_matmul)


def test_quant_matmul():
    # m=8, k=256, n=128, 4-bit g128: flops 2*8*256*128; bytes: codes
    # 256*128/2 = 16384, scales+zeros 2*2*128*4 = 2048, x 2*8*256 = 4096,
    # out 2*8*128 = 2048
    assert quant_matmul.cost(8, 256, 128) == (524288.0, 24576.0)


def test_paged_flash_decode():
    # two slots with 10 and 30 cached tokens, 4 heads over 2 KV heads, dh 8
    # flops 4*4*8*40 = 5120; bytes: codes 40*2*2*8 = 1280, scales
    # 40*2*2*2 = 320, q+out 2 slots * 2*4*8*4 = 512
    assert paged_flash_decode.cost([10, 30], 4, 2, 8) == (5120.0, 2112.0)


def test_gram():
    # 1024 rows of width 128: 2*1024*128^2 flops; rows 4*1024*128 bytes,
    # accumulator in and out 8*128^2
    assert gram.cost(1024, 128) == (33554432.0, 655360.0)


CFG = {"model_type": "mistral", "hidden_size": 8, "num_hidden_layers": 2,
       "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 4,
       "intermediate_size": 16, "vocab_size": 32, "rope_theta": 1e4,
       "rms_norm_eps": 1e-5}


def test_model_flops():
    # linear params per layer: q 8x8 + k,v 8x4 each + o 8x8 + 3 x 8x16
    # = 64+32+32+64+384 = 576, two layers 1152 -> 2304 flops per token;
    # attention 4*2*4*2 = 64 per cached token; head 2*8*32 = 512
    assert model.serve_flops(CFG, [], [5]) == 2304 + 64 * 5 + 512
    assert model.serve_flops(CFG, [(3, 0)], []) == 3 * 2304 + 64 * 6 + 512


def test_peaks_by_device_kind():
    pk = counts.peaks("TPU v5 lite")
    assert pk["bf16_flops_per_s"] == 197e12 and pk["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        counts.peaks("cpu")
    t, bound = counts.least_time(197e12, 1.0, pk)
    assert (t, bound) == (1.0, "compute")
