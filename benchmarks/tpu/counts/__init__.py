"""Operations and bytes of each kernel call and of a whole model step,
computed from shapes; and the chip's peaks (``peaks.json``)."""
from __future__ import annotations

import json
from pathlib import Path


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind``; an unknown kind is
    an error, never a default."""
    table = json.loads((Path(__file__).resolve().parents[1]
                        / "peaks.json").read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(table)}")
    return table[device_kind]


def least_time(flops: float, nbytes: float, pk: dict) -> tuple[float, str]:
    """The least time the chip could take, and which bound holds."""
    tc = flops / pk["bf16_flops_per_s"]
    tm = nbytes / pk["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
