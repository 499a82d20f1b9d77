"""Trace reduction: busy time as a union, idle gaps by host span, kernel
time by name; on hand-made events and on a trace recorded on the chip."""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import pytest  # noqa: E402

from benchmarks.tpu import trace_reduce as tr  # noqa: E402

DEV = "/device:TPU:0"


def ev(name, start, dur, line=tr.OPS_LINE, plane=DEV):
    return {"plane": plane, "line": line, "name": name,
            "start_ns": float(start), "dur_ns": float(dur), "module": None}


def host(name, start, dur):
    return ev(name, start, dur, line="python", plane="/host:CPU")


def test_names_as_the_trace_prints_them():
    assert tr.op_name("%quant_matmul_pallas.82 = f32[24,14336]{1,0} "
                      "custom-call(bf16[24,5120]{1,0} %fusion.96)") == \
        "quant_matmul_pallas"
    assert tr.op_name("%fusion = f32[8]{0} fusion(f32[8]{0} %p)") == "fusion"
    assert tr.module_name("jit_run(3189721660645829562)") == "jit_run"


def test_busy_is_union_and_gaps_go_to_innermost_span():
    events = [
        host(tr.WINDOW, 0, 1000),
        host("bench:step", 0, 600),
        host("bench:burst", 100, 300),
        host("bench:wait", 600, 400),
        ev("qmm", 100, 200), ev("qmm", 150, 100),   # overlap: counted once
        ev("fusion", 350, 50),
        ev("fusion", 900, 200),                     # clipped at 1000
        ev("jit_run", 100, 300, line=tr.MODULES_LINE),
    ]
    red = tr.reduce(events)
    assert red["window_s"] == pytest.approx(1000e-9)
    assert red["busy_s"] == pytest.approx((200 + 50 + 100) * 1e-9)
    gaps = dict(red["idle_gaps"])
    # [0,100) -> step, [300,350) -> burst, [400,900): middle 650 -> wait
    assert gaps["bench:step"] == pytest.approx(100e-9)
    assert gaps["bench:burst"] == pytest.approx(50e-9)
    assert gaps["bench:wait"] == pytest.approx(500e-9)
    ops = dict(red["device_ops"])
    assert ops["qmm"] == pytest.approx(300e-9)
    assert ops["fusion"] == pytest.approx(150e-9)
    assert tr.time_of(red, ("qm",)) == pytest.approx(300e-9)
    assert tr.module_time(red, ("jit_run",)) == pytest.approx(300e-9)


def test_no_window_or_no_device_reads_nothing():
    assert tr.reduce([ev("qmm", 0, 10)])["busy_s"] == 0.0
    assert tr.reduce([host(tr.WINDOW, 0, 10)])["n_ops"] == 0


def test_whole_window_spans_the_device_events():
    w = tr.whole_window([ev("a", 10, 5), ev("b", 40, 20)])
    assert (w["start_ns"], w["dur_ns"]) == (10, 50)


def test_recorded_chip_trace():
    """A short stretch of a serving cell's trace from one TPU v5e chip."""
    path = Path(__file__).with_name("testdata") / "serve_trace.json.gz"
    red = tr.reduce(tr.load(path))
    assert red["window_s"] == pytest.approx(0.25)
    assert red["busy_s"] == pytest.approx(0.24387791700000003)
    top, secs = red["device_ops"][0]
    assert top == "quant_matmul_pallas" and secs == pytest.approx(0.21134, 1e-4)
    assert len(red["device_ops"]) == 10 and len(red["idle_gaps"]) <= 10
    assert tr.module_time(red, ("jit__lambda",)) == pytest.approx(0.242898,
                                                                   1e-5)
    assert "while" not in dict(red["device_ops"])


def _reader(name):
    from benchmarks.tpu import spec

    return spec.load_module(ROOT / "benchmarks" / "tpu" / "metrics"
                            / f"{name}.py", f"test_reader_{name}").read


@pytest.mark.parametrize("name, readings", [
    # prompt tokens in the window, but no prompt program in the trace
    ("prefill_ms_per_ktok", {"prefill": [(512, 0)],
                             "trace": tr.reduce([host(tr.WINDOW, 0, 10)])}),
    # rounds in the window, but no decode burst seen by the probe
    ("decode_slots_mean", {"slots": [], "rounds_s": [1.8, 1.9]}),
    # a traced pass with neither capture nor solve programs in it
    ("capture_s_per_layer", {"trace": tr.reduce([host(tr.WINDOW, 0, 10)])}),
    ("solve_s_per_layer", {"trace": tr.reduce([host(tr.WINDOW, 0, 10)])}),
])
def test_reader_that_loses_its_names_fails_loudly(name, readings):
    with pytest.raises(RuntimeError):
        _reader(name)(name, readings)


@pytest.mark.parametrize("name, readings", [
    ("prefill_ms_per_ktok", {"prefill": [],
                             "trace": tr.reduce([host(tr.WINDOW, 0, 10)])}),
    ("decode_slots_mean", {"slots": [], "rounds_s": []}),
])
def test_reader_with_nothing_to_read_returns_nothing(name, readings):
    assert _reader(name)(name, readings) is None
