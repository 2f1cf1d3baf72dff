"""The split FORCE update kernel's roofline reader: its value on a
hand-built trace summary, on one chip and on 2x2, and nothing where no
op of that name ran (the XLA-fused update of a recorded trace)."""

from pathlib import Path

import pytest

import helpers
from helpers import BENCH, run

bench_trace = run.load_file(BENCH / "trace.py", "bench_trace")
DATA = Path(__file__).resolve().parent / "data"
ONE_CHIP = {"nx": 16384, "ny": 8192, "mesh": None}
TWO_BY_TWO = {"nx": 16384, "ny": 8192, "mesh": [2, 2]}


def _reader():
    return run.load_file(BENCH / "metrics" / "euler_update_hbm_roofline.py",
                         "bench_metric_euler_update_hbm_roofline")


def _op(name, opcode="custom-call", target="tpu_custom_call"):
    return (f"%{name} = f32[4,16384,8192]{{2,1,0:T(8,128)}} {opcode}(%a), "
            f"custom_call_target=\"{target}\"")


def _summary(by_name, devices=1):
    dev = bench_trace.Device([(0, 10 ** 9)], dict(by_name), 0)
    return bench_trace.Summary((0, 10 ** 9), {i: dev for i in range(devices)},
                               [])


def _run(cfg, summary, steps=10):
    return run.Run(cfg, {"steps": steps}, {"steps": steps},
                   type("S", (), {"done": []})(), summary, "TPU v5 lite")


@pytest.mark.parametrize("cfg,devices", [(ONE_CHIP, 1), (TWO_BY_TWO, 4)])
def test_reader_values_on_a_summary(cfg, devices):
    """Two passes of 2 x 16 B a cell at 819 GB/s over the kernel's time
    per step; other ops, and a fusion of a similar name, do not count."""
    import work

    cells = cfg["nx"] * cfg["ny"] // devices
    kernel_ns = 20_000_000 // devices          # 2 ms a step, 10 steps
    summary = _summary({_op("euler_force_update.12"): kernel_ns // 2,
                        _op("euler_force_update.13"): kernel_ns // 2,
                        _op("concatenate.15", "concatenate"): 7 * 10 ** 7,
                        "%euler_force_update_fusion = f32[4] fusion(%a)":
                            10 ** 8}, devices)
    want = 100 * (2 * 2 * 16 * cells / 819e9) / (kernel_ns * 1e-9 / 10)
    got = _reader().read(_run(cfg, summary), work)
    assert got == pytest.approx(want)


def test_reader_returns_nothing_without_the_kernel():
    """The XLA-fused update of a trace recorded on a v5e before the
    kernel existed."""
    import work

    summary = bench_trace.summarize(str(DATA / "euler_small.xplane.pb"), [0])
    cfg = {"nx": 1024, "ny": 512, "mesh": None}
    assert _reader().read(_run(cfg, summary, steps=8), work) is None
