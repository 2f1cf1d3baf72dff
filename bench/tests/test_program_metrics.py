"""The per-layer readers of the program's own record (``repro.tracing``):
values on a hand-built record, nothing where the record is empty or the
program keeps none."""

import sys

import pytest

import helpers  # noqa: F401  (puts bench/ and src/ on the path)
from helpers import BENCH, run

from repro import tracing


def _reader(name):
    return run.load_file(BENCH / "metrics" / f"{name}.py",
                         "bench_metric_" + name.replace(".", "_"))


def _span(name, t0, t1, **attrs):
    return tracing.Span(name, 1, None, "MainThread", t0, t1, attrs)


# two traced calls of 10 steps on 2x2; 8192 x 4096 float32 SoA a chip,
# transmissive, so each chip sends one 1-wide strip along each axis
RECORD = tracing.Record(
    [_span("ripple.run", 0.0, 0.010, steps=10),
     _span("ripple.dispatch", 0.001, 0.004, region="fused"),
     _span("ripple.run", 1.0, 1.010, steps=10),
     _span("ripple.dispatch", 1.001, 1.002, region="fused"),
     _span("ripple.init_state", 2.0, 2.0005),
     _span("ripple.init_state", 3.0, 3.0015)],
    {"halo.bytes": 20 * (4096 * 16 + 8192 * 16)})


def _run(traced):
    return run.Run({}, dict(traced), dict(traced),
                   type("S", (), {"done": []})(), None, "TPU v5 lite")


@pytest.mark.parametrize("metric,traced,value", [
    ("dispatch_ms.euler", {"steps": 20}, 4e-3 * 1e3 / 20),
    ("init_state_ms.eikonal", {"solves": 2}, 2e-3 * 1e3 / 2),
    ("halo_bytes_per_step", {"steps": 20}, 196608),
])
def test_reader_values_on_a_record(metric, traced, value, monkeypatch):
    import work

    monkeypatch.setattr(tracing, "recorded", lambda: RECORD)
    assert _reader(metric).read(_run(traced), work) == pytest.approx(value)


@pytest.mark.parametrize("metric,traced", [
    ("dispatch_ms.euler", {"steps": 20}),
    ("init_state_ms.eikonal", {"solves": 2}),
    ("halo_bytes_per_step", {"steps": 20}),
])
def test_reader_returns_nothing_on_an_empty_record(metric, traced,
                                                  monkeypatch):
    import work

    monkeypatch.setattr(tracing, "recorded",
                        lambda: tracing.Record([], {}))
    assert _reader(metric).read(_run(traced), work) is None


@pytest.mark.parametrize("metric", ["dispatch_ms.euler",
                                    "init_state_ms.eikonal",
                                    "halo_bytes_per_step"])
def test_reader_returns_nothing_without_the_program_record(metric,
                                                          monkeypatch):
    """A program without ``repro.tracing`` (an older commit): no value,
    no error."""
    import repro
    import work

    monkeypatch.setattr(tracing, "recorded", lambda: RECORD)
    monkeypatch.delattr(repro, "tracing")
    monkeypatch.setitem(sys.modules, "repro.tracing", None)
    r = _run({"steps": 20, "solves": 2})
    assert _reader(metric).read(r, work) is None
