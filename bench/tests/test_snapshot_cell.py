"""The ``euler.snapshot100`` cell: what decides its ``correct``, shown to
fail where it must, and its per-layer readers.

- A sound run at a CPU size is correct, and the plain reference in
  bfloat16, put in the snapshots' place, reads above the limit.
- Faults planted under the timed path each read ``correct`` false: the
  double-buffer copy skipped (with the host work held until the next
  chunk has donated the state, as a slow host would be), each
  snapshot taken one pass late, the host node never run, the chunk
  after the last snapshot one step short.
- The four readers on a hand-built record and trace summary, and
  nothing where the program keeps no such record.
"""

import sys

import jax
import jax.numpy as jnp
import pytest

import helpers
from helpers import BENCH, run

from repro import tracing

WORKLOAD = "euler.snapshot100"
bench_trace = run.load_file(BENCH / "trace.py", "bench_trace")


def small_plan() -> dict:
    """The cell cut to a CPU size: 64 x 32, a snapshot every 2 passes,
    calls of 4 steps (two periods a call, as on the chip)."""
    plan = run.cell_plan(run.load_json(helpers.ROOT / "BENCHMARK.json"),
                         WORKLOAD)
    plan["cfg"].update({"nx": 64, "ny": 32, "snapshot_every": 2})
    plan["mix"].update({"steps_per_call": 4, "trace_calls": 1})
    return plan


def _model():
    return run.load_file(BENCH / "models" / "euler_snapshot.py",
                         "bench_model_euler_snapshot")


def _run(plan, seed=2**33 + 7):
    from repro.core import clear_executable_cache

    clear_executable_cache()
    return run.run_cell(plan, seed, 0.5, False, jax.devices())


def test_sound_run_is_correct():
    res = _run(small_plan())
    assert res["correct"], res["checks"]
    assert res["checks"]["snapshot_rel_err"]["value"] == 0.0
    assert res["checks"]["snapshot_torn"]["value"] == 0.0
    assert res["checks"]["returned_rel_err"]["value"] == 0.0
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert set(res["metrics"]) == {"step_ms", "setup_s"}


def test_control_in_bfloat16_fails_the_limit():
    plan = small_plan()
    model = _model()
    for seed in (3, 2**31 + 11, 2**40 + 1):
        readings = dict(model.control(plan["cfg"], plan["mix"], seed,
                                      jnp.bfloat16))
        assert readings["snapshot_rel_err"] > model.SNAPSHOT_LIMIT, readings
        assert readings["returned_rel_err"] > model.SNAPSHOT_LIMIT, readings


def _copy_skipped(monkeypatch):
    import time

    from repro.core import executor

    submit = executor._AsyncRun.submit

    def held(self, region_index, fn, vals, label=None):
        # host work that starts only once the next chunk has donated the
        # state it was handed (on the chip the host is that slow anyway)
        def late(rec):
            t0 = time.perf_counter()
            while not rec.data.is_deleted() and time.perf_counter() - t0 < 5:
                time.sleep(1e-3)
            fn(rec)
        submit(self, region_index, late, vals, label)

    monkeypatch.setattr(executor, "_snapshot_for_host", list)
    monkeypatch.setattr(executor._AsyncRun, "submit", held)


def _one_pass_late(monkeypatch):
    from repro.core import schedule

    due = schedule.due_passes
    monkeypatch.setattr(
        schedule, "due_passes",
        lambda periods, steps: [(i + 1, js) for i, js in due(periods, steps)
                                if i + 1 < steps])


def _never_run(monkeypatch):
    from repro.core import schedule

    monkeypatch.setattr(schedule, "due_passes", lambda periods, steps: [])


def _last_chunk_short(monkeypatch):
    from repro.core import executor

    fused = executor.Executor._run_fused
    monkeypatch.setattr(executor.Executor, "_run_fused",
                        lambda self, state, steps: fused(self, state,
                                                         steps - 1))


@pytest.mark.parametrize("fault", [_copy_skipped, _one_pass_late,
                                   _never_run, _last_chunk_short])
def test_fault_under_the_timed_path_is_not_correct(monkeypatch, fault):
    plan = small_plan()
    fault(monkeypatch)
    res = _run(plan)
    assert res["correct"] is False, res["checks"]


# -- the per-layer readers -------------------------------------------------------

def _reader(name):
    return run.load_file(BENCH / "metrics" / f"{name}.py",
                         "bench_metric_" + name.replace(".", "_"))


def _span(name, t0, t1, **attrs):
    return tracing.Span(name, 1, None, "MainThread", t0, t1, attrs)


# one traced call of 200 steps: two snapshots of 0.9 and 1.1 s on the
# pool, a 0.9 s slot wait behind the first chunk, a 2 ms drain, two
# copies of the 16384 x 8192 float32 state
STATE_BYTES = 4 * 16384 * 8192 * 4
RECORD = tracing.Record(
    [_span("ripple.run", 0.0, 6.8, steps=200, path="fused+host"),
     _span("ripple.dispatch", 0.001, 0.002, region="fused"),
     _span("ripple.host", 0.01, 0.91, node="euler_snapshot/snapshot"),
     _span("ripple.host_wait", 0.002, 0.902, reason="slot"),
     _span("ripple.dispatch", 0.903, 0.904, region="fused"),
     _span("ripple.host", 3.4, 4.5, node="euler_snapshot/snapshot"),
     _span("ripple.host_wait", 6.798, 6.8, reason="drain")],
    {"host.copy_bytes": 2 * STATE_BYTES})
TRACED = {"steps": 200, "snapshots": 2}


def _run_of(traced, summary=None, cfg=None):
    return run.Run(cfg or {}, dict(traced), dict(traced),
                   type("S", (), {"done": []})(), summary, "TPU v5 lite")


@pytest.mark.parametrize("metric,value", [
    ("host_wait_ms.snapshot", 2e-3 * 1e3 / 200),
    ("host_ms.snapshot", 2.0 * 1e3 / 2),
    ("host_copy_bytes_per_step", 21474836.48),
])
def test_reader_values_on_a_record(metric, value, monkeypatch):
    import work

    monkeypatch.setattr(tracing, "recorded", lambda: RECORD)
    assert _reader(metric).read(_run_of(TRACED), work) == \
        pytest.approx(value)


@pytest.mark.parametrize("metric", ["host_wait_ms.snapshot",
                                    "host_ms.snapshot",
                                    "host_copy_bytes_per_step"])
def test_reader_returns_nothing_on_an_empty_record(metric, monkeypatch):
    import work

    monkeypatch.setattr(tracing, "recorded",
                        lambda: tracing.Record([], {}))
    assert _reader(metric).read(_run_of(TRACED), work) is None


@pytest.mark.parametrize("metric", ["host_wait_ms.snapshot",
                                    "host_ms.snapshot",
                                    "host_copy_bytes_per_step"])
def test_reader_returns_nothing_without_the_program_record(metric,
                                                          monkeypatch):
    """A program without ``repro.tracing``: no value, no error."""
    import repro
    import work

    monkeypatch.setattr(tracing, "recorded", lambda: RECORD)
    monkeypatch.delattr(repro, "tracing")
    monkeypatch.setitem(sys.modules, "repro.tracing", None)
    assert _reader(metric).read(_run_of(TRACED), work) is None


def _op(name, opcode="custom-call", target="tpu_custom_call"):
    return (f"%{name} = f32[4,16384,8192]{{2,1,0:T(8,128)}} {opcode}(%a), "
            f"custom_call_target=\"{target}\"")


def _summary(by_name):
    dev = bench_trace.Device([(0, 10 ** 10)], dict(by_name), 0)
    return bench_trace.Summary((0, 10 ** 10), {0: dev}, [])


def test_copy_roofline_on_a_summary():
    """A read and a write of 2 GiB at 819 GB/s over the copy's time per
    snapshot; the step's ops, and a fusion of a similar name, do not
    count."""
    import work

    cfg = {"nx": 16384, "ny": 8192, "mesh": None}
    summary = _summary({"ROOT " + _op("host_snapshot.1"): 6_000_000,
                        _op("host_snapshot.2"): 6_000_000,
                        _op("euler_force_update.12"): 10 ** 9,
                        "%host_snapshot_fusion = f32[4] fusion(%a)": 10 ** 8})
    want = 100 * (2 * 16 * 16384 * 8192 / 819e9) / 6e-3
    got = _reader("snapshot_copy_hbm_roofline").read(
        _run_of(TRACED, summary, cfg), work)
    assert got == pytest.approx(want)


def test_copy_roofline_returns_nothing_without_the_kernel():
    import work

    cfg = {"nx": 16384, "ny": 8192, "mesh": None}
    summary = _summary({_op("copy.3", "copy", ""): 6_000_000})
    assert _reader("snapshot_copy_hbm_roofline").read(
        _run_of(TRACED, summary, cfg), work) is None


def test_diagnostics_match_the_plain_float64_formulas():
    """The blocked, threaded diagnostics against the same quantities
    computed over the whole snapshot at once in float64."""
    import numpy as np
    from concurrent.futures import ThreadPoolExecutor

    model = _model()
    r = np.random.default_rng(5)
    u = np.stack([r.uniform(0.1, 2.0, (200, 48)), r.uniform(2.0, 9.0, (200, 48)),
                  r.uniform(-1, 1, (200, 48)), r.uniform(-1, 1, (200, 48))]
                 ).astype(np.float32)
    with ThreadPoolExecutor(3) as pool:
        got = model.diagnostics(u, 1.4, 0.25, pool)
    rho, e, mu, mv = u.astype(np.float64)
    want = {name: u[i].astype(np.float64).sum() * 0.25
            for i, name in enumerate(model.TOTALS)}
    want["rho_min"] = float(u[0].min())
    want["p_min"] = float((0.4 * (e - 0.5 * (mu * mu + mv * mv) / rho)).min())
    assert got.keys() == want.keys()
    for k in want:
        assert got[k] == pytest.approx(want[k], rel=1e-12), k
