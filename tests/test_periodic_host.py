"""Periodic host nodes (``every=k``): a host node that runs on pass ``i``
of a ``run()`` where ``i % k == 0``.  Where every host node of a graph is
a leading periodic one, ``run()`` fuses the passes between due passes
into chunks of the device-only step (the ``fused+host`` path) and hands
each due node one double-buffer copy of its arguments; the results are
bitwise those of the pass-by-pass path."""

import threading
import time
import weakref
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tracing
from repro.core import (DistTensor, ExecutionKind, Executor, Graph,
                        SumReducer, clear_executable_cache,
                        make_reduction_result)

ROOT = Path(__file__).resolve().parents[1]


def _counting_graph(seen, every=3, sleep=0.0, fail_on=None):
    """A snapshot node at the first level reading ``u``, then a device
    step ``u + 1`` and a sum: pass ``i`` starts from ``u0 + i``."""
    u = DistTensor("u", (16, 8))
    total = make_reduction_result("total")

    def snapshot(x):
        time.sleep(sleep)
        if fail_on is not None and len(seen) == fail_on:
            raise ValueError("snapshot failed")
        seen.append(np.asarray(x).copy())

    def advance(x):
        return x + 1.0

    g = Graph(name="periodic")
    g.emplace(snapshot, exec_kind=ExecutionKind.Cpu, args=(u,), every=every)
    g.split(advance, u, writes=(0,))
    g.then_reduce(u, total, SumReducer())
    return g


def _float_graph(seen, every=4):
    """A step with rounding in it, for bitwise comparisons."""
    u = DistTensor("u", (32, 16))
    m = make_reduction_result("m", init=1.0)

    def snapshot(x, s):
        seen.append((np.asarray(x).copy(), float(np.asarray(s))))

    def step(x, s):
        return x * 0.999 + jnp.sin(x) * 0.01 / s

    g = Graph(name="periodic_float")
    g.emplace(snapshot, exec_kind=ExecutionKind.Cpu, args=(u, m),
              every=every)
    g.split(step, u, m, writes=(0,))
    g.then_reduce(u, m, SumReducer())
    return g


def _start(ex, shape=(16, 8)):
    u0 = jnp.arange(np.prod(shape), dtype=jnp.float32).reshape(shape)
    return ex.init_state(u=u0)


@pytest.mark.parametrize("async_regions", [True, False])
@pytest.mark.parametrize("every,steps", [(3, 10), (1, 4), (5, 5), (7, 3)])
def test_node_runs_on_due_passes_and_sees_that_pass(every, steps,
                                                    async_regions):
    seen = []
    ex = Executor(_counting_graph(seen, every), async_regions=async_regions)
    st = ex.run(_start(ex), steps)
    u0 = np.arange(128, dtype=np.float32).reshape(16, 8)
    due = list(range(0, steps, every))
    assert len(seen) == len(due)
    for got, i in zip(seen, due):
        np.testing.assert_array_equal(got, u0 + i)
    np.testing.assert_array_equal(np.asarray(st["u"]), u0 + steps)


@pytest.mark.parametrize("async_regions", [True, False])
@pytest.mark.parametrize("donate", [True, False])
def test_fused_chunks_equal_pass_by_pass_bitwise(async_regions, donate):
    """``fused+host`` against one call a pass (each call one pass of the
    region executables, the node due in each) and the sequential
    schedule: the same final state, and the fused snapshots those the
    calls took at the due passes, bit for bit."""
    outs = {}
    for name, kw in {"fused": {},
                     "sequential": {"schedule": "sequential"}}.items():
        seen = []
        ex = Executor(_float_graph(seen), donate=donate,
                      async_regions=async_regions, **kw)
        st = ex.run(_start(ex, (32, 16)), 11)
        outs[name] = (st, seen)
    seen = []
    ex = Executor(_float_graph(seen), donate=donate,
                  async_regions=async_regions)
    st = _start(ex, (32, 16))
    for _ in range(11):
        st = ex(st)
    assert len(seen) == 11
    outs["passes"] = (st, seen[::4])
    assert len(outs["fused"][1]) == 3           # passes 0, 4, 8
    for name in ("passes", "sequential"):
        for k in ("u", "m"):
            np.testing.assert_array_equal(np.asarray(outs["fused"][0][k]),
                                          np.asarray(outs[name][0][k]))
        assert len(outs[name][1]) == 3
        for (a, sa), (b, sb) in zip(outs["fused"][1], outs[name][1]):
            np.testing.assert_array_equal(a, b)
            assert sa == sb


def test_two_periods_make_two_fused_calls_and_two_copies(tmp_path):
    """200 steps with ``every=100``: two fused calls of one trace, two
    double-buffer copies of the state, no region call; ``ripple.run``
    says ``fused+host`` and each ``ripple.host`` names its node."""
    clear_executable_cache()
    tracing.clear()
    seen = []
    ex = Executor(_counting_graph(seen, every=100))
    st = _start(ex)
    nbytes = st["u"].nbytes
    with jax.profiler.trace(str(tmp_path)):
        jax.block_until_ready(ex.run(st, 200))
    rec = tracing.recorded()
    tracing.clear()
    dispatch = [s for s in rec.spans if s.name == "ripple.dispatch"]
    assert [s.attrs["region"] for s in dispatch] == ["fused", "fused"]
    assert rec.counters["host.copy_bytes"] == 2 * nbytes
    runs = [s for s in rec.spans if s.name == "ripple.run"]
    assert [s.attrs["path"] for s in runs] == ["fused+host"]
    hosts = [s for s in rec.spans if s.name == "ripple.host"]
    assert [s.attrs["node"] for s in hosts] == ["periodic/snapshot"] * 2
    assert len(seen) == 2
    # chunks of other lengths reuse the one trace
    ex.run(ex.run(_start(ex), 250), 30)
    assert ex.cache_stats()["trace_events"] == 1


def test_sleeping_callback_reads_its_own_pass_while_chunks_donate():
    """Each snapshot sleeps while the next chunks donate ``u``: the
    double buffer keeps its own pass's state."""
    seen = []
    ex = Executor(_counting_graph(seen, every=2, sleep=0.2), donate=True)
    st = ex.run(_start(ex), 6)
    u0 = np.arange(128, dtype=np.float32).reshape(16, 8)
    assert [float(s[0, 0] - u0[0, 0]) for s in seen] == [0.0, 2.0, 4.0]
    np.testing.assert_array_equal(np.asarray(st["u"]), u0 + 6)


def test_long_run_keeps_two_due_passes_of_copies(monkeypatch):
    """Twenty due passes in one call, each callback slower than the
    chunk after it: the copies of every due pass before the previous one
    are freed before a due pass makes its own, so the copies in device
    memory are those of two due passes at most, however long the
    call."""
    from repro.core import executor

    snapshot_for_host = executor._snapshot_for_host
    copies, alive = [], []

    def counted(vals):
        out = snapshot_for_host(vals)
        alive.append(sum(c() is not None for c in copies))
        copies.extend(map(weakref.ref, executor._host_arg_leaves(out)))
        return out

    monkeypatch.setattr(executor, "_snapshot_for_host", counted)
    seen = []
    ex = Executor(_counting_graph(seen, every=2, sleep=0.05), donate=True)
    st = ex.run(_start(ex), 40)
    assert len(alive) == 20 and max(alive) == 1
    u0 = np.arange(128, dtype=np.float32).reshape(16, 8)
    for i, got in enumerate(seen):
        np.testing.assert_array_equal(got, u0 + 2 * i)
    np.testing.assert_array_equal(np.asarray(st["u"]), u0 + 40)


def test_callback_exception_reraises_at_the_drain():
    seen = []
    ex = Executor(_counting_graph(seen, every=2, fail_on=1))
    with pytest.raises(ValueError, match="snapshot failed"):
        ex.run(_start(ex), 6)
    assert len(seen) == 1
    # the executor stays usable: a later snapshot succeeds
    seen.clear()
    ex.run(_start(ex), 1)
    assert len(seen) == 1


def test_pool_thread_runs_the_due_node():
    threads = []
    u = DistTensor("u", (8,))
    g = Graph(name="thr_periodic")
    g.emplace(lambda x: threads.append(threading.current_thread().name),
              exec_kind=ExecutionKind.Cpu, args=(u,), every=2)
    g.split(lambda x: x + 1.0, u, writes=(0,))
    ex = Executor(g)
    ex.run(ex.init_state(), 4)
    assert len(threads) == 2
    assert all(t.startswith("ripple-host") for t in threads)


def _path(ex, tmp_path, steps=3):
    tracing.clear()
    with jax.profiler.trace(str(tmp_path)):
        jax.block_until_ready(ex.run(ex.init_state(), steps))
    runs = [s for s in tracing.recorded().spans if s.name == "ripple.run"]
    tracing.clear()
    return runs[0].attrs["path"]


def test_only_leading_periodic_host_graphs_take_the_fused_path(tmp_path):
    """A per-step host node, a periodic one that reads what the pass
    wrote, or a ``sync()`` keep the pass-by-pass path; a device-only
    graph keeps ``fused``."""
    u = DistTensor("u", (8,))

    def build(kind):
        g = Graph(name=f"path_{kind}")
        if kind == "leading":
            g.emplace(lambda x: None, exec_kind=ExecutionKind.Cpu,
                      args=(u,), every=2)
        g.split(lambda x: x + 1.0, u, writes=(0,))
        if kind == "per_step":
            g.then(lambda x: None, exec_kind=ExecutionKind.Cpu, args=(u,))
        elif kind == "reads_written":
            g.then(lambda x: None, exec_kind=ExecutionKind.Cpu, args=(u,),
                   every=2)
        elif kind == "sync":
            g.sync()
        return Executor(g)

    assert _path(build("leading"), tmp_path) == "fused+host"
    assert _path(build("device"), tmp_path) == "fused"
    for kind in ("per_step", "reads_written", "sync"):
        assert _path(build(kind), tmp_path) == "regions", kind


@pytest.mark.parametrize("async_regions", [True, False])
def test_periodic_node_after_a_writer_runs_on_due_passes(async_regions):
    """On the pass-by-pass path too a periodic node runs only where it is
    due, and sees the state at its place in the pass."""
    seen = []
    u = DistTensor("u", (4,))
    g = Graph(name="late_periodic")
    g.split(lambda x: x + 1.0, u, writes=(0,))
    g.then(lambda x: seen.append(float(np.asarray(x)[0])),
           exec_kind=ExecutionKind.Cpu, args=(u,), every=3)
    ex = Executor(g, async_regions=async_regions)
    ex.run(ex.init_state(), 7)
    assert seen == [1.0, 4.0, 7.0]


def test_every_is_checked_where_the_node_is_added():
    u = DistTensor("u", (4,))
    g = Graph()
    with pytest.raises(ValueError, match="host node"):
        g.split(lambda x: x, u, every=2)
    for bad in (0, -1, 1.5, True):
        with pytest.raises(ValueError, match="whole number"):
            g.emplace(lambda x: None, exec_kind=ExecutionKind.Cpu,
                      args=(u,), every=bad)
    with pytest.raises(ValueError, match="subgraphs"):
        g.emplace(Graph(), exec_kind=ExecutionKind.Cpu, every=2)


# -- the benchmark's snapshot graph against its plain reference ---------------

def _snapshot_model():
    import importlib.util
    import sys

    sys.path.insert(0, str(ROOT / "bench"))
    spec = importlib.util.spec_from_file_location(
        "bench_model_euler_snapshot",
        ROOT / "bench" / "models" / "euler_snapshot.py")
    mod = sys.modules[spec.name] = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("async_regions", [True, False])
def test_euler_snapshots_are_their_steps(async_regions):
    """The shock-bubble at 64 x 32 with a snapshot every 3 passes: each
    snapshot equals the one a call a pass takes at its step bit for bit,
    and the plain ``jax.numpy`` reference at its step within the Euler
    cells' limit."""
    model = _snapshot_model()
    cfg = {
        "nx": 64, "ny": 32, "domain": [2.0, 1.0], "gamma": 1.4,
        "mach": 3.81, "cfl": 0.4, "shock_x": 0.3,
        "bubble_centre": [0.8, 0.5], "bubble_radius": 0.2,
        "bubble_rho": 0.1, "mesh": None, "partition": [None, None],
        "overlap": False, "snapshot_every": 3}
    seed = 2**33 + 5

    def solver(ring):
        ex = Executor(model.build_solver(cfg, ring).graph,
                      async_regions=async_regions)
        return ex, ex.init_state(u=model.euler_sb.initial_state(cfg, seed))

    ring = model.HostRing(cfg)
    ex, st = solver(ring)
    ex.run(st, 7)                             # snapshots at 0, 3, 6
    assert ring.taken == 3
    fused = [u for _, u in ring.slots]        # the newest two: 3 and 6
    ring, passes = model.HostRing(cfg), []
    ex, st = solver(ring)
    for i in range(7):                        # a snapshot every call
        st = ex(st)
        if i in (3, 6):
            passes.append(ring.slots[-1][1])
    assert ring.taken == 7
    for a, b in zip(fused, passes):
        np.testing.assert_array_equal(a, b)
    for u, step in zip(fused, (3, 6)):
        err = model.euler_sb.state_error(cfg, seed, u, step)
        assert err <= model.SNAPSHOT_LIMIT, (step, err)


@pytest.mark.parametrize("shape", [(4, 64, 32), (3, 16, 128), (16, 128),
                                   (5, 7)])
def test_host_snapshot_kernel_copies_in_interpret_mode(shape):
    from repro.kernels.snapshot import host_snapshot_pallas

    x = jnp.arange(np.prod(shape), dtype=jnp.float32).reshape(shape)
    np.testing.assert_array_equal(np.asarray(jax.jit(host_snapshot_pallas)(x)),
                                  np.asarray(x))


def test_host_snapshot_kernel_refuses_rank_one():
    from repro.kernels.snapshot import host_snapshot_pallas

    with pytest.raises(ValueError, match="rank >= 2"):
        host_snapshot_pallas(jnp.zeros((8,)))


def test_one_copy_holds_every_argument(tmp_path):
    """A due node with a record, a scalar and a constant argument: one
    ``ripple_host_snapshot`` call copies both arrays, and
    ``host.copy_bytes`` counts their bytes once."""
    from repro.core.executor import _snapshot_for_host
    from repro.core.layout import Layout, RecordArray, RecordSpec

    spec = RecordSpec.create("a", "b")
    rec = RecordArray(jnp.ones((2, 8)), spec, Layout.SOA)
    s = jnp.float32(3.0)
    tracing.clear()
    with jax.profiler.trace(str(tmp_path)):
        out = _snapshot_for_host([rec, s, 7])
    copied = tracing.recorded().counters["host.copy_bytes"]
    tracing.clear()
    assert copied == rec.data.nbytes + s.nbytes
    assert isinstance(out[0], RecordArray) and out[0].layout is Layout.SOA
    assert out[0].data is not rec.data and out[1] is not s and out[2] == 7
    np.testing.assert_array_equal(np.asarray(out[0].data), 1.0)
