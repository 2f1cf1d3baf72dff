"""Dependency-DAG scheduler (core/schedule.py): structural semantics,
executor integration, and the property-tested dag == sequential
equivalence harness (multi-device equivalence runs in subprocesses)."""

import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (DistTensor, ExecutionKind, Executor, Graph, Layout,
                        SumReducer, build_dag, dag_segments, execute,
                        make_reduction_result, node_access,
                        sequential_segments)

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _graph_gen import build_random_graph  # noqa: E402

from conftest import run_subprocess_devices  # noqa: E402

LAYOUTS = (Layout.AOS, Layout.SOA, Layout.AOSOA)


# -- access footprints ---------------------------------------------------------

def test_node_access_split_reads_all_args_writes_declared():
    a = DistTensor("a", (8,))
    b = DistTensor("b", (8,))
    g = Graph()
    g.split(lambda x, y: y, a, b)          # default: writes last tensor arg
    node = next(g.nodes())
    reads, writes = node_access(node)
    assert reads == {"a", "b"}
    assert writes == {"b"}


def test_node_access_reduce_writes_result():
    a = DistTensor("a", (8,))
    res = make_reduction_result("total")
    g = Graph()
    g.reduce(a, res, SumReducer())
    reads, writes = node_access(next(g.nodes()))
    assert (reads, writes) == ({"a"}, {"total"})


def test_node_access_host_node_never_writes():
    a = DistTensor("a", (8,))
    g = Graph()
    g.then(lambda x: None, exec_kind=ExecutionKind.Cpu, args=(a,),
           writes=(0,))
    reads, writes = node_access(next(g.nodes()))
    assert reads == {"a"}
    assert writes == frozenset()  # executor calls host fns for effects only


# -- DAG structure -------------------------------------------------------------

def _chain_graph():
    """u -> ws -> smax -> u (strict chain: nothing to fuse)."""
    u = DistTensor("u", (8, 8))
    ws = DistTensor("ws", (8, 8))
    smax = make_reduction_result("smax")
    g = Graph()
    g.split(lambda a, b: a * 2.0, u, ws)
    g.then_reduce(ws, smax, SumReducer())
    g.then_split(lambda a, s: a + s, u, smax, writes=(0,))
    return g


def test_dag_chain_has_no_antichain():
    ex = Executor(_chain_graph())
    assert [k for k, _ in ex._segments] == ["device"]
    assert ex.dag.fused_antichains() == []
    # raw edges carry the state key that created them
    reasons = {(e.reason, e.key) for e in ex.dag.edges}
    assert ("raw", "ws") in reasons and ("raw", "smax") in reasons


def test_dag_fuses_independent_levels_into_antichain():
    """A then-separated independent reduction hoists into wave 0 — the
    cross-level fusion program order would have serialized."""
    u = DistTensor("u", (8, 8))
    ws = DistTensor("ws", (8, 8))
    smax = make_reduction_result("smax")
    mass = make_reduction_result("mass")
    g = Graph()
    g.split(lambda a, b: a * 2.0, u, ws)
    g.then_reduce(ws, smax, SumReducer())
    g.then_reduce(u, mass, SumReducer())    # independent of ws/smax
    ex = Executor(g)
    fused = ex.dag.fused_antichains()
    assert len(fused) == 1 and len(fused[0]) == 2
    assert {u_.segment for u_ in fused[0]} == {0}
    assert "antichain x2" in ex.describe_dag()

    ex_seq = Executor(g, schedule="sequential")
    waves = [len(w) for w in ex_seq.dag.antichains()]
    assert waves == [1, 1, 1]               # program order: one per level


def test_dag_hoists_independent_device_node_past_host():
    """A device node with no dependency on a host callback fuses into the
    segment *before* it — the host node no longer cuts the jit in two."""
    a = DistTensor("a", (16,))
    b = DistTensor("b", (16,))
    seen = []
    g = Graph()
    g.split(lambda x: x + 1.0, a, writes=(0,))
    g.then(lambda x: seen.append(float(x[0])), exec_kind=ExecutionKind.Cpu,
           args=(a,))
    g.then_split(lambda x: x * 3.0, b, writes=(0,))  # independent of a
    ex = Executor(g, donate=False)
    assert [k for k, _ in ex._segments] == ["device", "host"]
    ex_seq = Executor(g, donate=False, schedule="sequential")
    assert [k for k, _ in ex_seq._segments] == ["device", "host", "device"]
    st = ex(ex.init_state(b=jnp.ones(16)))
    assert seen == [1.0]
    np.testing.assert_array_equal(np.asarray(st["b"]), np.full(16, 3.0))


def test_sync_remains_full_barrier():
    """sync() orders against everything, even data-independent nodes."""
    a = DistTensor("a", (8,))
    b = DistTensor("b", (8,))
    g = Graph()
    g.split(lambda x: x + 1.0, a, writes=(0,))
    g.sync()
    g.then_split(lambda x: x + 1.0, b, writes=(0,))  # independent of a
    ex = Executor(g)
    assert [k for k, _ in ex._segments] == ["device", "host", "device"]


def test_host_nodes_keep_program_order():
    """Two data-independent host callbacks must fire in program order
    (side effects are invisible to the footprint analysis)."""
    a = DistTensor("a", (8,))
    b = DistTensor("b", (8,))
    seen = []
    g = Graph()
    g.split(lambda x: x + 1.0, a, writes=(0,))
    g.split(lambda x: x + 2.0, b, writes=(0,))
    g.then(lambda x: seen.append(("a", float(x[0]))),
           exec_kind=ExecutionKind.Cpu, args=(a,))
    g.then(lambda x: seen.append(("b", float(x[0]))),
           exec_kind=ExecutionKind.Cpu, args=(b,))
    ex = Executor(g, donate=False)
    ex(ex.init_state())
    assert seen == [("a", 1.0), ("b", 2.0)]


def test_opaque_host_callback_stays_put():
    """A host node with no tensor args has an invisible footprint: it is
    pinned as a barrier, not hoisted to the front."""
    a = DistTensor("a", (8,))
    seen = []
    g = Graph()
    g.split(lambda x: x + 1.0, a, writes=(0,))
    g.then(lambda: seen.append("cb"), exec_kind=ExecutionKind.Cpu)
    g.then_split(lambda x: x * 2.0, a, writes=(0,))
    ex = Executor(g, donate=False)
    assert [k for k, _ in ex._segments] == ["device", "host", "device"]
    st = ex(ex.init_state())
    np.testing.assert_array_equal(np.asarray(st["a"]), np.full(8, 2.0))
    assert seen == ["cb"]


def test_loop_vertex_orders_conservatively():
    """A conditional subgraph reads the whole state (opaque predicate):
    it must wait for every earlier writer and hold back later writers."""
    x = DistTensor("x", (8,))
    loop = Graph(name="dec")
    loop.split(lambda v: v - 1.0, x, writes=(0,))
    loop.conditional(lambda s: s["x"][0] > 0.0)
    g = Graph()
    g.split(lambda v: jnp.full_like(v, 3.0), x, writes=(0,))
    g.then(loop)
    g.then_split(lambda v: v + 10.0, x, writes=(0,))
    for mode in ("dag", "sequential"):
        ex = Executor(g, donate=False, schedule=mode)
        kinds = [k for k, _ in ex._segments]
        assert kinds == ["device", "loop", "device"], mode
        st = ex(ex.init_state())
        np.testing.assert_array_equal(np.asarray(st["x"]), np.full(8, 10.0))


def test_schedule_rejects_unknown_mode():
    g = Graph()
    g.split(lambda x: x, DistTensor("x", (4,)), writes=(0,))
    with pytest.raises(ValueError, match="schedule"):
        Executor(g, schedule="eager")


def test_describe_dag_lists_hoisted_transfers():
    from repro.core import concurrent_padded_access
    src = DistTensor("src", (8, 6), halo=(1, 1))
    dst = DistTensor("dst", (8, 6))
    g = Graph()
    g.split(lambda s, d: s[1:-1, 1:-1], concurrent_padded_access(src), dst)
    ex = Executor(g)
    out = ex.describe_dag()
    assert "seg0 transfers: src 8 blocks" in out
    assert "hoisted to segment entry" in out
    assert all(h.nbytes > 0 for h in ex.plan.halo_transfers)


# -- run() fast path (satellite: consult the scheduler) ------------------------

def test_run_fast_path_consults_scheduler():
    g = _chain_graph()
    ex = Executor(g)
    assert ex.dag.device_only
    st = ex.run(ex.init_state(u=jnp.ones((8, 8))), steps=3)
    assert any(k[0] == "fused" for k in ex._fetched)  # fused fori path


def test_run_host_node_mid_graph_breaks_fusion():
    """Regression: a host node anywhere in the graph must run once per
    step — run() may not take the fused fori_loop path."""
    x = DistTensor("x", (8,))
    seen = []
    g = Graph()
    g.split(lambda v: v + 1.0, x, writes=(0,))
    g.then(lambda v: seen.append(float(v[0])), exec_kind=ExecutionKind.Cpu,
           args=(x,))
    g.then_split(lambda v: v * 2.0, x, writes=(0,))
    for mode in ("dag", "sequential"):
        seen.clear()
        ex = Executor(g, donate=False, schedule=mode)
        assert not ex.dag.device_only
        st = ex.run(ex.init_state(), steps=3)
        # x: 0 ->(+1) 1 ->(*2) 2 ->(+1) 3 ->(*2) 6 ->(+1) 7 ->(*2) 14
        assert seen == [1.0, 3.0, 7.0], mode
        np.testing.assert_array_equal(np.asarray(st["x"]), np.full(8, 14.0))


# -- property tests: schedule validity + value equivalence ---------------------

@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 10_000), layout=st.sampled_from(list(LAYOUTS)))
def test_prop_dag_schedule_is_valid(seed, layout):
    """Structural soundness on random graphs: every edge respects the
    (segment, wave) order, every unit is placed exactly once, and
    same-level conflicting device units share a wave."""
    g, _, _ = build_random_graph(seed, layout)
    dag = build_dag(g)
    segments = dag_segments(dag)
    pos = {u.uid: (u.segment, u.wave) for u in dag.units}
    assert all(p != (-1, -1) for p in pos.values())
    for e in dag.edges:
        assert pos[e.src] < pos[e.dst], (e, pos[e.src], pos[e.dst])
    placed = sum(len(w) for k, p in segments if k == "device" for w in p)
    placed += sum(1 for k, _ in segments if k != "device")
    assert placed == len(dag.units)
    # sequential placement covers the same nodes with the same semantics
    seq = sequential_segments(g)
    seq_nodes = [n for k, p in seq if k == "device" for w in p for n in w]
    dag_nodes = [n for k, p in segments if k == "device" for w in p
                 for n in w]
    assert {id(n) for n in seq_nodes} == {id(n) for n in dag_nodes}


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000), layout=st.sampled_from(list(LAYOUTS)))
def test_prop_dag_equals_sequential(seed, layout):
    """The acceptance bar: identical final state under both schedules,
    for random graphs, across all three record layouts (single device;
    2/8-device meshes in the slow subprocess tests below)."""
    g, overrides, keys = build_random_graph(seed, layout)
    outs = {}
    for mode in ("dag", "sequential"):
        ex = Executor(g, donate=False, schedule=mode)
        outs[mode] = ex(ex.init_state(**overrides()))
    for k in keys:
        np.testing.assert_array_equal(
            np.asarray(outs["dag"][k]), np.asarray(outs["sequential"][k]),
            err_msg=f"seed={seed} layout={layout} key={k}")


def test_kernel_builders_compose_into_one_dag_segment():
    """make_*_graph(graph=...) appends to an existing builder: two flux
    kernels over disjoint tensors, written on separate program levels,
    fuse into one antichain and match their standalone results."""
    from repro.core import Boundary
    from repro.kernels.stencil.ops import make_flux_difference_graph
    from repro.physics.euler import EULER_SPEC, shock_bubble_init

    def mk(i):
        u = DistTensor(f"u{i}", (16, 8), spec=EULER_SPEC, layout=Layout.SOA,
                       halo=(1, 1), boundary=Boundary.TRANSMISSIVE)
        out = DistTensor(f"f{i}", (16, 8), spec=EULER_SPEC,
                         layout=Layout.SOA)
        return u, out

    (u0, f0), (u1, f1) = mk(0), mk(1)
    g = Graph(name="two_flux")
    make_flux_difference_graph(u0, f0, 0.1, 0.1, overlap=False, graph=g)
    g.then()  # second kernel one program level later
    make_flux_difference_graph(u1, f1, 0.2, 0.2, overlap=False, graph=g)
    ex = Executor(g, donate=False)
    fused = ex.dag.fused_antichains()
    assert fused and len(fused[0]) == 2
    init = shock_bubble_init(16, 8)
    st = ex(ex.init_state(u0=init, u1=2.0 * init))
    for i, (u, f, lam, scale) in enumerate(
            [(u0, f0, 0.1, 1.0), (u1, f1, 0.2, 2.0)]):
        solo = make_flux_difference_graph(u, f, lam, lam, overlap=False)
        ex1 = Executor(solo, donate=False)
        ref = ex1(ex1.init_state(**{f"u{i}": scale * init}))
        np.testing.assert_array_equal(np.asarray(st[f.name]),
                                      np.asarray(ref[f.name]))


# -- acceptance: the examples expose fused antichains --------------------------

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_euler2d_example_fused_antichain_and_equivalence():
    sys.path.insert(0, REPO)
    from examples.euler2d import build_solver
    from repro.physics.euler import shock_bubble_init
    ex, u = build_solver(32, 16)
    fused = ex.dag.fused_antichains()
    assert fused and any(len(w) >= 2 for w in fused)
    assert "antichain x2" in ex.describe_dag()
    outs = {}
    for mode in ("dag", "sequential"):
        e = Executor(ex.graph, donate=False, schedule=mode)
        st = e.init_state(u=shock_bubble_init(32, 16))
        outs[mode] = e.run(st, 3)
    for k in ("u", "ws", "smax", "mass"):
        np.testing.assert_array_equal(np.asarray(outs["dag"][k]),
                                      np.asarray(outs["sequential"][k]),
                                      err_msg=k)


def test_particles_example_fused_antichain():
    sys.path.insert(0, REPO)
    from examples.particles import build_sim
    ex, _tensors, _vmax = build_sim(1024)
    fused = ex.dag.fused_antichains()
    assert any(len(w) >= 3 for w in fused)
    assert "antichain x3" in ex.describe_dag()


_CHILD_EQUIV = r"""
import sys
sys.path.insert(0, {tests_dir!r})
import numpy as np
from repro.core import Executor, Layout, make_mesh
from _graph_gen import build_random_graph

mesh = make_mesh(({n},), ("gx",))
for seed in range({seeds}):
    for layout in (Layout.AOS, Layout.SOA, Layout.AOSOA):
        g, overrides, keys = build_random_graph(seed, layout,
                                                partition=("gx",))
        outs = []
        for mode in ("dag", "sequential"):
            ex = Executor(g, mesh=mesh, donate=False, schedule=mode)
            outs.append(ex(ex.init_state(**overrides())))
        for k in keys:
            np.testing.assert_array_equal(
                np.asarray(outs[0][k]), np.asarray(outs[1][k]),
                err_msg=f"seed={{seed}} layout={{layout}} key={{k}}")
print("EQUIV-OK")
"""


@pytest.mark.slow
@pytest.mark.parametrize("n_devices,seeds", [(2, 6), (8, 4)])
def test_dag_equals_sequential_multidevice(n_devices, seeds):
    tests_dir = os.path.dirname(os.path.abspath(__file__))
    out = run_subprocess_devices(
        _CHILD_EQUIV.format(tests_dir=tests_dir, n=n_devices, seeds=seeds),
        n_devices=n_devices)
    assert "EQUIV-OK" in out
