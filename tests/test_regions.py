"""Region compiler (core/executor.py + core/schedule.py): segment-run
fusion into single cached executables, the plan-signature executable
cache, retrace-free run(), donation end-to-end, and host_loop
sub-executor caching."""

import warnings
from contextlib import contextmanager

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (DistTensor, ExecutionKind, Executor, Graph, Layout,
                        RecordSpec, Region, SumReducer, group_regions,
                        make_reduction_result, plan_signature,
                        preferred_layout)

SPEC = RecordSpec.create("a", "b")


def _bump_a(r):
    return r.set_field("a", r.field("a") + 1.0)


def _accum_b(r):
    return r.set_field("b", r.field("b") + r.field("a"))


def _chain_graph():
    """Device-only chain (one segment, one region, fused fori in run)."""
    u = DistTensor("u", (8, 8))
    ws = DistTensor("ws", (8, 8))
    smax = make_reduction_result("smax")
    g = Graph()
    g.split(lambda a, b: a * 2.0, u, ws)
    g.then_reduce(ws, smax, SumReducer())
    g.then_split(lambda a, s: a + s, u, smax, writes=(0,))
    return g


def build_relayout_chain(n_pairs=2, n=256):
    """``device, loop, device, loop, ...`` with AoS<->SoA relayouts at
    every segment boundary — the relayout-heavy multi-segment shape the
    region compiler exists for.  Each loop is flag-gated to run exactly
    once per pass (the preceding device segment resets its flag)."""
    r = DistTensor("r", (n,), spec=SPEC, layout=Layout.AOS)
    g = Graph(name=f"chain{n_pairs}")
    for i in range(n_pairs):
        f = DistTensor(f"f{i}", (1,))
        g.then_split(_bump_a, r, writes=(0,), layout=Layout.AOS)
        g.split(lambda x: jnp.zeros_like(x), f, writes=(0,))
        loop = Graph(name=f"loop{i}")
        loop.split(_accum_b, r, writes=(0,), layout=Layout.SOA)
        loop.split(lambda x: jnp.ones_like(x), f, writes=(0,))
        loop.conditional((lambda nm: lambda s: s[nm][0] < 0.5)(f"f{i}"))
        g.then(loop)
    return g


# -- region grouping -----------------------------------------------------------

def test_group_regions_fuses_device_and_loop_runs():
    regions = group_regions(["device", "loop", "device", "loop"])
    assert [(r.kind, r.start, r.stop) for r in regions] == [
        ("device", 0, 4)]
    regions = group_regions(["device", "host", "device", "host_loop",
                             "loop"])
    assert [(r.kind, r.start, r.stop) for r in regions] == [
        ("device", 0, 1), ("host", 1, 2), ("device", 2, 3),
        ("host_loop", 3, 4), ("device", 4, 5)]
    assert all(isinstance(r, Region) for r in regions)


def test_executor_regions_match_segments():
    ex = Executor(build_relayout_chain(), donate=False)
    assert [k for k, _ in ex._segments] == ["device", "loop", "device",
                                            "loop"]
    assert [(r.kind, len(r)) for r in ex._regions] == [("device", 4)]
    assert ex.plan.regions == ex._regions


# -- retrace-free run() --------------------------------------------------------

def test_run_fused_shares_one_trace_across_steps():
    """Satellite regression: the fused fori path must not close over
    ``steps`` — distinct step counts share one trace (checked both by
    our trace-event counter and jax's own lowering-cache size)."""
    ex = Executor(_chain_graph())
    assert ex.dag.device_only
    ex.run(ex.init_state(u=jnp.ones((8, 8))), steps=3)
    base = ex.cache_stats()["trace_events"]
    for steps in (1, 5, 17):
        ex.run(ex.init_state(u=jnp.ones((8, 8))), steps=steps)
    assert ex.cache_stats()["trace_events"] == base
    (key,) = [k for k in ex._cache.executables if k[0] == "fused"]
    assert ex._cache.executables[key].jit_fn._cache_size() == 1


def test_run_fused_values_match_stepwise_calls():
    g = _chain_graph()
    ex = Executor(g, donate=False)
    st_fused = ex.run(ex.init_state(u=jnp.ones((8, 8))), steps=3)
    st = ex.init_state(u=jnp.ones((8, 8)))
    for _ in range(3):
        st = ex(st)                      # one pass of the region executable
    for k in ("u", "ws", "smax"):
        np.testing.assert_array_equal(np.asarray(st_fused[k]),
                                      np.asarray(st[k]), err_msg=k)


def test_region_run_steady_state_is_retrace_and_dispatch_free():
    """The non-fused path: after warmup, further run() calls add zero
    traces, and the only eager relayout left is the trailing
    restore-to-initial (once per run(), not per step)."""
    ex = Executor(build_relayout_chain(), donate=False)
    ex.run(ex.init_state(), steps=2)      # warm: traces both entry variants
    warm = ex.cache_stats()
    assert warm["trace_events"] >= 1
    eager0 = ex.eager_relayouts
    ex.run(ex.init_state(), steps=10)
    after = ex.cache_stats()
    assert after["trace_events"] == warm["trace_events"]
    assert after["executables"] == warm["executables"]
    # 10 steps crossed 40 segment boundaries; only the final restore
    # (exit SoA -> initial AoS) ran eagerly
    assert ex.eager_relayouts - eager0 == 1


def test_region_dag_schedule_equals_sequential_schedule():
    """Bitwise acceptance: the region-compiled DAG schedule == the
    sequential reference schedule on the relayout-heavy chain."""
    outs = {}
    for schedule in ("dag", "sequential"):
        ex = Executor(build_relayout_chain(), donate=False,
                      schedule=schedule)
        outs[schedule] = ex.run(ex.init_state(), steps=3)
    for k in sorted(outs["dag"]):
        np.testing.assert_array_equal(np.asarray(outs["dag"][k]),
                                      np.asarray(outs["sequential"][k]),
                                      err_msg=k)


@pytest.mark.parametrize("async_regions", [True, False])
def test_run_passes_equal_stepwise_calls(async_regions):
    """A graph that cannot fuse (a per-step host node between two device
    regions): ``run(state, n)`` takes the region pass loop and equals
    ``n`` calls bit for bit, in its state and in what the host node
    saw."""
    def build(seen):
        u = DistTensor("u", (8, 8))
        g = Graph(name="passes_vs_calls")
        g.split(lambda x: x * 0.999 + jnp.sin(x) * 0.01, u, writes=(0,))
        g.then(lambda x: seen.append(np.asarray(x).copy()),
               exec_kind=ExecutionKind.Cpu, args=(u,))
        g.then_split(lambda x: x + jnp.cos(x) * 0.1, u, writes=(0,))
        return Executor(g, async_regions=async_regions)

    def u0():
        return jnp.arange(64, dtype=jnp.float32).reshape(8, 8) / 7.0

    seen_run, seen_calls = [], []
    ex = build(seen_run)
    assert ex._periodic_hosts is None            # the pass loop, not fused
    st_run = ex.run(ex.init_state(u=u0()), 4)
    ex = build(seen_calls)
    st = ex.init_state(u=u0())
    for _ in range(4):
        st = ex(st)
    np.testing.assert_array_equal(np.asarray(st_run["u"]),
                                  np.asarray(st["u"]))
    assert len(seen_run) == len(seen_calls) == 4
    for a, b in zip(seen_run, seen_calls):
        np.testing.assert_array_equal(a, b)


# -- plan signature + executable cache -----------------------------------------

def test_plan_signature_stable_across_rebuilds():
    ex1 = Executor(build_relayout_chain(), donate=False)
    ex2 = Executor(build_relayout_chain(), donate=False)
    assert plan_signature(ex1) == plan_signature(ex2)
    assert ex1.plan.signature == ex2.plan.signature
    assert ex1._cache is ex2._cache


def test_plan_signature_discriminates():
    base = Executor(build_relayout_chain(), donate=False)
    assert plan_signature(Executor(build_relayout_chain(), donate=True)) \
        != plan_signature(base)
    assert plan_signature(Executor(build_relayout_chain(), donate=False,
                                   schedule="sequential")) \
        != plan_signature(base)
    assert plan_signature(Executor(build_relayout_chain(n=512),
                                   donate=False)) != plan_signature(base)


def test_plan_signature_keys_bound_method_receiver():
    """A bound method proxies __code__ from its function; the receiver's
    state must still key the signature (wrong cache hits are forbidden —
    a miss is merely conservative)."""
    class Scaler:
        def __init__(self, k):
            self.k = k

        def apply(self, x):
            return x * self.k

    def build(k):
        u = DistTensor("u", (8,))
        g = Graph()
        g.split(Scaler(k).apply, u, writes=(0,))
        return Executor(g, donate=False)

    assert plan_signature(build(2.0)) != plan_signature(build(3.0))


_GLOBAL_SCALE = 2.0


def _scaled_by_global(x):
    return x * _GLOBAL_SCALE


def test_plan_signature_keys_kwonly_defaults_and_globals():
    """Wrong-hit regressions: keyword-only default values and the values
    of module globals a node fn reads must key the signature."""
    def build_kw(k):
        def f(x, *, s=k):
            return x * s
        u = DistTensor("u", (8,))
        g = Graph()
        g.split(f, u, writes=(0,))
        return Executor(g, donate=False)

    assert plan_signature(build_kw(2.0)) != plan_signature(build_kw(3.0))

    def build_global():
        u = DistTensor("u", (8,))
        g = Graph()
        g.split(_scaled_by_global, u, writes=(0,))
        return Executor(g, donate=False)

    global _GLOBAL_SCALE
    s1 = plan_signature(build_global())
    _GLOBAL_SCALE = 3.0
    try:
        s2 = plan_signature(build_global())
    finally:
        _GLOBAL_SCALE = 2.0
    assert s1 != s2


def test_second_executor_reuses_executables_without_tracing():
    """The serving pattern: a re-instantiated Executor over an identical
    graph reports plan-signature cache hits and adds zero traces."""
    ex1 = Executor(build_relayout_chain(3), donate=False)
    ex1.run(ex1.init_state(), steps=2)
    before = ex1.cache_stats()
    ex2 = Executor(build_relayout_chain(3), donate=False)
    st = ex2.run(ex2.init_state(), steps=2)
    after = ex2.cache_stats()
    assert after["trace_events"] == before["trace_events"]
    assert after["builds"] == before["builds"]
    assert after["hits"] >= 2          # both entry-layout variants reused
    rec = ex2.read(st, DistTensor("r", (256,), spec=SPEC))
    np.testing.assert_allclose(np.asarray(rec.field("a")), 6.0)


def test_describe_dag_shows_regions_and_cache():
    ex = Executor(build_relayout_chain(), donate=False)
    out = ex.describe_dag()
    assert "regions (fused executables):" in out
    assert "region 0 (device): seg0..seg3 (4 segments -> 1 executable)" \
        in out
    assert f"plan signature {ex.plan.signature}" in out
    assert "executable cache:" in out


# -- donation end-to-end -------------------------------------------------------

def _ptr(arr):
    try:
        return arr.unsafe_buffer_pointer()
    except Exception:  # pragma: no cover - platform without raw pointers
        pytest.skip("unsafe_buffer_pointer unsupported on this backend")


def test_donation_reuses_state_buffers_across_region_calls():
    u = DistTensor("u", (128, 128))
    g = Graph()
    g.split(lambda x: x + 1.0, u, writes=(0,))
    ex = Executor(g, donate=True)
    st = ex.init_state()
    st1 = ex(st)
    assert st["u"].is_deleted()            # donated into the region call
    p1 = _ptr(st1["u"])
    st2 = ex(st1)
    assert st1["u"].is_deleted()
    assert _ptr(st2["u"]) == p1            # buffer recycled call-to-call


def test_donate_false_keeps_inputs_and_copies():
    u = DistTensor("u", (128, 128))
    g = Graph()
    g.split(lambda x: x + 1.0, u, writes=(0,))
    ex = Executor(g, donate=False)
    st = ex.init_state()
    p0 = _ptr(st["u"])
    st1 = ex(st)
    assert not st["u"].is_deleted()        # input still readable
    assert _ptr(st1["u"]) != p0            # output is a fresh buffer
    np.testing.assert_array_equal(np.asarray(st["u"]), 0.0)
    np.testing.assert_array_equal(np.asarray(st1["u"]), 1.0)


@contextmanager
def warnings_errored_on_donation():
    with warnings.catch_warnings():
        warnings.filterwarnings("error", message=".*[Dd]onat.*")
        yield


def test_donation_skips_layout_unstable_buffers():
    """A tensor whose layout differs between region entry and exit cannot
    be aliased; the executor must not donate it (jax would warn about an
    unusable donation) but still donates the stable entries."""
    ex = Executor(build_relayout_chain(), donate=True)
    region = ex._regions[0]
    with ex._layout_epoch():
        fn, _ = ex._region_executable(region)
    assert "r" not in fn.donate_keys       # AoS at entry, SoA at exit
    assert "f0" in fn.donate_keys and "f1" in fn.donate_keys
    with warnings_errored_on_donation():
        st = ex.run(ex.init_state(), steps=3)
    rec = ex.read(st, DistTensor("r", (256,), spec=SPEC))
    np.testing.assert_allclose(np.asarray(rec.field("a")), 6.0)


# -- host_loop sub-executor caching --------------------------------------------

def test_host_loop_sub_executor_built_once():
    """Satellite regression: the host_loop sub-Executor used to be
    re-constructed (and re-jitted) on every pass."""
    x = DistTensor("x", (8,))
    seen = []
    loop = Graph(name="dec")
    loop.split(lambda v: v - 1.0, x, writes=(0,))
    loop.then(lambda v: seen.append(float(v[0])),
              exec_kind=ExecutionKind.Cpu, args=(x,))
    loop.conditional(lambda s: s["x"][0] > 0.0)
    g = Graph()
    g.split(lambda v: jnp.full_like(v, 3.0), x, writes=(0,))
    g.then(loop)
    ex = Executor(g, donate=False)
    kinds = [k for k, _ in ex._segments]
    assert "host_loop" in kinds
    st = ex.run(ex.init_state(), steps=2)
    assert len(ex._sub_execs) == 1
    sub = next(iter(ex._sub_execs.values()))
    ex.run(st, steps=1)
    assert next(iter(ex._sub_execs.values())) is sub
    assert seen == [2.0, 1.0, 0.0] * 3
    np.testing.assert_array_equal(np.asarray(st["x"]), np.zeros(8))


# -- layout-hint interplay -----------------------------------------------------

def test_region_with_record_hints_restores_initial_layout():
    """A region whose exit layout differs from the initial one restores
    eagerly on exit — state dicts stay interchangeable outside calls."""
    t = DistTensor("p", (256,), spec=SPEC, layout=Layout.SOA)
    g = Graph()
    g.split(_bump_a, preferred_layout(t, Layout.AOS), writes=(0,))
    g.sync()
    g.split(_bump_a, preferred_layout(t, Layout.AOSOA), writes=(0,))
    ex = Executor(g, donate=False)
    assert [r.kind for r in ex._regions] == ["device", "host", "device"]
    st = ex(ex.init_state())
    assert st["p"].shape == (256, 2)       # restored to initial (AoS)
    np.testing.assert_allclose(np.asarray(ex.read(st, t).field("a")), 2.0)
