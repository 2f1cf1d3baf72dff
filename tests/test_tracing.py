"""Spans, counters and node scopes of the executor (``repro.tracing``).

Spans and counters are recorded only while the JAX profiler records; the
device programs carry a name scope per graph node and a name per
executable whether or not it does."""

import glob
import threading

import jax
import jax.numpy as jnp
import pytest

from repro import tracing
from repro.core import (Boundary, DistTensor, ExecutionKind, Executor, Graph,
                        Layout, MaxReducer, RecordSpec,
                        clear_executable_cache, exclusive_padded_access,
                        make_reduction_result)
from repro.core.executor import _AsyncRun

from conftest import run_subprocess_devices


def _stencil_graph(name="euler_step"):
    """A one-device graph shaped like the Euler step: a haloed update and
    a max reduction that the update reads."""
    u = DistTensor("u", (32, 16), halo=(1, 0),
                   boundary=Boundary.TRANSMISSIVE)
    smax = make_reduction_result("smax", init=1.0)

    def update_x(p, s):
        return p[1:-1] - 0.1 / s * (p[2:] - p[:-2])

    g = Graph(name=name)
    g.reduce(u, smax, MaxReducer())
    g.then_split(update_x, exclusive_padded_access(u), smax, writes=(0,))
    return g


def _host_graph(seen, barrier=False):
    """device -> host callback -> device; with ``barrier`` the callback is
    a ``sync()`` (the dispatcher blocks), else a pooled host node."""
    a = DistTensor("a", (8,))
    g = Graph(name="hostchain")
    g.split(lambda x: x + 1.0, a, writes=(0,))

    def record(x=None):
        seen.append(threading.current_thread().name)

    if barrier:
        g.sync(record)
    else:
        g.then(record, exec_kind=ExecutionKind.Cpu, args=(a,))
    g.then_split(lambda x: x * 2.0, a, writes=(0,))
    return g


def _loop_graph():
    """A host loop: a host node inside a conditional subgraph."""
    c = DistTensor("c", (1,), dtype=jnp.int32)
    body = Graph(name="countdown")
    body.split(lambda x: x - 1, c, writes=(0,))
    body.then(lambda x: None, exec_kind=ExecutionKind.Cpu, args=(c,))
    body.conditional(lambda s: s["c"][0] > 0)
    g = Graph(name="outer")
    g.emplace(body)
    return g


@pytest.fixture
def record():
    tracing.clear()
    yield
    tracing.clear()


def _traced(tmp_path, fn):
    with jax.profiler.trace(str(tmp_path)):
        out = fn()
        jax.block_until_ready(out)
    return tracing.recorded()


def _named(rec, name):
    return [s for s in rec.spans if s.name == name]


def test_recording_follows_the_profiler(tmp_path, record):
    assert not tracing.recording()
    with jax.profiler.trace(str(tmp_path)):
        assert tracing.recording()
    assert not tracing.recording()


RUNS = {
    "fused": lambda: (_stencil_graph(), {}, lambda ex, s: ex.run(s, 3)),
    "call": lambda: (_stencil_graph(), {}, lambda ex, s: ex(s)),
    "passes": lambda: (_host_graph([], barrier=True), {},
                       lambda ex, s: ex.run(s, 2)),
    "host_pool": lambda: (_host_graph([]), {}, lambda ex, s: ex.run(s, 2)),
    "host_loop": lambda: (_loop_graph(), {}, lambda ex, s: ex(s)),
}


@pytest.mark.parametrize("path", sorted(RUNS))
def test_nothing_is_recorded_with_the_profiler_off(path, record):
    g, kw, go = RUNS[path]()
    ex = Executor(g, **kw)
    st = ex.init_state(c=jnp.array([3], jnp.int32)) \
        if path == "host_loop" else ex.init_state()
    jax.block_until_ready(go(ex, st))
    assert tracing.recorded() == tracing.Record([], {})


def test_fused_run_records_dispatch_under_run_and_trace_once(tmp_path,
                                                             record):
    clear_executable_cache()
    ex = Executor(_stencil_graph())
    st = ex.init_state()

    def two_calls():
        s = ex.run(st, 3)
        return ex.run(s, 3)

    rec = _traced(tmp_path, two_calls)
    runs = _named(rec, "ripple.run")
    assert [s.attrs for s in runs] == [
        {"graph": "euler_step", "steps": 3, "path": "fused"}] * 2
    assert len({s.run_id for s in runs}) == 2
    dispatch = _named(rec, "ripple.dispatch")
    assert len(dispatch) == 2
    for d, r in zip(dispatch, runs):
        assert d.parent == "ripple.run" and d.run_id == r.run_id
        assert r.t0 <= d.t0 <= d.t1 <= r.t1
        assert d.attrs == {"graph": "euler_step", "region": "fused"}
    traces = _named(rec, "ripple.trace")
    assert len(traces) == 1 and traces[0].parent == "ripple.dispatch"
    assert traces[0].run_id == runs[0].run_id


def test_init_state_records_its_span(tmp_path, record):
    ex = Executor(_stencil_graph())
    rec = _traced(tmp_path, ex.init_state)
    (s,) = _named(rec, "ripple.init_state")
    assert s.attrs == {"graph": "euler_step"} and s.parent is None


def test_pooled_host_node_keeps_the_submitting_run(tmp_path, record):
    seen = []
    ex = Executor(_host_graph(seen))
    st = ex.init_state()
    rec = _traced(tmp_path, lambda: ex.run(st, 2))
    (run,) = _named(rec, "ripple.run")
    assert run.attrs["path"] == "regions"
    hosts = _named(rec, "ripple.host")
    assert len(hosts) == 2 and len(seen) == 2
    for h in hosts:
        assert h.thread.startswith("ripple-host")
        assert h.run_id == run.run_id and h.parent == "ripple.run"
    waits = _named(rec, "ripple.host_wait")
    assert {"reason": "drain"} in [w.attrs for w in waits]
    assert all(w.run_id == run.run_id for w in waits)


@pytest.mark.parametrize("entry", ["call", "run"])
def test_host_loop_iterations_keep_the_outer_run_id(entry, tmp_path,
                                                    record):
    ex = Executor(_loop_graph())
    st = ex.init_state(c=jnp.array([3], jnp.int32))
    rec = _traced(tmp_path, lambda: ex(st) if entry == "call"
                  else ex.run(st, 2))
    (outer,) = [s for s in rec.spans if s.parent is None]
    assert outer.name == "ripple.run"
    # one sub-executor run per iteration, each under the outer run
    assert len(_named(rec, "ripple.run")) == 1 + 3
    assert {s.run_id for s in rec.spans} == {outer.run_id}


WAITS = {
    "barrier": lambda: (_host_graph([], barrier=True), {}),
    "condition": lambda: (_loop_graph(), {"c": jnp.array([2], jnp.int32)}),
    "inflight": lambda: (_host_graph([]), {}),
}


@pytest.mark.parametrize("reason", sorted(WAITS))
def test_dispatcher_waits_are_host_wait_spans(reason, tmp_path, record,
                                             monkeypatch):
    monkeypatch.setattr(_AsyncRun, "max_inflight", 1)
    g, init = WAITS[reason]()
    ex = Executor(g)
    st = ex.init_state(**init)
    rec = _traced(tmp_path, lambda: ex.run(st, 3))
    reasons = {s.attrs["reason"] for s in _named(rec, "ripple.host_wait")}
    assert reason in reasons


SPEC = RecordSpec.create("a", "b")


def _relayout_graph(n=16):
    """An AoS device node, then a loop that prefers SoA: a relayout at
    each segment boundary."""
    r = DistTensor("r", (n,), spec=SPEC, layout=Layout.AOS)
    f = DistTensor("f", (1,))
    g = Graph(name="relay")
    g.split(lambda x: x.set_field("a", x.field("a") + 1.0), r, writes=(0,),
            layout=Layout.AOS)
    g.split(lambda x: jnp.zeros_like(x), f, writes=(0,))
    loop = Graph(name="relay_loop")
    loop.split(lambda x: x.set_field("b", x.field("b") + x.field("a")), r,
               writes=(0,), layout=Layout.SOA)
    loop.split(lambda x: jnp.ones_like(x), f, writes=(0,))
    loop.conditional(lambda s: s["f"][0] < 0.5)
    g.then(loop)
    return g


def test_eager_relayouts_are_spans_and_traced_ones_a_scope(tmp_path,
                                                          record):
    ex = Executor(_relayout_graph(), donate=False)
    st = ex.init_state()
    eager0 = ex.eager_relayouts
    rec = _traced(tmp_path, lambda: ex.run(st, 2))
    spans = _named(rec, "ripple.relayout")
    assert len(spans) == ex.eager_relayouts - eager0 > 0
    assert {s.attrs["bytes"] for s in spans} == {16 * 2 * 4}
    dispatch = _named(rec, "ripple.dispatch")
    assert [s.attrs["region"] for s in dispatch] == [0, 0]
    assert "/relayout/" in ex.region_hlo(ex.init_state())


def test_xplane_host_plane_holds_the_spans(tmp_path, record):
    ex = Executor(_stencil_graph())
    st = ex.init_state()
    _traced(tmp_path, lambda: ex.run(st, 2))
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    names = {ev.name for plane in data.planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events}
    assert {"ripple.run", "ripple.dispatch"} <= names


def test_region_hlo_carries_node_scopes_and_executable_name():
    ex = Executor(_stencil_graph())
    hlo = ex.region_hlo(ex.init_state())
    assert hlo.startswith("HloModule jit_ripple_euler_step_region0")
    assert "/euler_step/update_x/" in hlo
    assert "/euler_step/update_x/halo_exchange/" in hlo
    assert "/euler_step/reduce:smax/" in hlo


def test_node_scope_labels():
    u = DistTensor("u", (8,))
    r = make_reduction_result("r")

    def step(x):
        return x + 1

    inner = Graph(name="inner")
    inner.split(step, u, writes=(0,))
    g = Graph(name="outer")
    g.split(step, u, writes=(0,))
    g.then_reduce(u, r, MaxReducer())
    g.then_split(step, u, writes=(0,))
    g.then(inner)
    scopes = sorted(g.node_scopes().values())
    assert scopes == ["inner/step", "outer/reduce:r", "outer/step[0]",
                      "outer/step[2]"]


def test_graph_name_is_part_of_the_plan_signature():
    a = Executor(_stencil_graph("euler_step"))
    b = Executor(_stencil_graph("other_step"))
    assert a.plan.signature != b.plan.signature


@pytest.mark.slow
def test_halo_bytes_count_the_planned_collective_payload():
    out = run_subprocess_devices("""
import re, tempfile
import jax, jax.numpy as jnp
from repro import tracing
from repro.core import (Boundary, DistTensor, Executor, Graph,
                        exclusive_padded_access, make_mesh)
mesh = make_mesh((2, 2), ("gx", "gy"))
u = DistTensor("u", (16, 8), partition=("gx", "gy"), halo=(1, 1),
               boundary=Boundary.TRANSMISSIVE)
g = Graph(name="sweep2d")
g.split(lambda p: 0.5 * (p[:-2] + p[2:]),
        exclusive_padded_access(u.with_(halo=(1, 0))), writes=(0,),
        overlap=True)
g.then_split(lambda p: 0.5 * (p[:, :-2] + p[:, 2:]),
             exclusive_padded_access(u.with_(halo=(0, 1))), writes=(0,),
             overlap=True)
ex = Executor(g, mesh=mesh)
st = ex.init_state(u=jnp.arange(128.0).reshape(16, 8))
st = ex.run(st, 1)
assert tracing.recorded().counters == {}
hlo = ex.region_hlo(st)
# what one pass permutes, from the compiled program: each collective-
# permute's payload times its source-target pairs, over the 4 chips
sent = 0
for dims, pairs in re.findall(
        r"= f32\\[([\\d,]*)\\]\\S* collective-permute\\(.*?"
        r"source_target_pairs=\\{(\\{.*?\\})\\}", hlo):
    nbytes = 4
    for d in dims.split(","):
        nbytes *= int(d)
    sent += nbytes * pairs.count("{")
per_chip = sent / 4
# a transmissive 2-shard axis: one chip of each pair sends each strip,
# (1, 4) float32 along gx and (8, 1) along gy
assert per_chip == 4 * 4 + 8 * 4, per_chip
with jax.profiler.trace(tempfile.mkdtemp()):
    st = ex.run(st, 3)
    st = ex(st)
    jax.block_until_ready(st)
assert tracing.recorded().counters == {"halo.bytes": per_chip * 4}   # 3+1
for scope in ("sweep2d/<lambda>[0]/halo_exchange/",
              "sweep2d/<lambda>[1]/halo_stitch/"):
    assert scope in hlo, scope
print("OK")
""", n_devices=4)
    assert "OK" in out
