"""Multi-device behaviour (8 fake CPU devices via subprocess — jax locks
the device count at first init, so these cannot run in the main pytest
process; see conftest.run_subprocess_devices)."""

import pytest

from conftest import run_subprocess_devices


@pytest.mark.slow
def test_halo_exchange_multidevice():
    run_subprocess_devices("""
import jax, jax.numpy as jnp, numpy as np
from repro.core import (DistTensor, Graph, Executor, Boundary,
                        concurrent_padded_access, make_mesh)
mesh = make_mesh((4,), ("gx",))
size = 64
src = DistTensor("src", (size,), partition=("gx",), halo=(1,),
                 boundary=Boundary.TRANSMISSIVE)
dst = DistTensor("dst", (size,), partition=("gx",))
for overlap in (False, True):
    g = Graph()
    g.split(lambda s, d: s[2:] - s[:-2], concurrent_padded_access(src), dst,
            overlap=overlap)
    ex = Executor(g, mesh=mesh)
    x0 = jnp.arange(size, dtype=jnp.float32) ** 2
    st = ex.init_state(src=x0)
    st = ex(st)
    xp = np.pad(np.arange(size, dtype=np.float64) ** 2, 1, mode="edge")
    np.testing.assert_allclose(np.asarray(st["dst"]), xp[2:] - xp[:-2])
print("OK")
""")


@pytest.mark.slow
def test_halo_corners_2d_all_policies():
    """2-D-partitioned halo exchange (edge strips + corner blocks via the
    two-phase schedule) matches the single-device reference for every
    Boundary policy, through both the synchronous and the overlapped
    lowering, on an 8-device (4x2) mesh."""
    run_subprocess_devices("""
import jax, jax.numpy as jnp, numpy as np
from repro.core import (DistTensor, Graph, Executor, Boundary,
                        concurrent_padded_access, make_mesh,
                        pad_boundary_only)

mesh = make_mesh((4, 2), ("gx", "gy"))
nx, ny = 16, 12

def sten(s, d):
    # 3x5-point stencil touching the corner halo cells; shape-polymorphic
    n0, n1 = s.shape[0] - 2, s.shape[1] - 4
    out = 0.0
    for di in range(3):
        for dj in range(5):
            out = out + (di + 1) * (dj + 1) * s[di:di + n0, dj:dj + n1]
    return out

x0 = jnp.asarray(np.random.default_rng(0).standard_normal((nx, ny)),
                 jnp.float32)
for boundary in Boundary:
    src = DistTensor("src", (nx, ny), partition=("gx", "gy"), halo=(1, 2),
                     boundary=boundary, boundary_constant=3.5)
    dst = DistTensor("dst", (nx, ny), partition=("gx", "gy"))
    outs = {}
    for overlap in (False, True):
        g = Graph()
        g.split(sten, concurrent_padded_access(src), dst, overlap=overlap)
        ex = Executor(g, mesh=mesh)
        outs[overlap] = np.asarray(ex(ex.init_state(src=x0))["dst"])
        ht = ex.plan.transfers_for_segment(0)
        assert any(h.mesh_axis == "gx" for h in ht)
        assert any(h.mesh_axis == "gy" for h in ht)
        assert any(len(h.block) == 2 for h in ht)  # corners scheduled
        assert all(h.overlapped == overlap for h in ht)
        assert not ex.plan.overlap_fallbacks
    ref_in = pad_boundary_only(x0, axis=0, width=1, boundary=boundary,
                               constant=3.5)
    ref_in = pad_boundary_only(ref_in, axis=1, width=2, boundary=boundary,
                               constant=3.5)
    ref = np.asarray(sten(ref_in, None))
    np.testing.assert_allclose(outs[False], ref, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(outs[True], ref, rtol=1e-5, atol=1e-5)
print("OK")
""")


@pytest.mark.slow
def test_euler_2d_overlap_matches_sync():
    """The flagship finite-volume path 2-D-partitioned on 8 devices:
    dimension-split AND unsplit Euler steps with overlap=True produce the
    same values as the synchronous lowering, and the plan reports the
    scheduled transfers."""
    run_subprocess_devices("""
import sys, os, jax, jax.numpy as jnp, numpy as np, repro
src_dir = os.path.dirname(os.path.dirname(os.path.abspath(repro.__file__)))
sys.path.insert(0, os.path.join(os.path.dirname(src_dir), "examples"))
from euler2d import build_solver
from repro.physics.euler import shock_bubble_init

nx, ny = 64, 32
U0 = shock_bubble_init(nx, ny)
for unsplit in (False, True):
    outs = {}
    for overlap in (False, True):
        ex, u = build_solver(nx, ny, n_devices=8, px=2, overlap=overlap,
                             unsplit=unsplit)
        state = ex.init_state(u=U0)
        state = ex.run(state, steps=5)
        outs[overlap] = np.asarray(state["u"])
        if overlap:
            ht = ex.plan.halo_transfers
            assert any(h.overlapped and h.mesh_axis == "gx" for h in ht)
            assert any(h.overlapped and h.mesh_axis == "gy" for h in ht)
            if unsplit:  # one node spans both axes -> corner blocks
                assert any(h.overlapped and len(h.block) == 2 for h in ht)
            assert not ex.plan.overlap_fallbacks
    np.testing.assert_allclose(outs[True], outs[False], rtol=1e-5,
                               atol=1e-6)
    print("unsplit" if unsplit else "split", "overlap == sync")
print("OK")
""")


@pytest.mark.slow
def test_kernel_graphs_2d_overlap():
    """The stencil (FORCE flux) and eikonal kernel graph builders run
    2-D-partitioned with overlap and match their synchronous lowering."""
    run_subprocess_devices("""
import jax, jax.numpy as jnp, numpy as np
from repro.core import (Boundary, DistTensor, Executor, Layout, RecordArray,
                        make_mesh, pad_boundary_only)
from repro.kernels.stencil.ops import make_flux_difference_graph
from repro.kernels.eikonal.ops import make_eikonal_graph
from repro.physics.euler import EULER_SPEC, shock_bubble_init

mesh = make_mesh((2, 4), ("gx", "gy"))
nx, ny = 32, 16

# FORCE flux difference over a 2-D-partitioned Euler record
u = DistTensor("u", (nx, ny), spec=EULER_SPEC, layout=Layout.SOA,
               partition=("gx", "gy"), halo=(1, 1),
               boundary=Boundary.TRANSMISSIVE)
out = DistTensor("du", (nx, ny), spec=EULER_SPEC, layout=Layout.SOA,
                 partition=("gx", "gy"))
U0 = shock_bubble_init(nx, ny)
res = {}
for overlap in (False, True):
    g = make_flux_difference_graph(u, out, 0.1, 0.2, overlap=overlap)
    ex = Executor(g, mesh=mesh)
    st = ex(ex.init_state(u=U0))
    res[overlap] = np.asarray(st["du"])
    if overlap:
        assert not ex.plan.overlap_fallbacks
        assert any(h.overlapped and len(h.block) == 2
                   for h in ex.plan.halo_transfers)
np.testing.assert_allclose(res[True], res[False], rtol=1e-5, atol=1e-6)

# eikonal FIM sweep: phi updated in place, unpadded mask sliced per strip
phi0 = jnp.full((nx, ny), 10.0).at[nx // 2, ny // 2].set(0.0)
mask0 = jnp.zeros((nx, ny), bool).at[nx // 2, ny // 2].set(True)
phi = DistTensor("phi", (nx, ny), partition=("gx", "gy"), halo=(1, 1))
mask = DistTensor("mask", (nx, ny), dtype=jnp.bool_,
                  partition=("gx", "gy"))
res = {}
for overlap in (False, True):
    g = make_eikonal_graph(phi, mask, 1.0 / nx, overlap=overlap)
    ex = Executor(g, mesh=mesh)
    st = ex.init_state(phi=phi0, mask=mask0)
    st = ex.run(st, steps=6)
    res[overlap] = np.asarray(st["phi"])
    if overlap:
        assert not ex.plan.overlap_fallbacks
np.testing.assert_allclose(res[True], res[False], rtol=1e-5, atol=1e-6)
# the sweeps actually propagated the front off the source shard
assert (res[True] < 10.0).mean() > 0.1
print("OK")
""")


@pytest.mark.slow
def test_overlap_small_shard_warns_and_falls_back():
    """Shards too thin for boundary strips: overlap degrades to the
    synchronous path with a warning + plan record, same values."""
    run_subprocess_devices("""
import warnings
import jax, jax.numpy as jnp, numpy as np
from repro.core import (DistTensor, Graph, Executor, Boundary,
                        concurrent_padded_access, make_mesh)

mesh = make_mesh((8,), ("gx",))
size = 16  # shard extent 2 == 2 * halo -> no interior left
src = DistTensor("src", (size,), partition=("gx",), halo=(1,))
dst = DistTensor("dst", (size,), partition=("gx",))
outs = {}
for overlap in (False, True):
    g = Graph()
    g.split(lambda s, d: s[2:] - s[:-2], concurrent_padded_access(src), dst,
            overlap=overlap)
    if overlap:
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            ex = Executor(g, mesh=mesh)
        assert any("falls back to synchronous" in str(x.message) for x in w)
        assert len(ex.plan.overlap_fallbacks) == 1
        assert "shard extent" in ex.plan.overlap_fallbacks[0].reason
    else:
        ex = Executor(g, mesh=mesh)
    x0 = jnp.arange(size, dtype=jnp.float32) ** 2
    outs[overlap] = np.asarray(ex(ex.init_state(src=x0))["dst"])
np.testing.assert_allclose(outs[True], outs[False])
print("OK")
""")


@pytest.mark.slow
def test_sharded_train_matches_unsharded():
    run_subprocess_devices("""
import numpy as np, jax, jax.numpy as jnp
import repro.configs as C
from repro.launch.steps import make_train_step, input_specs, param_specs
from repro.launch.mesh import make_mesh
from repro.models.lm import init_lm
from repro.models.config import ShapeCfg

mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
for arch in ("qwen3_8b", "phi3_5_moe", "recurrentgemma_9b"):
    cfg = C.get_smoke(arch)
    step_fn, opt = make_train_step(cfg, mesh)
    p_sds, _ = param_specs(cfg, mesh)
    params = init_lm(cfg, jax.random.PRNGKey(0), tp=2)[0]
    params = jax.tree.map(lambda x, s: jax.device_put(x, s.sharding),
                          params, p_sds)
    state = {"params": params, "opt": opt.init(params),
             "step": jnp.zeros((), jnp.int32)}
    shape = ShapeCfg("t", "train", 32, 8)
    bspecs = input_specs(cfg, shape, mesh)
    rng = np.random.default_rng(0)
    batch = {}
    for k, sd in bspecs.items():
        arr = (rng.integers(0, cfg.vocab_size, sd.shape).astype(np.int32)
               if k in ("tokens", "labels")
               else rng.standard_normal(sd.shape).astype(np.float32))
        batch[k] = jax.device_put(arr, sd.sharding)
    _, m = jax.jit(step_fn)(state, batch)

    step1, opt1 = make_train_step(cfg, None)
    params1 = init_lm(cfg, jax.random.PRNGKey(0), tp=2)[0]
    state1 = {"params": params1, "opt": opt1.init(params1),
              "step": jnp.zeros((), jnp.int32)}
    batch1 = {k: jnp.asarray(np.asarray(v)) for k, v in batch.items()}
    _, m1 = jax.jit(step1)(state1, batch1)
    d = abs(float(m["loss"]) - float(m1["loss"]))
    assert d < 5e-3, (arch, d)
    print(arch, "ok", d)
print("OK")
""", timeout=1800)


@pytest.mark.slow
def test_sharded_flash_decode_matches_local():
    run_subprocess_devices("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.models.attention import decode_attention, make_sharded_decode_attention
mesh = make_mesh((2, 4), ("data", "model"))
B, S, H, Hkv, D = 4, 64, 8, 2, 16
rng = np.random.default_rng(0)
q = jnp.asarray(rng.standard_normal((B, H, D)).astype(np.float32)) * 0.3
kc = jnp.asarray(rng.standard_normal((B, S, Hkv, D)).astype(np.float32)) * 0.3
vc = jnp.asarray(rng.standard_normal((B, S, Hkv, D)).astype(np.float32)) * 0.3
clen = jnp.asarray([50, 33, 64, 7], dtype=jnp.int32)
fn = make_sharded_decode_attention(mesh, batch_axes=("data",),
                                   seq_axes=("model",), heads_tp=True)
out = jax.jit(fn)(
    jax.device_put(q, NamedSharding(mesh, P("data", "model", None))),
    jax.device_put(kc, NamedSharding(mesh, P("data", "model", None, None))),
    jax.device_put(vc, NamedSharding(mesh, P("data", "model", None, None))),
    jax.device_put(clen, NamedSharding(mesh, P("data"))))
ref = decode_attention(q, kc, vc, clen)
np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                           rtol=2e-4, atol=2e-5)
print("OK")
""")


@pytest.mark.slow
def test_moe_a2a_matches_local_dispatch():
    run_subprocess_devices("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.models.common import ParamTree
from repro.models.moe import init_moe, make_moe_a2a, moe_block
mesh = make_mesh((4, 2), ("data", "model"))
E, d, f, T = 8, 16, 32, 64
pt = ParamTree(jax.random.PRNGKey(0))
init_moe(pt, d_model=d, d_ff=f, n_experts=E, name="moe")
p = pt.params["moe"]
x = jnp.asarray(np.random.default_rng(0).standard_normal((T, d))
                .astype(np.float32)) * 0.5
fn = make_moe_a2a(mesh, dp_axes=("data",), top_k=2, capacity_factor=8.0,
                  residual_tp=False)
ps = {"router": jax.device_put(p["router"], NamedSharding(mesh, P(None, None))),
      "wi": jax.device_put(p["wi"], NamedSharding(mesh, P("data", None, None, "model"))),
      "wo": jax.device_put(p["wo"], NamedSharding(mesh, P("data", "model", None)))}
xs = jax.device_put(x, NamedSharding(mesh, P("data", None)))
out, aux = jax.jit(fn)(ps, xs)
# generous capacity on both sides -> dropless -> exact match
ref, aux_ref = moe_block(p, x, top_k=2, capacity_factor=8.0)
np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                           rtol=2e-3, atol=2e-4)
# aux is the mean of per-shard estimates (GShard convention) — close to
# but not identical with the global estimate
assert abs(float(aux) - float(aux_ref)) < 0.25
print("OK")
""", timeout=1200)


@pytest.mark.slow
def test_compressed_psum_error_feedback():
    run_subprocess_devices("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.optim.compression import ErrorFeedbackState, compressed_psum
mesh = make_mesh((8,), ("data",))
rng = np.random.default_rng(0)
gs = rng.standard_normal((8, 64)).astype(np.float32)
true_mean = gs.mean(axis=0)

def run_step(g_all, resid):
    def f(g, r):
        out, ef = compressed_psum({"g": g}, "data",
                                  ef=ErrorFeedbackState({"g": r}))
        return out["g"], ef.residual["g"]
    return jax.jit(jax.shard_map(
        f, mesh=mesh, in_specs=(P("data", None), P("data", None)),
        out_specs=(P(None, None), P("data", None)), check_vma=False))(
        g_all, resid)

resid = jnp.zeros((8, 64), jnp.float32)
total = np.zeros((1, 64), np.float32)
n = 30
for _ in range(n):
    mean, resid = run_step(jnp.asarray(gs), resid)
    total += np.asarray(mean)
# error feedback: time-averaged compressed mean converges to true mean
np.testing.assert_allclose(total[0] / n, true_mean, atol=2e-2)
print("OK")
""", timeout=1200)


@pytest.mark.slow
def test_seqpar_halo_and_carry():
    run_subprocess_devices("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.models.ssm import seqpar_conv_halo, seqpar_scan_carry
mesh = make_mesh((4,), ("sp",))
B, S, C = 2, 32, 4
x = jnp.asarray(np.random.default_rng(0).standard_normal((B, S, C))
                .astype(np.float32))

def f(x_l):
    halo = seqpar_conv_halo(x_l, width=3, axis_name="sp")
    return jnp.concatenate([halo, x_l], axis=1)

out = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=(P(None, "sp", None),),
              out_specs=P(None, "sp", None), check_vma=False))(x)
# each shard's first 3 entries = previous shard's last 3 (zeros for shard 0)
out = np.asarray(out).reshape(B, 4, 8 + 3, C)
ref = np.asarray(x).reshape(B, 4, 8, C)
np.testing.assert_allclose(out[:, 0, :3], 0.0)
for i in range(1, 4):
    np.testing.assert_allclose(out[:, i, :3], ref[:, i - 1, -3:])

# linear recurrence carry: h_t = a h_{t-1} + b with constant a per shard
a = jnp.asarray(np.random.default_rng(1).uniform(0.5, 0.9, (B, S, C))
                .astype(np.float32))
b = jnp.asarray(np.random.default_rng(2).standard_normal((B, S, C))
                .astype(np.float32))

def local_scan(a_l, b_l):
    def step(h, inp):
        ai, bi = inp
        h = ai * h + bi
        return h, h
    h_last, _ = jax.lax.scan(step, jnp.zeros((B, C)),
                             (jnp.moveaxis(a_l, 1, 0), jnp.moveaxis(b_l, 1, 0)))
    return h_last

def f2(a_l, b_l):
    h_local = local_scan(a_l, b_l)
    a_total = jnp.prod(a_l, axis=1)
    incoming = seqpar_scan_carry(a_total, h_local, axis_name="sp")
    # true last state of this shard given incoming carry
    return (incoming * a_total + h_local)[:, None]

out = jax.jit(jax.shard_map(f2, mesh=mesh,
                            in_specs=(P(None, "sp", None),) * 2,
                            out_specs=P(None, "sp", None),
                            check_vma=False))(a, b)
# reference: global sequential scan, take last state of each shard
h = np.zeros((B, C), np.float32)
refs = []
for t in range(S):
    h = np.asarray(a)[:, t] * h + np.asarray(b)[:, t]
    if (t + 1) % 8 == 0:
        refs.append(h.copy())
ref = np.stack(refs, axis=1)  # (B, 4, C): last state of each shard
np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4, atol=1e-5)
print("OK")
""", timeout=1200)


@pytest.mark.slow
def test_fsdp_train_matches_unsharded():
    run_subprocess_devices("""
import numpy as np, jax, jax.numpy as jnp
import repro.configs as C
from repro.launch.steps import make_train_step, input_specs, param_specs
from repro.launch.mesh import make_mesh
from repro.models.lm import init_lm
from repro.models.config import ShapeCfg

mesh = make_mesh((2, 4), ("data", "model"))
cfg = C.get_smoke("qwen3_8b").with_(train_sharding="fsdp")
step_fn, opt = make_train_step(cfg, mesh)
p_sds, _ = param_specs(cfg, mesh)
params = init_lm(cfg, jax.random.PRNGKey(0), tp=1)[0]
params = jax.tree.map(lambda x, s: jax.device_put(x, s.sharding),
                      params, p_sds)
state = {"params": params, "opt": opt.init(params),
         "step": jnp.zeros((), jnp.int32)}
shape = ShapeCfg("t", "train", 32, 8)
bspecs = input_specs(cfg, shape, mesh)
rng = np.random.default_rng(0)
batch = {k: jax.device_put(
    rng.integers(0, cfg.vocab_size, sd.shape).astype(np.int32), sd.sharding)
    for k, sd in bspecs.items()}
_, m = jax.jit(step_fn)(state, batch)

cfg1 = cfg.with_(train_sharding="tp")
step1, opt1 = make_train_step(cfg1, None)
params1 = init_lm(cfg1, jax.random.PRNGKey(0), tp=1)[0]
state1 = {"params": params1, "opt": opt1.init(params1),
          "step": jnp.zeros((), jnp.int32)}
batch1 = {k: jnp.asarray(np.asarray(v)) for k, v in batch.items()}
_, m1 = jax.jit(step1)(state1, batch1)
d = abs(float(m["loss"]) - float(m1["loss"]))
assert d < 5e-3, d
print("OK", d)
""", timeout=1800)


@pytest.mark.slow
def test_sharded_program_does_not_depend_on_string_hashing(monkeypatch):
    """A reduction over a 2-D mesh lowers to the same program whatever
    ``PYTHONHASHSEED`` a process draws (a set's order once chose the
    all-reduce's axis order, and with it the compile-cache key)."""
    code = """
import hashlib, re
import jax.numpy as jnp
from repro.core import (DistTensor, Executor, Graph, SumReducer,
                        make_mesh, make_reduction_result)
mesh = make_mesh((2, 2), ("gx", "gy"))
u = DistTensor("u", (8, 8), partition=("gx", "gy"))
total = make_reduction_result("total")
g = Graph(name="sum2d")
g.reduce(u, total, SumReducer())
ex = Executor(g, mesh=mesh)
hlo = re.sub(r"metadata=[{][^}]*[}]", "", ex.region_hlo(ex.init_state()))
print("HLO", hashlib.sha1(hlo.encode()).hexdigest())
"""
    digests = set()
    for seed in ("0", "1"):   # ("gx", "gy") and ("gy", "gx") as set orders
        monkeypatch.setenv("PYTHONHASHSEED", seed)
        out = run_subprocess_devices(code, n_devices=4)
        digests.add(out.split("HLO ")[1].strip())
    assert len(digests) == 1


def test_mesh_call_fed_its_own_output_traces_nothing_new():
    """``init_state`` places a mesh's reduction results replicated, as
    every call returns them, so a call fed the previous call's output
    runs the program the first call traced."""
    code = """
import jax.numpy as jnp
from repro.core import (DistTensor, Executor, Graph, SumReducer,
                        make_mesh, make_reduction_result)
mesh = make_mesh((2, 2), ("gx", "gy"))
u = DistTensor("u", (8, 8), partition=("gx", "gy"))
total = make_reduction_result("total")
g = Graph(name="sum2d")
g.reduce(u, total, SumReducer())
ex = Executor(g, mesh=mesh)
state = ex.run(ex.init_state(u=jnp.ones((8, 8))), steps=2)
first = ex.cache_stats()["trace_events"]
state = ex.run(state, steps=2)
print("TRACES", first, ex.cache_stats()["trace_events"], float(state["total"]))
"""
    first, after, total = run_subprocess_devices(code, n_devices=4).split(
        "TRACES ")[1].split()
    assert int(first) >= 1 and int(after) == int(first)
    assert float(total) == 64.0
