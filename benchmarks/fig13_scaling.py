"""Paper Figs 12/13 — Euler 2D shock-bubble weak/strong scaling.

On this container all fake devices share ONE CPU core, so wall time does
NOT show parallel speedup; the transferable metrics are (a) the per-device
collective bytes (halo traffic) as the device count grows and (b) the
halo-to-compute byte ratio, which determines the TPU scaling efficiency
(halo bytes / ICI bw vs compute bytes / HBM bw).  Runs in a subprocess
with 8 virtual devices.
"""

import json
import os
import subprocess
import sys

from .common import Csv

_CHILD = r"""
import os, sys, json, time
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")
import jax, jax.numpy as jnp, numpy as np
from repro.analysis import analyze_hlo
from repro.core import (Boundary, DistTensor, Executor, Graph, Layout,
                        MaxReducer, RecordArray, SumReducer,
                        concurrent_padded_access, exclusive_padded_access,
                        make_mesh, make_reduction_result)
from repro.physics.euler import (EULER_SPEC, shock_bubble_init, sound_speed,
                                 update_dim, update_full)

def build(nx, ny, n_dev, steps):
    mesh = make_mesh((n_dev,), ("gy",))
    ux = DistTensor("u", (nx, ny), spec=EULER_SPEC, layout=Layout.SOA,
                    partition=(None, "gy"), halo=(1, 0),
                    boundary=Boundary.TRANSMISSIVE)
    uy = ux.with_(halo=(0, 1))
    lam = 1e-3
    gx = Graph(); gy_ = Graph()
    gx.split(lambda rec: RecordArray(update_dim(rec.data, 0, lam),
                                     EULER_SPEC, Layout.SOA),
             concurrent_padded_access(ux), writes=(0,))
    gy_.split(lambda rec: RecordArray(update_dim(rec.data, 1, lam),
                                      EULER_SPEC, Layout.SOA),
              concurrent_padded_access(uy), writes=(0,), overlap=True)
    g = Graph(); g.emplace(gx); g.then(gy_)
    ex = Executor(g, mesh=mesh)
    return ex

def build2d(nx, ny, px, py, overlap):
    # 2-D decomposition, one unsplit 2-D-stencil node: the halo schedule
    # spans both mesh axes (edge strips + corner blocks)
    mesh = make_mesh((px, py), ("gx", "gy"))
    u = DistTensor("u", (nx, ny), spec=EULER_SPEC, layout=Layout.SOA,
                   partition=("gx", "gy"), halo=(1, 1),
                   boundary=Boundary.TRANSMISSIVE)
    lam = 1e-3
    g = Graph()
    g.split(lambda rec: RecordArray(update_full(rec.data, lam, lam),
                                    EULER_SPEC, Layout.SOA),
            concurrent_padded_access(u), writes=(0,), overlap=overlap)
    return Executor(g, mesh=mesh)

def build_sched(nx, ny, n_dev, schedule):
    # full euler step (wavespeed -> smax/mass reductions -> update): the
    # DAG schedule fuses the independent mass reduction into the
    # wavespeed antichain; sequential runs the four levels in order
    mesh = make_mesh((n_dev,), ("gy",))
    u = DistTensor("u", (nx, ny), spec=EULER_SPEC, layout=Layout.SOA,
                   partition=(None, "gy"), halo=(0, 1),
                   boundary=Boundary.TRANSMISSIVE)
    ws = DistTensor("ws", (nx, ny), partition=(None, "gy"))
    smax = make_reduction_result("smax", init=1.0)
    mass = make_reduction_result("mass")

    def wavespeeds(rec, _ws):
        U = rec.data
        c = sound_speed(U)
        return jnp.maximum(jnp.abs(U[2] / U[0]) + c,
                           jnp.abs(U[3] / U[0]) + c)

    def upd(rec, s):
        return RecordArray(update_dim(rec.data, 1, 4e-4 / s), EULER_SPEC,
                           Layout.SOA)

    g = Graph()
    g.split(wavespeeds, u, ws)
    g.then_reduce(ws, smax, MaxReducer())
    g.then_reduce(u, mass, SumReducer(), field="rho")
    g.then_split(upd, exclusive_padded_access(u), smax, writes=(0,))
    return Executor(g, mesh=mesh, schedule=schedule)

def measure(ex, state, reps=5):
    t0 = time.perf_counter()
    state = ex(state)  # warm/compile: trace + compile + first run
    jax.block_until_ready(jax.tree.leaves(state))
    first = (time.perf_counter() - t0) * 1e3
    t0 = time.perf_counter()
    for _ in range(reps):
        state = ex(state)
    jax.block_until_ready(jax.tree.leaves(state))
    dt = (time.perf_counter() - t0) / reps * 1e3
    # the region compiler's executable for the (single) device region
    a = analyze_hlo(ex.region_hlo(state))
    return state, first, dt, a

out = []
base = 128
for mode in ("weak", "strong"):
    for n_dev in (1, 2, 4, 8):
        if mode == "weak":
            nx, ny = base, base * n_dev   # constant cells per device
        else:
            nx, ny = base, base * 8       # fixed global problem
        ex = build(nx, ny, n_dev, 1)
        state = ex.init_state(u=shock_bubble_init(nx, ny))
        state, first, dt, a = measure(ex, state)
        out.append(dict(mode=mode, n_dev=n_dev, nx=nx, ny=ny,
                        first_call_ms=first, ms_per_step=dt,
                        halo_bytes_per_dev=a["collective_link_bytes"],
                        hlo_bytes_per_dev=a["bytes"]))

# 2-D mesh: overlapped vs synchronous halo scheduling on the same problem
nx = ny = 2 * base
ref = None
for overlap in (False, True):
    ex = build2d(nx, ny, 2, 4, overlap)
    state = ex.init_state(u=shock_bubble_init(nx, ny))
    state, first, dt, a = measure(ex, state)
    u_out = np.asarray(state["u"])
    if ref is None:
        ref = u_out
    else:
        np.testing.assert_allclose(u_out, ref, rtol=1e-5, atol=1e-6)
    out.append(dict(mode="2d-overlap" if overlap else "2d-sync",
                    n_dev=8, nx=nx, ny=ny, first_call_ms=first,
                    ms_per_step=dt,
                    halo_bytes_per_dev=a["collective_link_bytes"],
                    hlo_bytes_per_dev=a["bytes"]))

# DAG vs sequential scheduling on the full euler step: value-equal
# (bitwise) by construction, but the DAG fuses the independent mass
# reduction into the wavespeed antichain (one fewer serialized wave)
nx, ny = base, 2 * base
ref = None
for schedule in ("sequential", "dag"):
    ex = build_sched(nx, ny, 8, schedule)
    state = ex.init_state(u=shock_bubble_init(nx, ny))
    state, first, dt, a = measure(ex, state)
    u_out = np.asarray(state["u"])
    if ref is None:
        ref = u_out
    else:
        np.testing.assert_array_equal(u_out, ref)
    n_fused = len(ex.plan.dag.fused_antichains())
    assert (n_fused >= 1) == (schedule == "dag"), (schedule, n_fused)
    out.append(dict(mode=f"sched-{schedule}", n_dev=8, nx=nx, ny=ny,
                    first_call_ms=first, ms_per_step=dt,
                    halo_bytes_per_dev=a["collective_link_bytes"],
                    hlo_bytes_per_dev=a["bytes"]))
print("JSON" + json.dumps(out))
"""


def main() -> list[dict]:
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    # virtual CPU devices by design: the child never contends for a chip
    # that this (parent) process may hold
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                         capture_output=True, text=True, timeout=1800)
    if res.returncode != 0:
        print(res.stdout)
        print(res.stderr)
        raise RuntimeError("fig13 child failed")
    data = json.loads(res.stdout.split("JSON", 1)[1])
    csv = Csv("mode", "devices", "grid", "first_call_ms",
              "ms_per_step(1-core-caveat)",
              "halo_bytes_per_dev", "hlo_bytes_per_dev", "halo_fraction")
    for r in data:
        frac = r["halo_bytes_per_dev"] / max(r["hlo_bytes_per_dev"], 1)
        csv.row(r["mode"], r["n_dev"], f"{r['nx']}x{r['ny']}",
                r["first_call_ms"], r["ms_per_step"],
                int(r["halo_bytes_per_dev"]),
                int(r["hlo_bytes_per_dev"]), frac)
    return csv.dicts()


if __name__ == "__main__":
    main()
