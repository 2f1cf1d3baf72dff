"""Euler 2D shock-bubble (the paper's §8 scaling application), built on
the Ripple graph API exactly as paper Listing 12: per-step wavespeed
field -> max-reduction -> CFL dt -> dimension-split FORCE updates with
halo exchange — ONE graph, built once, executed many times.

``--px`` splits the mesh over BOTH grid dims (paper Fig. 7's
multi-dimensional transfer space) and ``--overlap`` hides the halo
ppermutes behind each update's interior program; ``--unsplit`` swaps the
dimension-split updates for one 2-D-stencil node so a single node's halo
schedule spans both axes (corner blocks included).

  PYTHONPATH=src python examples/euler2d.py --nx 128 --ny 64 --steps 50
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python examples/euler2d.py --devices 8 --px 2 --overlap
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import (Boundary, DistTensor, Executor, Graph, Layout,
                        MaxReducer, RecordArray, SumReducer,
                        exclusive_padded_access, make_mesh,
                        make_reduction_result)
from repro.physics.euler import (EULER_SPEC, RHO, pressure,
                                 shock_bubble_init, sound_speed, update_dim,
                                 update_full)


def build_solver(nx: int, ny: int, n_devices: int = 1, cfl: float = 0.4,
                 px: int = 1, overlap: bool = False, unsplit: bool = False):
    dx, dy = 2.0 / nx, 1.0 / ny
    mesh = None
    partition = (None, None)
    if n_devices > 1:
        if px > 1:
            if n_devices % px:
                raise ValueError(f"--px {px} must divide --devices {n_devices}")
            mesh = make_mesh((px, n_devices // px), ("gx", "gy"))
            partition = ("gx", "gy")  # 2-D decomposition
        else:
            mesh = make_mesh((n_devices,), ("gy",))
            partition = (None, "gy")  # paper: split the higher dim

    u = DistTensor("u", (nx, ny), spec=EULER_SPEC, layout=Layout.SOA,
                   partition=partition, halo=(1, 1),
                   boundary=Boundary.TRANSMISSIVE)
    ux = u.with_(halo=(1, 0))
    uy = u.with_(halo=(0, 1))
    ws = DistTensor("ws", (nx, ny), partition=partition)
    smax = make_reduction_result("smax", init=1.0)
    mass = make_reduction_result("mass")

    def set_wavespeeds(rec, _ws):
        U = rec.data
        c = sound_speed(U)
        return jnp.maximum(jnp.abs(U[2] / U[0]) + c,
                           jnp.abs(U[3] / U[0]) + c)

    def update_x(rec, s):
        dt = cfl * min(dx, dy) / s
        return RecordArray(update_dim(rec.data, 0, dt / dx), EULER_SPEC,
                           Layout.SOA)

    def update_y(rec, s):
        dt = cfl * min(dx, dy) / s
        return RecordArray(update_dim(rec.data, 1, dt / dy), EULER_SPEC,
                           Layout.SOA)

    def update_xy(rec, s):
        # unsplit scheme: both directional fluxes share one dt bound
        dt = cfl / (s * (1.0 / dx + 1.0 / dy))
        return RecordArray(update_full(rec.data, dt / dx, dt / dy),
                           EULER_SPEC, Layout.SOA)

    # paper Listing 12: one graph per step, reduction feeds the dt.  The
    # mass diagnostic only reads u, so the DAG schedule fuses it into the
    # same antichain as the wavespeed node (describe_dag shows the wave)
    # even though program order puts it two levels later.
    g = Graph(name="euler_step")
    g.split(set_wavespeeds, u, ws)
    g.then_reduce(ws, smax, MaxReducer())
    g.then_reduce(u, mass, SumReducer(), field="rho")
    if unsplit:
        g.then_split(update_xy, exclusive_padded_access(u), smax,
                     writes=(0,), overlap=overlap)
    else:
        g.then_split(update_x, exclusive_padded_access(ux), smax,
                     writes=(0,), overlap=overlap)
        g.then_split(update_y, exclusive_padded_access(uy), smax,
                     writes=(0,), overlap=overlap)
    return Executor(g, mesh=mesh), u


def run(nx: int, ny: int, steps: int, n_devices: int = 1, px: int = 1,
        overlap: bool = False, unsplit: bool = False,
        show_dag: bool = False):
    dx, dy = 2.0 / nx, 1.0 / ny
    ex, u = build_solver(nx, ny, n_devices, px=px, overlap=overlap,
                         unsplit=unsplit)
    fused = ex.dag.fused_antichains()
    print(f"schedule: {len(ex._segments)} segment(s), "
          f"{len(fused)} fused antichain(s) "
          f"{[[un.label for un in w] for w in fused]}")
    if show_dag:
        print(ex.describe_dag())
    if overlap:
        ht = ex.plan.halo_transfers
        print(f"halo schedule: {len(ht)} blocks "
              f"({sum(1 for h in ht if h.overlapped)} overlapped, "
              f"{sum(1 for h in ht if h.mesh_axis)} ppermutes); "
              f"fallbacks: {len(ex.plan.overlap_fallbacks)}")
        for h in ht[:6]:
            print("  " + h.describe())
    U0 = shock_bubble_init(nx, ny)
    mass0 = float(jnp.sum(U0[RHO])) * dx * dy
    state = ex.init_state(u=U0)

    # warmup/compile
    t0 = time.perf_counter()
    state = ex(state)
    compile_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    chunk = 10
    for i in range(0, steps - 1, chunk):
        state = ex.run(state, steps=min(chunk, steps - 1 - i))
        U = state["u"]
        # graph-level mass reduction: it reads u in wave 0 (that's what
        # lets it fuse into the wavespeed antichain), so the value is the
        # mass at the START of the last step — labelled accordingly
        mass = float(state["mass"]) * dx * dy
        print(f"step {i + chunk:4d}: smax={float(state['smax']):.3f} "
              f"rho in [{float(U[RHO].min()):.3f}, "
              f"{float(U[RHO].max()):.3f}] "
              f"mass drift (step start) {abs(mass - mass0) / mass0:.2e}")
    wall = time.perf_counter() - t0

    U = state["u"]
    assert np.isfinite(np.asarray(U)).all()
    assert (np.asarray(U[RHO]) > 0).all()
    assert (np.asarray(pressure(U)) > 0).all()
    print(f"\n{steps} steps on {nx}x{ny} ({n_devices} device(s)): "
          f"first-step(+compile) {compile_s:.2f}s, then "
          f"{wall / max(steps - 1, 1) * 1e3:.1f} ms/step")
    return U


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--nx", type=int, default=128)
    ap.add_argument("--ny", type=int, default=64)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--devices", type=int, default=1)
    ap.add_argument("--px", type=int, default=1,
                    help="mesh extent along x (2-D decomposition when > 1)")
    ap.add_argument("--overlap", action="store_true",
                    help="hide halo ppermutes behind interior compute")
    ap.add_argument("--unsplit", action="store_true",
                    help="one 2-D-stencil update node instead of "
                         "dimension-split x/y nodes")
    ap.add_argument("--show-dag", action="store_true",
                    help="print the full dependency-DAG schedule "
                         "(describe_dag) before running")
    args = ap.parse_args()
    enable_compile_cache()
    run(args.nx, args.ny, args.steps, args.devices, px=args.px,
        overlap=args.overlap, unsplit=args.unsplit,
        show_dag=args.show_dag)
