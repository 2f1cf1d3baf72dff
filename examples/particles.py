"""Two independent particle populations on one Ripple graph (paper §7.2).

Program order writes the pusher/field/diagnostic nodes on separate
levels, but none of them share a tensor — the dependency-DAG scheduler
(``core/schedule.py``) discovers the independence and fuses them into a
single antichain inside one jit segment, so XLA overlaps all three.
Layout polymorphism rides along: the ions store AoS, the electrons
AoSoA, and the same Pallas kernel body updates both.

  PYTHONPATH=src python examples/particles.py [--n 4096] [--steps 100]
  PYTHONPATH=src python examples/particles.py --show-dag
"""

import argparse
import time

import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import (DistTensor, Executor, Graph, Layout, MaxReducer,
                        make_reduction_result)
from repro.kernels.particle.ops import PARTICLE_SPEC, particle_update
from repro.kernels.saxpy.kernel import SAXPY_SPEC
from repro.kernels.saxpy.ops import saxpy_record

DT = 0.01


def build_sim(n: int, block: int = 512):
    ions = DistTensor("ions", (n,), spec=PARTICLE_SPEC, layout=Layout.AOS)
    electrons = DistTensor("electrons", (n,), spec=PARTICLE_SPEC,
                           layout=Layout.AOSOA)
    field = DistTensor("field", (n,), spec=SAXPY_SPEC, layout=Layout.SOA)
    vmax = make_reduction_result("vmax")

    g = Graph(name="particle_step")
    # four levels in program order: the three pushers share no tensors,
    # so the DAG schedule fuses them into one antichain; the vmax reduce
    # reads the updated ions (RAW edge) and lands in the next wave
    g.split(lambda r: particle_update(r, DT, block=block), ions, writes=(0,))
    g.then_split(lambda r: particle_update(r, DT, block=block), electrons,
                 writes=(0,))
    g.then_split(lambda r: saxpy_record(r, DT, block=block), field,
                 writes=(0,))
    g.then_reduce(ions, vmax, MaxReducer(), field="v")
    return Executor(g), (ions, electrons, field), vmax


def init_fields(rng, n):
    return {
        "x": jnp.asarray(rng.standard_normal((n, 3)), jnp.float32),
        "v": jnp.asarray(rng.standard_normal((n, 3)), jnp.float32),
    }


def run(n: int, steps: int, show_dag: bool = False):
    from repro.core import RecordArray

    rng = np.random.default_rng(0)
    ex, (ions, electrons, field), vmax = build_sim(n)
    fused = ex.dag.fused_antichains()
    print(f"schedule: {len(ex._segments)} segment(s), "
          f"{len(fused)} fused antichain(s) "
          f"{[[u.label for u in w] for w in fused]}")
    if show_dag:
        print(ex.describe_dag())

    ion0, ele0 = init_fields(rng, n), init_fields(rng, n)
    fld0 = {"x": jnp.asarray(rng.standard_normal(n), jnp.float32),
            "y": jnp.zeros(n, jnp.float32)}
    state = ex.init_state(
        ions=RecordArray.from_fields(PARTICLE_SPEC, ion0, Layout.AOS),
        electrons=RecordArray.from_fields(PARTICLE_SPEC, ele0,
                                          Layout.AOSOA),
        field=RecordArray.from_fields(SAXPY_SPEC, fld0, Layout.SOA))

    t0 = time.perf_counter()
    state = ex.run(state, steps)
    wall = time.perf_counter() - t0

    # drift-free kinematics: x_t = x_0 + t*dt*v, so verify both species
    # against the closed form (and the field against its saxpy series)
    for name, init in (("ions", ion0), ("electrons", ele0)):
        t = ions if name == "ions" else electrons
        got = np.asarray(ex.read(state, t).field("x"))
        want = np.asarray(init["x"]) + steps * DT * np.asarray(init["v"])
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    got_y = np.asarray(ex.read(state, field).field("y"))
    np.testing.assert_allclose(
        got_y, steps * DT * np.asarray(fld0["x"]), rtol=1e-4, atol=1e-4)
    print(f"vmax={float(state['vmax']):.3f}; {steps} steps x {n} "
          f"particles/species ok in {wall:.2f}s "
          f"({wall / steps * 1e3:.2f} ms/step)")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=4096)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--show-dag", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()
    run(args.n, args.steps, show_dag=args.show_dag)
