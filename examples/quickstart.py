"""Quickstart: the Ripple core API in five minutes (paper Listings 1-9).

  PYTHONPATH=src python examples/quickstart.py

The block between the ``--8<-- [start:readme]`` markers is embedded
verbatim in README.md; ``tests/test_docstrings.py`` asserts the two stay
in sync (a tested doc-example).
"""

import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import (Boundary, DistTensor, Executor, Graph, Layout,
                        RecordArray, RecordSpec, SumReducer, Vector,
                        concurrent_padded_access, execute,
                        make_reduction_result, preferred_layout, relayout)

enable_compile_cache()

# ---------------------------------------------------------------------------
# 1. Polymorphic layout (paper Listing 2): one record type, two layouts
# ---------------------------------------------------------------------------
State = RecordSpec.create("density", "pressure", Vector("vel", 2))

fields = {"density": jnp.ones((4, 4)),
          "pressure": jnp.full((4, 4), 2.0),
          "vel": jnp.zeros((4, 4, 2))}
aos = RecordArray.from_fields(State, fields, Layout.AOS)   # (*space, C)
soa = aos.with_layout(Layout.SOA)                           # (C, *space)
print("AoS storage:", aos.data.shape, "| SoA storage:", soa.data.shape)
assert float(soa.field("pressure")[0, 0]) == 2.0  # accessors hide layout

# ---------------------------------------------------------------------------
# 2. Tensors + graphs (paper Listing 7): SAXPY as a split node
#    (this block is the README's tested quickstart snippet)
# ---------------------------------------------------------------------------
# --8<-- [start:readme]
import jax.numpy as jnp
import numpy as np

from repro.core import DistTensor, Executor, Graph

size = 1024
x = DistTensor("x", (size,))
y = DistTensor("y", (size,))

g = Graph()
g.split(lambda a, xs, ys: a * xs + ys, 2.0, x, y)   # writes y (last arg)

ex = Executor(g)            # tune="auto" would measure layouts/tiles too
state = ex.init_state(x=jnp.arange(size, dtype=jnp.float32),
                      y=jnp.ones(size, jnp.float32))
state = ex.run(state, steps=1)
assert (np.asarray(state["y"]) == 2 * np.arange(size) + 1).all()
print(ex.plan.describe())   # schedule + regions + cache + tuning report
# --8<-- [end:readme]

# ---------------------------------------------------------------------------
# 3. Reduction + conditional (paper Listings 8/9): map-reduce loop
# ---------------------------------------------------------------------------
t = DistTensor("t", (256,))
total = make_reduction_result("total")

init = Graph(name="init")
init.split(lambda v: jnp.full_like(v, 3.0), t, writes=(0,))

loop = Graph(name="map_reduce")
loop.split(lambda v: v - 1.0, t, writes=(0,))
loop.then_reduce(t, total, SumReducer())
loop.conditional(lambda s: s["total"] != 0.0)

main = Graph()
main.emplace(init)
main.then(loop)
state = execute(main)
print("map-reduce converged: total =", float(state["total"]))

# ---------------------------------------------------------------------------
# 4. Stencils with halo (paper Listing 10): padded concurrent access
# ---------------------------------------------------------------------------
src = DistTensor("src", (64,), halo=(1,), boundary=Boundary.TRANSMISSIVE)
dst = DistTensor("dst", (64,))
g = Graph()
g.split(lambda s, d: s[2:] - s[:-2], concurrent_padded_access(src), dst)
state = execute(g, src=jnp.arange(64.0) ** 2)
print("central difference[1:4] =", np.asarray(state["dst"][1:4]))

# ---------------------------------------------------------------------------
# 5. Layout selection: user pin vs solver-chosen (paper §4.2)
# ---------------------------------------------------------------------------
# Three layouts now exist: AOS (*space, C), SOA (C, *space), and the tiled
# AOSOA (*space[:-1], n_tiles, C, tile).  relayout() converts exactly.
rec = RecordArray.from_fields(State, fields, Layout.SOA)
print("AoSoA storage:", relayout(rec, Layout.AOSOA).data.shape)

# (a) User pin: pin_layout=True forces the executor to keep your layout.
p = DistTensor("p", (4, 256), spec=State, layout=Layout.AOS, pin_layout=True)
g = Graph()
g.split(lambda r: r.set_field("density", r.field("density") + 1.0), p,
        writes=(0,))
ex = Executor(g)
print("pinned choice:", ex.plan.per_segment[0]["p"])       # Layout.AOS

# (b) Solver-chosen: annotate a node with the kernel's preferred layout
# (preferred_layout(...) or layout= on split/emplace) and the per-segment
# layout solver honors it, inserting relayout nodes at jit-segment
# boundaries when producer and consumer segments disagree.
q = DistTensor("q", (4, 256), spec=State)                   # declared SOA
g = Graph()
g.split(lambda r: r.set_field("density", r.field("density") * 2.0),
        preferred_layout(q, Layout.AOSOA), writes=(0,))
ex = Executor(g)
print("solver choice:", ex.plan.per_segment[0]["q"])        # Layout.AOSOA
print("relayout steps:", ex.plan.relayouts)                 # [] (one segment)

# (c) Measured: Executor(tune="auto") benchmarks the halo-feasible
# layouts per state key (x each kernel's tile_candidates()) with real
# timed executions, commits the argmin, and persists the decision in
# ~/.cache/repro-tune (or $REPRO_TUNE_CACHE) so the next process loads
# it with zero re-measurement:
ex = Executor(g, tune="auto")
print(ex.plan.describe_tuning())

print("\nOn a mesh, DistTensor(partition=('data',)) shards the space and")
print("the same graph runs SPMD with ppermute halo exchange - see")
print("tests/test_distributed.py and examples/euler2d.py.")
