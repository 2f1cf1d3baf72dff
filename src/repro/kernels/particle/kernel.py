"""Particle update Pallas kernel (paper §7.2, Table 3) — layout polymorphic.

``x += v * dt`` for N particles with 3-d position/velocity stored in ONE
record buffer as AoS ``(n, 6)``, SoA ``(6, n)`` or AoSoA
``(n_tiles, 6, tile)``.  The kernel body is written once against
:class:`RecordRef`; the layout only changes the BlockSpec.  On TPU the
SoA block streams 128-lane contiguous VREGs per component while the AoS
block wastes lanes on the 6-wide minor dim — the paper's coalescing
argument, relocated to lane tiling (DESIGN.md §2).  AoSoA keeps the
lane-filling tile minor AND whole records contiguous per tile, which is
the preferred streaming layout when no cross-particle stencil exists.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.layout import (Layout, RecordArray, RecordRef, RecordSpec,
                               Vector, record_grid_1d)
from repro.kernels import interpret_mode
from repro.tuning.tiles import register_tile_kernel

PARTICLE_SPEC = RecordSpec.create(Vector("x", 3), Vector("v", 3))

# metadata consumed by the ops.py wrapper, which relayouts inputs whose
# layout is not natively supported
SUPPORTED_LAYOUTS = (Layout.AOS, Layout.SOA, Layout.AOSOA)
PREFERRED_LAYOUT = Layout.AOSOA
TILE_KERNEL = "particle"  # name in the autotuner's tile registry
DEFAULT_BLOCK = 512


def tile_candidates(shape: tuple[int, ...]) -> tuple[int, ...]:
    """Feasible particles-per-program block sizes for ``n`` particles
    (the autotuner's search axis): exact tilings only, so no variant
    ever needs the masked tail path."""
    (n,) = shape
    return tuple(b for b in (128, 256, 512, 1024, 2048, 4096)
                 if b <= n and n % b == 0)


register_tile_kernel(TILE_KERNEL, tile_candidates)


def _particle_kernel(spec: RecordSpec, layout: Layout, dt_ref, p_ref, o_ref):
    p = RecordRef(p_ref, spec, layout)
    o = RecordRef(o_ref, spec, layout)
    for c in range(3):
        x = p.get("x", c)
        dt = dt_ref[0].astype(x.dtype)
        v = p.get("v", c)
        o.set("x", x + v * dt, c)
        o.set("v", v, c)


def particle_update_pallas(
    particles: RecordArray,
    dt: float,
    *,
    block: int = 512,
    interpret: bool | None = None,
) -> RecordArray:
    (n,) = particles.space
    spec, layout = particles.spec, particles.layout
    assert n % block == 0, f"n={n} must tile by block={block}"
    grid, bspec = record_grid_1d(spec, layout, n, block)

    # the scalar rides in SMEM (32-bit words), the record tiles in VMEM
    dt_arr = jnp.asarray(dt, dtype=jnp.float32).reshape(1)
    out = pl.pallas_call(
        partial(_particle_kernel, spec, layout),
        out_shape=jax.ShapeDtypeStruct(particles.data.shape, particles.dtype),
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), bspec],
        out_specs=bspec,
        interpret=interpret_mode(interpret),
    )(dt_arr, particles.data)
    return RecordArray(out, spec, layout)
