"""FORCE flux-difference Pallas kernel (paper §7.3, Table 4).

Stencil over a haloed 2-D Euler state record:

* the haloed input stays in ``ANY`` (HBM) memory space; each grid program
  DMAs its halo-inclusive tile ``(bx+2, by+2)`` into a VMEM scratch
  buffer — this IS the paper's ``in_shared()`` staging on TPU (DESIGN.md
  §2 C2); the FORCE coefficients ride in SMEM;
* the kernel reads SoA tiles, which arrive component-major (zero
  relayout).  A DMA window must span whole (8, 128) memory tiles, which
  an AoS record's 4-wide minor dim cannot, so AoS (and AoSoA) inputs are
  relayouted at the wrapper boundary — the layout cost the paper
  measures;
* block shape = the paper's sub-partition knob (§4.1), hardware-aligned
  to multiples of (8, 128) for the VPU.

Its body traces ``euler.flux_difference``, whose jnp formula stays jnp.
The dimension-split update that the Euler solvers run,
``euler.update_dim``, has its own kernel beside this one (``update.py``),
which it calls on a TPU.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.layout import Layout, RecordArray
from repro.kernels import interpret_mode
from repro.physics import euler
from repro.tuning.tiles import register_tile_kernel

# dispatch metadata consumed by ops.py and the executor's layout solver:
# the halo-inclusive tile walk DMAs component planes, so AoS and AoSoA
# inputs are relayouted at the wrapper boundary (exactly what the solver
# would emit)
SUPPORTED_LAYOUTS = (Layout.SOA,)
PREFERRED_LAYOUT = Layout.SOA
TILE_KERNEL = "flux"      # name in the autotuner's tile registry
DEFAULT_BLOCK = (8, 128)


def tile_candidates(shape: tuple[int, ...]) -> tuple[tuple[int, int], ...]:
    """Feasible ``(bx, by)`` VMEM tile shapes for an interior of
    ``(nx, ny)`` cells (the autotuner's search axis): multiples of the
    (8, 128) VPU tile that tile the interior exactly — Mosaic refuses an
    output block narrower than 128 lanes — and the halo-inclusive load
    handles the +2 ring."""
    nx, ny = shape
    return tuple((bx, by)
                 for bx in (8, 16, 32, 64) if bx <= nx and nx % bx == 0
                 for by in (128, 256) if by <= ny and ny % by == 0)


register_tile_kernel(TILE_KERNEL, tile_candidates)


def _flux_kernel(bx: int, by: int, u_ref, lam_ref, o_ref, tile_ref, sem):
    i = pl.program_id(0)
    j = pl.program_id(1)
    # stage the halo-inclusive tile into VMEM (paper's shared-memory load)
    _, wx, wy = tile_ref.shape
    copy = pltpu.make_async_copy(
        u_ref.at[:, pl.ds(i * bx, wx), pl.ds(j * by, wy)], tile_ref, sem)
    copy.start()
    copy.wait()
    tile = tile_ref[:, :bx + 2, :by + 2]
    lam_x = lam_ref[0].astype(tile.dtype)
    lam_y = lam_ref[1].astype(tile.dtype)
    o_ref[...] = euler.flux_difference(tile, lam_x, lam_y)  # (4, bx, by)


def flux_difference_pallas(
    state_haloed: RecordArray,
    lam_x: float,
    lam_y: float,
    *,
    block: tuple[int, int] = (8, 128),
    interpret: bool | None = None,
) -> RecordArray:
    """Paper Table 4: sum of FORCE flux differences over both dims.

    ``state_haloed`` is an SoA record of space ``(nx+2, ny+2)``; returns
    space ``(nx, ny)``.
    """
    assert state_haloed.layout is Layout.SOA, state_haloed.layout
    nx, ny = (s - 2 for s in state_haloed.space)
    bx, by = block
    bx, by = min(bx, nx), min(by, ny)
    assert nx % bx == 0 and ny % by == 0, (nx, ny, bx, by)
    grid = (nx // bx, ny // by)

    # DMA windows are whole (8, 128) memory tiles: stage an aligned
    # superset of the halo-inclusive tile, padding the input so the last
    # window stays in bounds
    wx, wy = pl.cdiv(bx + 2, 8) * 8, pl.cdiv(by + 2, 128) * 128
    data = jnp.pad(state_haloed.data,
                   [(0, 0), (0, wx - bx - 2), (0, wy - by - 2)])
    lam = jnp.asarray([lam_x, lam_y], dtype=jnp.float32)
    out = pl.pallas_call(
        partial(_flux_kernel, bx, by),
        out_shape=jax.ShapeDtypeStruct((4, nx, ny), state_haloed.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((4, bx, by), lambda i, j: (0, i, j)),
        scratch_shapes=[pltpu.VMEM((4, wx, wy), state_haloed.dtype),
                        pltpu.SemaphoreType.DMA(())],
        interpret=interpret_mode(interpret),
    )(data, lam)
    return RecordArray(out, state_haloed.spec, Layout.SOA)
