"""Eikonal FIM Pallas kernel (paper §7.4, Table 5).

Solves ``|grad phi| = 1/f`` (f = 1: signed-distance reinit) with the Fast
Iterative Method.  The paper's winning configuration stages a tile in
shared memory and runs several update sweeps on it before writing back;
on TPU each grid program DMAs a halo-inclusive tile into a VMEM scratch
buffer and runs ``inner`` Jacobi sweeps on it with frozen halos (the FIM
ghost-zone trade), then the outer loop (graph-level, with halo exchange + convergence
reduction — paper's conditional MapReduce) repeats until converged.

The Godunov upwind update in 2-D (f=1, grid step h):

    a = min(phi_W, phi_E);  b = min(phi_S, phi_N)
    phi' = min(a, b) + h                      if |a - b| >= h
         = (a + b + sqrt(2 h^2 - (a-b)^2))/2  otherwise
    phi  = min(phi, phi')   (monotone descent; sources pinned)
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode
from repro.tuning.tiles import register_tile_kernel

TILE_KERNEL = "eikonal"   # name in the autotuner's tile registry
DEFAULT_BLOCK = (8, 128)


def tile_candidates(shape: tuple[int, ...]) -> tuple[tuple[int, int], ...]:
    """Feasible ``(bx, by)`` FIM tile shapes for an interior of
    ``(nx, ny)`` cells (the autotuner's search axis).  Bigger tiles
    amortize the frozen-halo inner sweeps over more cells (the paper's
    ghost-zone trade); candidates are multiples of the (8, 128) VPU tile
    (Mosaic refuses a block narrower than 128 lanes) that tile the
    interior exactly."""
    nx, ny = shape
    return tuple((bx, by)
                 for bx in (8, 16, 32, 64) if bx <= nx and nx % bx == 0
                 for by in (128, 256) if by <= ny and ny % by == 0)


register_tile_kernel(TILE_KERNEL, tile_candidates)


def godunov_update(phi: jax.Array, mask: jax.Array, h: float) -> jax.Array:
    """One Jacobi sweep on a haloed tile; interior cells updated only.

    ``phi``: (m+2, n+2), or a kernel ref whose leading (m+2, n+2) corner
    is the tile; ``mask``: (m, n) True where source (pinned).  Returns the
    updated *interior* (m, n).
    """
    m, n_ = mask.shape
    w = phi[0:m, 1:n_ + 1]
    e = phi[2:m + 2, 1:n_ + 1]
    s = phi[1:m + 1, 0:n_]
    n = phi[1:m + 1, 2:n_ + 2]
    c = phi[1:m + 1, 1:n_ + 1]
    a = jnp.minimum(w, e)
    b = jnp.minimum(s, n)
    lo = jnp.minimum(a, b)
    diff = jnp.abs(a - b)
    two = jnp.asarray(2.0, phi.dtype)
    quad = 0.5 * (a + b + jnp.sqrt(jnp.maximum(two * h * h - diff * diff, 0.0)))
    new = jnp.where(diff >= h, lo + h, quad)
    new = jnp.minimum(c, new)
    return jnp.where(mask, c, new)


def _fim_kernel(bx: int, by: int, inner: int, h: float,
                phi_ref, mask_ref, o_ref, tile_ref, sem):
    i = pl.program_id(0)
    j = pl.program_id(1)
    wx, wy = tile_ref.shape
    copy = pltpu.make_async_copy(
        phi_ref.at[pl.ds(i * bx, wx), pl.ds(j * by, wy)], tile_ref, sem)
    copy.start()
    copy.wait()
    mask = mask_ref[...] != 0

    def body(_, carry):
        tile_ref[1:bx + 1, 1:by + 1] = godunov_update(tile_ref, mask, h)
        return carry

    jax.lax.fori_loop(0, inner, body, 0)
    o_ref[...] = tile_ref[1:bx + 1, 1:by + 1]


def eikonal_fim_pallas(
    phi_haloed: jax.Array,
    source_mask: jax.Array,
    h: float,
    *,
    inner: int = 4,
    block: tuple[int, int] = (8, 128),
    interpret: bool | None = None,
) -> jax.Array:
    """``inner`` VMEM-staged FIM sweeps per tile.  ``phi_haloed`` is
    (nx+2, ny+2); ``source_mask`` is (nx, ny); returns (nx, ny)."""
    nx, ny = (s - 2 for s in phi_haloed.shape)
    bx, by = (min(block[0], nx), min(block[1], ny))
    assert nx % bx == 0 and ny % by == 0, (nx, ny, bx, by)
    grid = (nx // bx, ny // by)
    # DMA windows are whole (8, 128) memory tiles: stage an aligned
    # superset of the halo-inclusive tile, padding the input so the last
    # window stays in bounds.  The mask rides as int32 (Mosaic blocks
    # hold no booleans).
    wx, wy = pl.cdiv(bx + 2, 8) * 8, pl.cdiv(by + 2, 128) * 128
    phi = jnp.pad(phi_haloed, [(0, wx - bx - 2), (0, wy - by - 2)])
    return pl.pallas_call(
        partial(_fim_kernel, bx, by, inner, h),
        out_shape=jax.ShapeDtypeStruct((nx, ny), phi_haloed.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((bx, by), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((bx, by), lambda i, j: (i, j)),
        scratch_shapes=[pltpu.VMEM((wx, wy), phi_haloed.dtype),
                        pltpu.SemaphoreType.DMA(())],
        interpret=interpret_mode(interpret),
    )(phi, source_mask.astype(jnp.int32))
