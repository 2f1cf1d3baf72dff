"""Split FORCE update Pallas kernel: ``euler.update_dim`` in one pass.

``U' = U - lam (F_{i+1/2} - F_{i-1/2})`` along one grid dim of a haloed
SoA Euler state ``(4, n+2, m)`` (``dim`` 0) or ``(4, n, m+2)`` (``dim``
1).  XLA lowers the jnp formula to a dozen fusions that write the face
fluxes to HBM as full grids; this kernel streams the state through VMEM
once instead:

* each grid program gets an ``(4, bx, by)`` output tile, the matching
  input block and a neighbour block (the next 8 rows, or the next 128
  lanes) that holds the tile's two trailing halo cells; Pallas' BlockSpec
  pipeline DMAs the next program's blocks while this one computes.  The
  neighbour's block index is clamped to the input's last block: where the
  clamp bites, the cells it would have fed lie past the output and are
  masked on write-back, so ragged interiors (a ``pl.cdiv`` grid) need no
  fallback;
* inside a program the tile is walked in ``(8, 128)`` chunks, one vreg a
  field, along the update's direction, the walk unrolled so the scheduler
  overlaps neighbouring chunks: each chunk's FORCE face fluxes
  (``euler.force_flux_of``) are computed once and carried to the next
  chunk, and the one-cell shift between a chunk and its successor is a
  rotate of each and a select.  A face computes the physical flux of both
  its cells: carrying a cell's flux to the next face instead costs a
  rotate, which saves nothing along rows and costs time along lanes;
* float32 throughout, with the jnp formula's order of operations; the
  coefficients ``lam``, ``0.5 / lam`` and ``0.5 * lam`` (``lam`` is a
  traced scalar, from the CFL maximum) ride in SMEM.

``euler.update_dim`` calls this kernel on a TPU when the output fills at
least one ``(8, 128)`` tile, and keeps the jnp formula elsewhere; the
Table 4 kernel (``kernel.py``) traces ``euler.flux_difference`` in its
body, which stays jnp.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode
from repro.physics import euler
from repro.tuning.tiles import register_tile_kernel, resolve_tile

KERNEL_NAME = "euler_force_update"   # the custom call's name in HLO/traces
TILE_KERNEL = "force_update"         # name in the autotuner's tile registry
DEFAULT_BLOCK = (128, 1024)
SUB, LANE = 8, 128                   # one float32 vreg


def fits(shape_out: tuple[int, int], dtype) -> bool:
    """Whether the kernel takes an update whose output space is
    ``shape_out``: float32, filling at least one ``(8, 128)`` tile."""
    n, m = shape_out
    return jnp.dtype(dtype) == jnp.float32 and n >= SUB and m >= LANE


def tile_candidates(shape: tuple[int, ...]) -> tuple[tuple[int, int], ...]:
    """Feasible ``(bx, by)`` output tiles for an update of output space
    ``(n, m)`` (the autotuner's search axis): multiples of the (8, 128)
    vreg no larger than the interior rounded up to it.  The grid is a
    ``pl.cdiv`` one, so a tile need not divide the interior."""
    n, m = shape
    return tuple((bx, by)
                 for bx in (32, 64, 128) if bx <= pl.cdiv(n, SUB) * SUB
                 for by in (512, 1024, 2048) if by <= pl.cdiv(m, LANE) * LANE)


register_tile_kernel(TILE_KERNEL, tile_candidates)


def _update_kernel(dim: int, bx: int, by: int, u_ref, nbr_ref, coef_ref,
                   o_ref):
    axis = dim + 1                       # the update's axis in a chunk
    lam, half_over_lam, half_lam = coef_ref[0], coef_ref[1], coef_ref[2]
    steps = (bx // SUB, by // LANE)[dim]           # chunks along ``axis``
    across = (by // LANE, bx // SUB)[dim]          # chunks across it
    k = (SUB, LANE)[dim]
    at_end = jax.lax.broadcasted_iota(jnp.int32, (4, SUB, LANE), axis) \
        == k - 1

    def roll(x):
        """``x`` one cell back along the update: cell i holds cell i+1."""
        return pltpu.roll(x, k - 1, axis)

    def join(r_a, r_b):
        """The window one cell past chunk a, from the rolled chunks a and
        b = a's successor."""
        return jnp.where(at_end, r_b, r_a)

    def window(c, s):
        """(rows, lanes) of chunk ``s`` along the update, ``c`` across."""
        if dim == 0:
            c, s = s, c
        return (slice(None), pl.ds(pl.multiple_of(c * SUB, SUB), SUB),
                pl.ds(pl.multiple_of(s * LANE, LANE), LANE))

    def chunk(U):
        """A chunk's cells, and the same rolled."""
        return U, roll(U)

    def face(a, b):
        """The chunk one cell past ``a``'s cells and the FORCE fluxes of
        the faces between them (``b`` is the next chunk)."""
        V = join(a[1], b[1])
        F = euler.force_flux_of(a[0], V, euler.flux(a[0], dim),
                                euler.flux(V, dim), dim, half_over_lam,
                                half_lam)
        return V, F, roll(F)

    def row(c, _):
        def step(s, carry, new):
            # carry: chunk s+1, and chunk s's shifted cells and faces
            b, (V_a, F_a, RF_a) = carry
            V_b, F_b, RF_b = face(b, new)
            o_ref[window(c, s)] = V_a - lam * (join(RF_a, RF_b) - F_a)
            return new, (V_b, F_b, RF_b)

        a = chunk(u_ref[window(c, 0)])
        b = chunk(u_ref[window(c, 1)] if steps > 1 else nbr_ref[window(c, 0)])
        carry = (b, face(a, b))
        carry = jax.lax.fori_loop(
            0, max(steps - 2, 0),
            lambda s, carry: step(s, carry, chunk(u_ref[window(c, s + 2)])),
            carry, unroll=True)
        if steps > 1:
            carry = step(steps - 2, carry, chunk(nbr_ref[window(c, 0)]))
        # the last chunk needs only the first face past it, which its own
        # cells give
        step(steps - 1, carry, carry[0])
        return _

    jax.lax.fori_loop(0, across, row, 0)


def force_update_pallas(U_haloed: jax.Array, dim: int, lam, *, block=None,
                        interpret: bool | None = None) -> jax.Array:
    """``euler.update_dim(U_haloed, dim, lam)`` as one Pallas pass.

    ``U_haloed`` is float32 ``(4, n+2, m)`` for ``dim`` 0 or
    ``(4, n, m+2)`` for ``dim`` 1; returns ``(4, n, m)``.  ``block=None``
    resolves the ``(bx, by)`` output tile through the autotuner's ambient
    tile scope (``repro.tuning.tiles``); an explicit ``block`` wins, and
    outside any scope ``DEFAULT_BLOCK`` applies."""
    shape = list(U_haloed.shape[1:])
    shape[dim] -= 2
    n, m = shape
    bx, by = resolve_tile(TILE_KERNEL, block, DEFAULT_BLOCK, shape=shape)
    bx, by = min(bx, pl.cdiv(n, SUB) * SUB), min(by, pl.cdiv(m, LANE) * LANE)
    if dim == 0:
        nbr_block = (4, SUB, by)
        last = pl.cdiv(n + 2, SUB) - 1

        def nbr_index(i, j):
            return 0, jnp.minimum((i + 1) * (bx // SUB), last), j
    else:
        nbr_block = (4, bx, LANE)
        last = pl.cdiv(m + 2, LANE) - 1

        def nbr_index(i, j):
            return 0, i, jnp.minimum((j + 1) * (by // LANE), last)

    lam = jnp.asarray(lam, jnp.float32)
    coef = jnp.stack([lam, 0.5 / lam, 0.5 * lam])
    # double-buffered input and output tiles and neighbour blocks, and
    # room for the walk's spills
    tile_bytes = 4 * bx * by * 4
    return pl.pallas_call(
        partial(_update_kernel, dim, bx, by),
        out_shape=jax.ShapeDtypeStruct((4, n, m), U_haloed.dtype),
        grid=(pl.cdiv(n, bx), pl.cdiv(m, by)),
        in_specs=[
            pl.BlockSpec((4, bx, by), lambda i, j: (0, i, j)),
            pl.BlockSpec(nbr_block, nbr_index),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((4, bx, by), lambda i, j: (0, i, j)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=6 * tile_bytes + (8 << 20)),
        name=KERNEL_NAME,
        interpret=interpret_mode(interpret),
    )(U_haloed, U_haloed, coef)
