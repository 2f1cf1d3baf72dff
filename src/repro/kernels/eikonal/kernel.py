"""Eikonal FIM Pallas kernels (paper §7.4, Table 5).

Solves ``|grad phi| = 1/f`` (f = 1: signed-distance reinit) with the Fast
Iterative Method: Jacobi sweeps of the Godunov update, repeated by the
outer loop (graph-level, with halo exchange + convergence reduction —
paper's conditional MapReduce) until converged.  Two kernels, chosen by
``inner``, the number of sweeps an HBM pass makes:

* ``inner == 1`` (a pure radius-1 stencil, whose answer no tile
  changes): ``eikonal_fim_stream`` streams the haloed field through VMEM
  in full-width row bands, BlockSpec-pipelined so the next band loads
  while this one computes, and forms the neighbour shifts in registers.
  The band height comes from the shape (``stream_block``: the tallest
  band whose double-buffered blocks fit ``STREAM_VMEM``); column panels
  only where not even 8 full-width rows fit.
* ``inner > 1`` (the paper's winning configuration, which stages a tile
  in shared memory and sweeps it several times): ``eikonal_fim_pallas``
  DMAs a halo-inclusive tile into VMEM and runs ``inner`` sweeps on it
  with frozen halos (the FIM ghost-zone trade).  The answer depends on
  the tile, which ``ref.eikonal_fim_ref`` mirrors, so the tile is part
  of the algorithm and keeps its ``(8, 128)`` default.

Both share ``godunov``'s arithmetic, float32 in the stream.  The Godunov
upwind update in 2-D (f=1, grid step h):

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
STREAM_TILE_KERNEL = "eikonal_stream"   # the streaming sweep's registry name
SUB, LANE = 8, 128                      # one float32 vreg
# The source mask in the stream's blocks: Mosaic blocks hold no booleans,
# and a boolean mask converted to int8 keeps its size, so XLA hoists the
# convert out of a solve loop, which it does not do for int32.
MASK_DTYPE = jnp.int8
STREAM_VMEM = 16 << 20    # bytes of double-buffered blocks a stream step holds
MAX_BAND = 512            # rows of a band: its walk is unrolled
PANEL_ROWS = 256          # rows of a column panel, where no band fits


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


def stream_fits(shape: tuple[int, int], dtype) -> bool:
    """Whether the streaming sweep takes an interior of ``shape``:
    float32, filling at least one ``(8, 128)`` tile."""
    nx, ny = shape
    return jnp.dtype(dtype) == jnp.float32 and nx >= SUB and ny >= LANE


def _stream_bytes(bx: int, by: int) -> int:
    """VMEM of one stream step's blocks, double-buffered: the ``(bx, by)``
    input block and its neighbours below (8 rows), right (128 lanes) and
    at the corner, the mask and the output."""
    mask = jnp.dtype(MASK_DTYPE).itemsize
    return 2 * ((bx + SUB) * (by + LANE) * 4 + bx * by * (mask + 4))


def stream_vmem_limit(bx: int, by: int) -> int:
    """The streaming sweep's ``vmem_limit_bytes`` for a ``(bx, by)``
    block: its double-buffered blocks, and room for the walk's spills."""
    return _stream_bytes(bx, by) + (8 << 20)


def stream_block(shape: tuple[int, int]) -> tuple[int, int]:
    """The streaming sweep's ``(bx, by)`` for an interior of ``shape``.

    A band spans the full width (``by`` is ``ny`` rounded up to 128
    lanes) and is the tallest multiple of 8 rows, up to ``MAX_BAND`` and
    the interior, whose blocks fit ``STREAM_VMEM``.  Where not even 8
    rows of the full width fit, the field goes in column panels of
    ``PANEL_ROWS`` rows and the widest multiple of 128 lanes that fits."""
    nx, ny = shape
    top = min(MAX_BAND, pl.cdiv(nx, SUB) * SUB)
    by = pl.cdiv(ny, LANE) * LANE
    bx = next((bx for bx in range(top, 0, -SUB)
               if _stream_bytes(bx, by) <= STREAM_VMEM), 0)
    if bx:
        return bx, by
    bx = min(PANEL_ROWS, top)
    by = next(by for by in range(by - LANE, 0, -LANE)
              if _stream_bytes(bx, by) <= STREAM_VMEM)
    return bx, by


def stream_candidates(shape: tuple[int, ...]) -> tuple[tuple[int, int], ...]:
    """Feasible ``(bx, by)`` bands of the streaming sweep for an interior
    of ``(nx, ny)`` (the autotuner's search axis): the shape rule's
    choice and shorter full-width bands of 32 to 256 rows that fit the
    same budget."""
    nx, ny = shape
    bx, by = stream_block((nx, ny))
    return ((bx, by),) + tuple(
        (b, by) for b in (32, 64, 128, 256)
        if b < bx and _stream_bytes(b, by) <= STREAM_VMEM)


register_tile_kernel(STREAM_TILE_KERNEL, stream_candidates)


def godunov(w, e, s, n, c, mask, h: float):
    """The Godunov update of centre ``c`` from its four neighbours (W/E
    along rows, S/N along lanes); sources (``mask`` True) keep ``c``."""
    a = jnp.minimum(w, e)
    b = jnp.minimum(s, n)
    lo = jnp.minimum(a, b)
    diff = jnp.abs(a - b)
    two = jnp.asarray(2.0, c.dtype)
    quad = 0.5 * (a + b + jnp.sqrt(jnp.maximum(two * h * h - diff * diff, 0.0)))
    new = jnp.where(diff >= h, lo + h, quad)
    new = jnp.minimum(c, new)
    return jnp.where(mask, c, new)


def godunov_update(phi: jax.Array, mask: jax.Array, h: float) -> jax.Array:
    """One Jacobi sweep on a haloed tile; interior cells updated only.

    ``phi``: (m+2, n+2), or a kernel ref whose leading (m+2, n+2) corner
    is the tile; ``mask``: (m, n) True where source (pinned).  Returns the
    updated *interior* (m, n).
    """
    m, n_ = mask.shape
    return godunov(phi[0:m, 1:n_ + 1], phi[2:m + 2, 1:n_ + 1],
                   phi[1:m + 1, 0:n_], phi[1:m + 1, 2:n_ + 2],
                   phi[1:m + 1, 1:n_ + 1], mask, h)


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


def _stream_kernel(bx: int, lanes: int, panels: bool, h: float, *refs):
    """One band (or panel) of a streaming sweep, walked in ``(8, 128)``
    chunks: down each column of chunks, the chunk below carried to the
    next step.  A chunk's lane shifts by 1 and 2 come from it and its
    right-hand successor, its row shifts from it and the chunk below,
    each a rotate of both and a select."""
    if panels:
        band, below, right, corner, mask_ref, o_ref = refs
    else:               # the band holds its right-hand halo chunk
        band, below, mask_ref, o_ref = refs
        right, corner = band, below
    row = jax.lax.broadcasted_iota(jnp.int32, (SUB, LANE), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (SUB, LANE), 1)

    def roll(x, k, axis):
        """``x`` rotated k cells back along ``axis``: cell i holds i+k."""
        return pltpu.roll(x, (SUB, LANE)[axis] - k, axis)

    def join(rx, ry, k, axis):
        """The window k cells past a chunk along ``axis``, from the chunk
        and its successor there, each rolled k cells back."""
        return jnp.where((row, lane)[axis] >= (SUB, LANE)[axis] - k, ry, rx)

    def at(r, c):
        """Chunk ``(r, c)`` of a ref."""
        return (pl.ds(pl.multiple_of(r * SUB, SUB), SUB),
                pl.ds(pl.multiple_of(c * LANE, LANE), LANE))

    def chunk(x, y):
        """What the walk keeps of input chunk ``x`` (``y`` is the chunk
        right of it): its cells one lane on, which are the W neighbours of
        the output chunk level with it, and the row shifts that the output
        chunk above it takes from it."""
        x1 = join(roll(x, 1, 1), roll(y, 1, 1), 1, 1)
        x2 = join(roll(x, 2, 1), roll(y, 2, 1), 2, 1)
        return (x1, roll(x, 1, 0), roll(x1, 1, 0), roll(x1, 2, 0),
                roll(x2, 1, 0))

    def emit(r, c, a, b):
        """Output chunk ``(r, c)`` from input chunk ``a`` level with it
        and ``b`` below ``a``: each neighbour is the input one row on and
        one lane on, shifted by one cell."""
        w = a[0]                                # rows +0, lanes +1
        s = join(a[1], b[1], 1, 0)              # rows +1, lanes +0
        cc = join(a[2], b[2], 1, 0)             # rows +1, lanes +1
        e = join(a[3], b[3], 2, 0)              # rows +2, lanes +1
        n = join(a[4], b[4], 1, 0)              # rows +1, lanes +2
        o_ref[at(r, c)] = godunov(w, e, s, n, cc,
                                  mask_ref[at(r, c)] != 0, h)

    def column(c, right_refs, cr):
        """Chunk column ``c``; its right-hand successor is column ``cr``
        of ``right_refs`` (the band's and the row below's)."""
        def load(refs, r):
            return chunk(refs[0][at(r, c)], refs[1][at(r, cr)])

        def step(r, a):
            b = load((band, right_refs[0]), r + 1)
            emit(r, c, a, b)
            return b

        # Unrolled where it is lowered, traced once.
        a = jax.lax.fori_loop(0, bx // SUB - 1, step,
                              load((band, right_refs[0]), 0), unroll=True)
        emit(bx // SUB - 1, c, a, load((below, right_refs[1]), 0))

    def body(c, carry):
        column(c, (band, below), c + 1)
        return carry

    jax.lax.fori_loop(0, lanes - 1 if panels else lanes, body, 0)
    if panels:
        column(lanes - 1, (right, corner), 0)


def eikonal_fim_stream(
    phi_haloed: jax.Array,
    source_mask: jax.Array,
    h: float,
    *,
    block: tuple[int, int] | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """One FIM Jacobi sweep streamed through VMEM: ``godunov_update`` of
    the float32 ``(nx+2, ny+2)`` ``phi_haloed``, bit for bit, as one
    Pallas call; ``source_mask`` is ``(nx, ny)``.

    Each grid program writes a ``(bx, by)`` output block from the
    matching input block and its neighbours: the next 8 rows (the
    block's two trailing halo rows) and, for column panels, the next 128
    lanes and the 8 x 128 corner.  A neighbour's block index is clamped
    to the input's last block: where the clamp bites, the rows it would
    have fed lie past the output and are dropped on write-back, so a
    ``pl.cdiv`` grid takes ragged interiors.  ``by`` at least ``ny`` is
    a full-width band, whose input block holds its right-hand halo
    (``ny`` rounded up to 128 lanes, plus 128) and needs no lane or
    corner neighbour.  ``block=None`` takes ``stream_block``."""
    nx, ny = (s - 2 for s in phi_haloed.shape)
    bx, by = block or stream_block((nx, ny))
    bx = min(pl.cdiv(bx, SUB), pl.cdiv(nx, SUB)) * SUB
    by = min(pl.cdiv(by, LANE), pl.cdiv(ny, LANE)) * LANE
    panels = by < ny
    last_row = pl.cdiv(nx + 2, SUB) - 1

    def below(i):
        return jnp.minimum((i + 1) * (bx // SUB), last_row)

    if panels:
        last_lane = pl.cdiv(ny + 2, LANE) - 1

        def right(j):
            return jnp.minimum((j + 1) * (by // LANE), last_lane)

        grid = (pl.cdiv(nx, bx), pl.cdiv(ny, by))
        phi_specs = [
            pl.BlockSpec((bx, by), lambda i, j: (i, j)),
            pl.BlockSpec((SUB, by), lambda i, j: (below(i), j)),
            pl.BlockSpec((bx, LANE), lambda i, j: (i, right(j))),
            pl.BlockSpec((SUB, LANE), lambda i, j: (below(i), right(j))),
        ]
        out_spec = pl.BlockSpec((bx, by), lambda i, j: (i, j))
    else:
        grid = (pl.cdiv(nx, bx),)
        phi_specs = [
            pl.BlockSpec((bx, by + LANE), lambda i: (i, 0)),
            pl.BlockSpec((SUB, by + LANE), lambda i: (below(i), 0)),
        ]
        out_spec = pl.BlockSpec((bx, by), lambda i: (i, 0))
    return pl.pallas_call(
        partial(_stream_kernel, bx, by // LANE, panels, h),
        out_shape=jax.ShapeDtypeStruct((nx, ny), phi_haloed.dtype),
        grid=grid,
        in_specs=phi_specs + [out_spec],
        out_specs=out_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * len(grid),
            vmem_limit_bytes=stream_vmem_limit(bx, by)),
        interpret=interpret_mode(interpret),
    )(*[phi_haloed] * len(phi_specs), source_mask.astype(MASK_DTYPE))
