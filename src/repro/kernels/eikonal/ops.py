"""Jitted public wrapper + graph builder for the eikonal FIM sweep.

``inner == 1`` (a pure radius-1 stencil, the benchmark's sweep) streams
the field through VMEM in full-width bands (``eikonal_fim_stream``),
whose height the interior's shape sets (``stream_block``); an output that
does not fill one ``(8, 128)`` float32 tile takes ``godunov_update``.
``inner > 1`` keeps the staged kernel (``eikonal_fim_pallas``): its
answer depends on its tile, which ``eikonal_fim_ref`` mirrors, so its
tile is part of the algorithm and not a speed knob."""

from functools import partial
from typing import Optional

import jax

from repro.core.graph import Graph, exclusive_padded_access
from repro.core.tensor import DistTensor
from repro.tuning.tiles import resolve_tile
from .kernel import (DEFAULT_BLOCK, STREAM_TILE_KERNEL, TILE_KERNEL,
                     eikonal_fim_pallas, eikonal_fim_stream, godunov_update,
                     stream_block, stream_fits)
from .ref import eikonal_fim_ref


@partial(jax.jit,
         static_argnames=("h", "inner", "block", "use_pallas"))
def _eikonal_fim_jit(phi_haloed, source_mask, h, *, inner: int, block,
                     use_pallas: bool):
    if not use_pallas:
        return eikonal_fim_ref(phi_haloed, source_mask, h, inner=inner,
                               block=block)
    if inner > 1:
        return eikonal_fim_pallas(phi_haloed, source_mask, h, inner=inner,
                                  block=block)
    if stream_fits(source_mask.shape, phi_haloed.dtype):
        return eikonal_fim_stream(phi_haloed, source_mask, h, block=block)
    return godunov_update(phi_haloed, source_mask, h)


def eikonal_fim_sweep(phi_haloed, source_mask, h, *, inner: int = 4,
                      block=None, use_pallas: bool = True):
    """``inner`` FIM Jacobi sweeps per tile over a haloed ``(nx+2, ny+2)``
    level-set array (paper Table 5); returns the updated ``(nx, ny)``
    interior.

    ``block=None`` resolves the ``(bx, by)`` tile through the
    autotuner's ambient tile scope (``repro.tuning.tiles``); an explicit
    ``block`` always wins.  Outside any scope ``inner == 1`` on the
    Pallas path takes the band ``stream_block`` sets from the interior's
    shape (tuned as ``eikonal_stream``), and ``inner > 1`` the staged
    kernel's ``DEFAULT_BLOCK`` (tuned as ``eikonal``)."""
    interior = tuple(s - 2 for s in phi_haloed.shape)
    if inner == 1 and use_pallas and stream_fits(interior, phi_haloed.dtype):
        block = resolve_tile(STREAM_TILE_KERNEL, block,
                             stream_block(interior), shape=interior)
    else:
        block = resolve_tile(TILE_KERNEL, block, DEFAULT_BLOCK,
                             shape=interior)
    return _eikonal_fim_jit(phi_haloed, source_mask, h, inner=inner,
                            block=block, use_pallas=use_pallas)


def make_eikonal_graph(
    phi: DistTensor,
    mask: DistTensor,
    h: float,
    *,
    inner: int = 1,
    overlap: bool = True,
    use_pallas: bool = False,
    block=None,
    graph: Optional[Graph] = None,
) -> Graph:
    """One outer FIM sweep as a Ripple graph node: ``phi`` (halo ``(1, 1)``,
    possibly 2-D partitioned) updated in place, ``source_mask`` riding as
    an unpadded output-aligned arg (the overlapped lowering slices it per
    boundary strip).  Run the graph repeatedly — or wrap it in
    ``conditional`` with a residual reduction — for the paper's
    convergence loop.

    ``inner > 1`` runs frozen-halo sweeps per tile, which makes the
    result depend on the tile decomposition (paper's FIM ghost-zone
    trade) — so only the default ``inner=1`` (a pure radius-1 stencil)
    is decomposition-invariant and value-identical between the
    overlapped and synchronous lowerings.  ``inner=1`` takes strips of
    any thickness: the reference path (``use_pallas=False``) lowers it
    without any tile grid, and the Pallas path streams a strip that fills
    an ``(8, 128)`` tile and updates a thinner one with the jnp formula.
    ``inner > 1`` needs a ``block`` that tiles every strip extent.

    ``graph=`` appends the sweep node to an existing builder (see
    ``make_flux_difference_graph``) so independent kernel nodes can share
    one DAG-scheduled jit segment.
    """
    def sweep(p_haloed, m):
        if inner == 1 and not use_pallas:
            return godunov_update(p_haloed, m, h)
        return eikonal_fim_sweep(p_haloed, m, h, inner=inner, block=block,
                                 use_pallas=use_pallas)

    g = graph if graph is not None else Graph(name="eikonal_sweep")
    g.split(sweep, exclusive_padded_access(phi), mask, writes=(0,),
            overlap=overlap)
    return g
