"""Jitted public wrapper + graph builder for the eikonal FIM sweep."""

from functools import partial
from typing import Optional

import jax

from repro.core.graph import Graph, exclusive_padded_access
from repro.core.tensor import DistTensor
from repro.tuning.tiles import resolve_tile
from .kernel import DEFAULT_BLOCK, TILE_KERNEL, eikonal_fim_pallas
from .ref import eikonal_fim_ref


@partial(jax.jit,
         static_argnames=("h", "inner", "block", "use_pallas"))
def _eikonal_fim_jit(phi_haloed, source_mask, h, *, inner: int, block,
                     use_pallas: bool):
    if use_pallas:
        return eikonal_fim_pallas(phi_haloed, source_mask, h, inner=inner,
                                  block=block)
    return eikonal_fim_ref(phi_haloed, source_mask, h, inner=inner, block=block)


def eikonal_fim_sweep(phi_haloed, source_mask, h, *, inner: int = 4,
                      block=None, use_pallas: bool = True):
    """``inner`` VMEM-staged FIM Jacobi sweeps per tile over a haloed
    ``(nx+2, ny+2)`` level-set array (paper Table 5); returns the
    updated ``(nx, ny)`` interior.

    ``block=None`` resolves the ``(bx, by)`` tile through the
    autotuner's ambient tile scope (``repro.tuning.tiles``); an explicit
    ``block`` always wins, and outside any scope the kernel default
    applies."""
    interior = tuple(s - 2 for s in phi_haloed.shape)
    block = resolve_tile(TILE_KERNEL, block, DEFAULT_BLOCK, shape=interior)
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
    overlapped and synchronous lowerings.  The reference path
    (``use_pallas=False``) lowers ``inner=1`` without any tile grid, so
    boundary strips of any thickness work; the Pallas path, and
    ``inner > 1``, need a ``block`` that tiles every strip extent.

    ``graph=`` appends the sweep node to an existing builder (see
    ``make_flux_difference_graph``) so independent kernel nodes can share
    one DAG-scheduled jit segment.
    """
    from .kernel import godunov_update

    def sweep(p_haloed, m):
        if inner == 1 and not use_pallas:
            return godunov_update(p_haloed, m, h)
        return eikonal_fim_sweep(p_haloed, m, h, inner=inner, block=block,
                                 use_pallas=use_pallas)

    g = graph if graph is not None else Graph(name="eikonal_sweep")
    g.split(sweep, exclusive_padded_access(phi), mask, writes=(0,),
            overlap=overlap)
    return g
