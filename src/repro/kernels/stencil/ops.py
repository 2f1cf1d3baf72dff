"""Jitted public wrapper + graph builder for the FORCE flux-difference
stencil.

Layout dispatch: the Pallas kernel DMAs halo-inclusive SoA tiles.  An
AoS or AoSoA input is relayouted to SoA on the way in and back on the
way out — the same boundary conversion the executor's layout solver
emits, so results are numerically identical under all three layouts.
"""

from functools import partial
from typing import Optional

import jax

from repro.core.graph import Graph, concurrent_padded_access
from repro.core.layout import dispatch_with_relayout
from repro.core.tensor import DistTensor
from repro.tuning.tiles import resolve_tile
from .kernel import (DEFAULT_BLOCK, PREFERRED_LAYOUT, SUPPORTED_LAYOUTS,
                     TILE_KERNEL, flux_difference_pallas)
from .ref import flux_difference_ref


@partial(jax.jit, static_argnames=("block", "use_pallas"))
def _flux_difference_jit(state_haloed, lam_x, lam_y, *, block,
                         use_pallas: bool):
    if not use_pallas:
        return flux_difference_ref(state_haloed, lam_x, lam_y)
    return dispatch_with_relayout(
        flux_difference_pallas, state_haloed, lam_x, lam_y,
        supported=SUPPORTED_LAYOUTS, preferred=PREFERRED_LAYOUT,
        block=block)


def flux_difference(state_haloed, lam_x, lam_y, *, block=None,
                    use_pallas: bool = True):
    """Sum of FORCE flux differences over both dims of a haloed 2-D
    Euler record (paper Table 4): ``(nx+2, ny+2)`` space in, ``(nx, ny)``
    out, layout polymorphic (AoSoA staged through the kernel's preferred
    per-axis layout).

    ``block=None`` resolves the ``(bx, by)`` VMEM tile through the
    autotuner's ambient tile scope (``repro.tuning.tiles``); an explicit
    ``block`` always wins, and outside any scope the kernel default
    applies."""
    interior = tuple(s - 2 for s in state_haloed.space)
    block = resolve_tile(TILE_KERNEL, block, DEFAULT_BLOCK, shape=interior)
    return _flux_difference_jit(state_haloed, lam_x, lam_y, block=block,
                                use_pallas=use_pallas)


def make_flux_difference_graph(
    u: DistTensor,
    out: DistTensor,
    lam_x,
    lam_y,
    *,
    overlap: bool = True,
    use_pallas: bool = False,
    block=None,
    graph: Optional[Graph] = None,
) -> Graph:
    """One-node Ripple graph: FORCE flux difference over a (possibly
    2-D-partitioned) Euler record ``u`` with halo ``(1, 1)`` into ``out``.

    With ``overlap=True`` the executor's transfer schedule sends every
    halo block (edge strips + corners) up front and hides the flights
    behind the interior program; the per-(axis, side) boundary strips are
    stitched afterwards.  The Pallas path asserts block-divisible extents
    (boundary strips are 1 cell thin), so the default here is the
    shape-polymorphic reference path — flip ``use_pallas`` where the
    interior extents divide ``block``.

    ``graph=`` appends the node to an existing builder instead of
    creating a fresh one: compose several kernel nodes into one graph and
    the dependency-DAG scheduler fuses the independent ones into a shared
    jit segment (``core/schedule.py``).
    """

    def flux_node(rec, _out):
        return flux_difference(rec, lam_x, lam_y, block=block,
                               use_pallas=use_pallas)

    g = graph if graph is not None else Graph(name="flux_difference")
    g.split(flux_node, concurrent_padded_access(u), out, overlap=overlap)
    return g
