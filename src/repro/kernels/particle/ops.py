"""Jitted public wrapper for the particle update (layout polymorphic:
AoS / SoA / AoSoA, same kernel body; a layout outside SUPPORTED_LAYOUTS
would be staged through PREFERRED_LAYOUT, mirroring the stencil wrapper)."""

from functools import partial

import jax

from repro.core.layout import dispatch_with_relayout
from repro.tuning.tiles import resolve_tile
from .kernel import (DEFAULT_BLOCK, PARTICLE_SPEC, PREFERRED_LAYOUT,
                     SUPPORTED_LAYOUTS, TILE_KERNEL, particle_update_pallas)
from .ref import particle_update_ref


@partial(jax.jit, static_argnames=("block", "use_pallas"))
def _particle_update_jit(particles, dt, *, block: int, use_pallas: bool):
    if not use_pallas:
        return particle_update_ref(particles, dt)
    return dispatch_with_relayout(
        particle_update_pallas, particles, dt, supported=SUPPORTED_LAYOUTS,
        preferred=PREFERRED_LAYOUT, block=block)


def particle_update(particles, dt, *, block=None, use_pallas: bool = True):
    """``x += v * dt`` over a particle RecordArray (paper Table 3) — one
    kernel body for AoS / SoA / AoSoA.

    ``block=None`` resolves the particles-per-program tile through the
    autotuner's ambient tile scope (``repro.tuning.tiles``); an explicit
    ``block`` always wins, and outside any scope the kernel default
    applies."""
    block = resolve_tile(TILE_KERNEL, block, DEFAULT_BLOCK,
                         shape=particles.space)
    return _particle_update_jit(particles, dt, block=block,
                                use_pallas=use_pallas)
