"""Jitted public wrapper for SSD."""

from functools import partial

import jax

from repro.tuning.tiles import resolve_tile
from .kernel import DEFAULT_CHUNK, TILE_KERNEL, ssd_pallas
from .ref import ssd_chunked, ssd_decode_step, ssd_naive


@partial(jax.jit, static_argnames=("chunk", "use_pallas"))
def _ssd_jit(x, dt, A, Bm, C, D=None, init_state=None, *, chunk: int,
             use_pallas: bool):
    if use_pallas:
        return ssd_pallas(x, dt, A, Bm, C, D, init_state, chunk=chunk)
    return ssd_chunked(x, dt, A, Bm, C, D, init_state, chunk=chunk)


def ssd(x, dt, A, Bm, C, D=None, init_state=None, *, chunk=None,
        use_pallas: bool = True):
    """Mamba-2 SSD: Pallas intra-chunk quadratic part + XLA inter-chunk
    scan; returns ``(y, final_state)``.

    ``chunk=None`` resolves the chunk length through the autotuner's
    ambient tile scope (kernel ``"ssd"``); an explicit ``chunk`` always
    wins, and outside any scope the kernel default applies."""
    chunk = resolve_tile(TILE_KERNEL, chunk, DEFAULT_CHUNK,
                         shape=(x.shape[1],))
    return _ssd_jit(x, dt, A, Bm, C, D, init_state, chunk=chunk,
                    use_pallas=use_pallas)
