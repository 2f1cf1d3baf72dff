"""Jitted public wrappers for SAXPY (flat arrays + layout-polymorphic
record form; the record form is the paper's Table 2 layout axis)."""

from functools import partial

import jax

from repro.core.layout import dispatch_with_relayout
from repro.tuning.tiles import resolve_tile
from .kernel import (DEFAULT_BLOCK, PREFERRED_LAYOUT, SAXPY_SPEC,
                     SUPPORTED_LAYOUTS, TILE_KERNEL, saxpy_pallas,
                     saxpy_record_pallas)
from .ref import saxpy_record_ref, saxpy_ref


@partial(jax.jit, static_argnames=("block", "bounds_check", "use_pallas"))
def saxpy(a, x, y, *, block: int = 1024, bounds_check: bool = True,
          use_pallas: bool = True):
    """``a * x + y`` over flat arrays (paper Table 2's iterator-overhead
    probe; the record form below is the layout axis)."""
    if use_pallas:
        return saxpy_pallas(a, x, y, block=block, bounds_check=bounds_check)
    return saxpy_ref(a, x, y)


@partial(jax.jit, static_argnames=("block", "use_pallas"))
def _saxpy_record_jit(rec, a, *, block: int, use_pallas: bool):
    if not use_pallas:
        return saxpy_record_ref(rec, a)
    return dispatch_with_relayout(
        saxpy_record_pallas, rec, a, supported=SUPPORTED_LAYOUTS,
        preferred=PREFERRED_LAYOUT, block=block)


def saxpy_record(rec, a, *, block=None, use_pallas: bool = True):
    """``y = a*x + y`` on a RecordArray with fields ``x``/``y`` — same
    kernel body under AoS, SoA and AoSoA (paper's polymorphism claim).
    A layout outside SUPPORTED_LAYOUTS is staged through PREFERRED_LAYOUT
    (all three are native today, so this is the contract, not a copy).

    ``block=None`` resolves the VMEM tile through the autotuner's
    ambient tile scope (``repro.tuning.tiles``): an ``Executor`` with a
    tuned plan traces this call under its measured-best block; outside
    any scope the kernel default applies.  An explicit ``block`` always
    wins."""
    block = resolve_tile(TILE_KERNEL, block, DEFAULT_BLOCK, shape=rec.space)
    return _saxpy_record_jit(rec, a, block=block, use_pallas=use_pallas)
