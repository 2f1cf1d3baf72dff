"""SAXPY Pallas kernel (paper §7.1, Table 2).

The paper uses SAXPY to measure the overhead of its iterator abstraction
(bounds checking) vs raw CUDA/cuBLAS.  The TPU analogue of the paper's
"NBC" (no-boundary-check) variant is a grid that exactly tiles the array
(no masking); the checked variant masks the tail block with
``pl.program_id``-derived indices — the same cost model: one extra
predicated lane op per element.

Block size is the VMEM tiling knob (paper's single-line memory-space
config): blocks must be multiples of 128 lanes for full VREG occupancy.
"""

from __future__ import annotations

from functools import partial as _partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.layout import (Layout, RecordArray, RecordRef, RecordSpec,
                               record_grid_1d)
from repro.kernels import interpret_mode
from repro.tuning.tiles import register_tile_kernel

# record form: x and y live in ONE record buffer (paper §4.2's layout axis
# for Table 2); metadata consumed by the ops.py wrapper, which relayouts
# inputs whose layout is not natively supported
SAXPY_SPEC = RecordSpec.create("x", "y")
SUPPORTED_LAYOUTS = (Layout.AOS, Layout.SOA, Layout.AOSOA)
PREFERRED_LAYOUT = Layout.SOA
TILE_KERNEL = "saxpy"     # name in the autotuner's tile registry
DEFAULT_BLOCK = 1024


def tile_candidates(shape: tuple[int, ...]) -> tuple[int, ...]:
    """Feasible VMEM block sizes for a 1-d record space of extent ``n``
    (the autotuner's search axis for this kernel): lane-width multiples
    that tile ``n`` exactly, the kernel's default included when it
    fits."""
    (n,) = shape
    return tuple(b for b in (256, 512, 1024, 2048, 4096, 8192)
                 if b <= n and n % b == 0)


register_tile_kernel(TILE_KERNEL, tile_candidates)


def _saxpy_kernel(a_ref, x_ref, y_ref, o_ref):
    o_ref[...] = a_ref[0] * x_ref[...] + y_ref[...]


def _saxpy_kernel_masked(size, block, a_ref, x_ref, y_ref, o_ref):
    i = pl.program_id(0)
    idx = i * block + jax.lax.iota(jnp.int32, block)
    valid = idx < size  # paper's iterator validity check
    v = a_ref[0] * x_ref[...] + y_ref[...]
    o_ref[...] = jnp.where(valid, v, y_ref[...])


def saxpy_pallas(
    a: jax.Array,
    x: jax.Array,
    y: jax.Array,
    *,
    block: int = 1024,
    bounds_check: bool = True,
    interpret: bool | None = None,
) -> jax.Array:
    """y_out = a * x + y over a 1-d array, VMEM-tiled in ``block`` chunks."""
    size = x.shape[0]
    if size % block:
        # pad to the grid; masked variant keeps tail exact
        pad = block - size % block
        x = jnp.pad(x, (0, pad))
        y = jnp.pad(y, (0, pad))
    grid = (x.shape[0] // block,)
    a_arr = jnp.asarray(a, dtype=x.dtype).reshape(1)

    if bounds_check:
        from functools import partial

        kern = partial(_saxpy_kernel_masked, size, block)
    else:
        kern = _saxpy_kernel

    out = pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda i: (0,)),
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((block,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        interpret=interpret_mode(interpret),
    )(a_arr, x, y)
    return out[:size]


def _saxpy_record_kernel(spec: RecordSpec, layout: Layout, a_ref, p_ref,
                         o_ref):
    p = RecordRef(p_ref, spec, layout)
    o = RecordRef(o_ref, spec, layout)
    x = p.get("x")
    a = a_ref[0].astype(x.dtype)
    o.set("x", x)
    o.set("y", a * x + p.get("y"))


def saxpy_record_pallas(
    rec: RecordArray,
    a,
    *,
    block: int = 1024,
    interpret: bool | None = None,
) -> RecordArray:
    """``y = a*x + y`` over a two-field record in any of the three layouts
    — the kernel body is a single :class:`RecordRef` program."""
    (n,) = rec.space
    spec, layout = rec.spec, rec.layout
    assert n % block == 0, f"n={n} must tile by block={block}"
    grid, bspec = record_grid_1d(spec, layout, n, block)

    # the scalar rides in SMEM (32-bit words), the record tiles in VMEM
    a_arr = jnp.asarray(a, dtype=jnp.float32).reshape(1)
    out = pl.pallas_call(
        _partial(_saxpy_record_kernel, spec, layout),
        out_shape=jax.ShapeDtypeStruct(rec.data.shape, rec.dtype),
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), bspec],
        out_specs=bspec,
        interpret=interpret_mode(interpret),
    )(a_arr, rec.data)
    return RecordArray(out, spec, layout)
