"""The device-side copy a host node reads under donation (the double
buffer of ``core/executor.py``), as the named DMA kernel ``host_snapshot``.

The kernel moves the array HBM to HBM with a few DMAs in flight at once,
one per piece of the leading axis; nothing passes through VMEM.  Its
``name`` is what the device trace shows (``%host_snapshot... =
custom-call``), so the copy's time can be read there.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret_mode

KERNEL_NAME = "host_snapshot"
MAX_PIECES = 4      # DMAs in flight at once


def _pieces(shape: tuple[int, ...]) -> int:
    """How many DMAs split the leading axis: the most, up to
    ``MAX_PIECES``, that divide it evenly (in whole (8, 128) tile rows
    where the leading axis is the tiled second-minor one)."""
    lead = shape[0]
    unit = 8 if len(shape) == 2 else 1
    for n in range(MAX_PIECES, 1, -1):
        if lead % (n * unit) == 0:
            return n
    return 1


def _copy_kernel(n: int, x_ref, o_ref, sems):
    size = x_ref.shape[0] // n
    copies = [pltpu.make_async_copy(x_ref.at[pl.ds(i * size, size)],
                                    o_ref.at[pl.ds(i * size, size)],
                                    sems.at[i])
              for i in range(n)]
    for c in copies:
        c.start()
    for c in copies:
        c.wait()


def host_snapshot_pallas(x: jax.Array, *,
                         interpret: bool | None = None) -> jax.Array:
    """A copy of ``x`` (rank 2 or more) in a new buffer, by DMA."""
    if x.ndim < 2:
        raise ValueError(f"host_snapshot copies arrays of rank >= 2, got "
                         f"shape {x.shape}")
    n = _pieces(x.shape)
    return pl.pallas_call(
        partial(_copy_kernel, n),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA((n,))],
        name=KERNEL_NAME,
        interpret=interpret_mode(interpret),
    )(x)


def snapshot_copy(x: jax.Array, kernel: bool) -> jax.Array:
    """``x`` copied into a new buffer: by the compiled ``host_snapshot``
    kernel where ``kernel`` (the caller's choice: a TPU, one device, rank
    >= 2), else by ``jnp.copy``."""
    return host_snapshot_pallas(x, interpret=False) if kernel \
        else jnp.copy(x)
