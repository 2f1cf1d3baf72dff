"""Pallas kernels for the paper's hot spots (SAXPY, particles, FORCE flux,
eikonal FIM) and the LM twins (flash attention, SSD).

Every kernel runs compiled by Mosaic on a TPU and in the Pallas
interpreter elsewhere; :func:`interpret_mode` is the one place that
decision is made."""

from __future__ import annotations

from typing import Optional

import jax


def interpret_mode(interpret: Optional[bool] = None) -> bool:
    """The ``interpret`` flag for ``pl.pallas_call``, from the platform.

    ``None`` (every wrapper's default) interprets on CPU and compiles on
    TPU.  ``False`` forces a Mosaic lowering whatever the backend (how a
    TPU compile is rehearsed for a described, not attached, chip).  An
    explicit ``True`` on a TPU is an error: on the chip a kernel is
    compiled or it does not run."""
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        return not on_tpu
    if interpret and on_tpu:
        raise ValueError("interpret=True on a TPU backend: Pallas kernels "
                         "run compiled on the chip")
    return bool(interpret)
