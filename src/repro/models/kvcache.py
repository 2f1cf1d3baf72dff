"""KV cache with polymorphic layout — the paper's C1 applied to serving.

The cache is a RecordArray with fields (k, v) of size head_dim over the
space (batch, seq, kv_heads):

* AoS  -> one array (B, S, Hkv, 2*hd): k/v interleaved per (position, head);
          reading k is a minor-dim slice (zero transpose), appending one
          token writes one contiguous slab.
* SoA  -> one array (2*hd, B, S, Hkv): each of the 2*hd component planes is
          contiguous over (B, S, Hkv); reads transpose the component axis
          to the minor position.
* AoSoA -> the last space dim is tiled by ``aosoa_tile``:
          "bsh" tiles Hkv (sequence stays a plain storage axis, so token
          writes are ordinary dynamic slices); "bhs" tiles the sequence
          itself, and a token write addresses (pos // tile, pos % tile)
          across the two storage axes — the dynamic-slice write path that
          used to be rejected with a ValueError.

On GPU the paper finds SoA wins for vector-field kernels (coalescing).
For TPU *decode reads* the AoS record keeps head_dim minor-most (exactly
one 128-lane tile for hd=128) while SoA leaves the small Hkv axis minor —
so the winner flips with the workload, which is precisely the paper's
argument for making layout a one-line polymorphic knob rather than a
rewrite.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core.layout import (Layout, RecordArray, RecordSpec, Vector,
                               relayout_data)

__all__ = ["KVLayout", "kv_spec", "kv_make", "kv_read", "kv_write_prefill",
           "kv_write_token", "kv_pspec"]

KVLayout = Layout  # re-export under the serving name


def kv_spec(head_dim: int) -> RecordSpec:
    return RecordSpec.create(Vector("k", head_dim), Vector("v", head_dim))


def _space(batch: int, seq: int, kv_heads: int, order: str):
    return (batch, seq, kv_heads) if order == "bsh" else (batch, kv_heads, seq)


def kv_make(batch: int, seq: int, kv_heads: int, head_dim: int,
            dtype=jnp.bfloat16, layout: Layout = Layout.AOS,
            order: str = "bsh") -> jax.Array:
    """Cache storage.  ``order`` is the SPACE axis order — the second
    polymorphic-layout knob (paper C1): "bsh" keeps sequence adjacent to
    batch; "bhs" puts sequence minor-most-but-one so the decode score dot
    consumes k as (B, H, S, hd) with NO per-step transpose (measured:
    -47%% decode HBM traffic on qwen3 decode_32k; EXPERIMENTS §Perf)."""
    shape = RecordArray.storage_shape(kv_spec(head_dim),
                                      _space(batch, seq, kv_heads, order),
                                      layout)
    return jnp.zeros(shape, dtype)


def kv_read(storage: jax.Array, head_dim: int,
            layout: Layout = Layout.AOS,
            order: str = "bsh") -> tuple[jax.Array, jax.Array]:
    """-> (k, v) each (B, S, Hkv, hd) for "bsh" / (B, Hkv, S, hd) for
    "bhs" (native, no transpose)."""
    rec = RecordArray(storage, kv_spec(head_dim), layout)
    return rec.field("k"), rec.field("v")


def kv_write_prefill(storage: jax.Array, k: jax.Array, v: jax.Array,
                     layout: Layout = Layout.AOS,
                     order: str = "bsh") -> jax.Array:
    """Write the first S_in positions of the cache from prefill k/v
    (B, S_in, Hkv, hd) — one transpose at prefill for "bhs".  For AoSoA
    the bulk write stages through the AoS view (a pure transpose, traced
    into the prefill executable) because the update region need not be
    tile-aligned."""
    hd = k.shape[-1]
    kv = jnp.concatenate([k, v], axis=-1).astype(storage.dtype)
    if order == "bhs":
        kv = jnp.swapaxes(kv, 1, 2)             # (B, Hkv, S_in, 2hd)
    if layout is Layout.AOSOA:
        spec = kv_spec(hd)
        aos = relayout_data(storage, spec, Layout.AOSOA, Layout.AOS)
        aos = lax.dynamic_update_slice(aos, kv, (0, 0, 0, 0))
        return relayout_data(aos, spec, Layout.AOS, Layout.AOSOA)
    if layout is Layout.AOS:
        return lax.dynamic_update_slice(storage, kv, (0, 0, 0, 0))
    return lax.dynamic_update_slice(
        storage, jnp.moveaxis(kv, -1, 0), (0, 0, 0, 0))


def _aosoa_tilefold(kv: jax.Array, tile: int) -> jax.Array:
    """(B, Hkv, C) token slab -> (B, Hkv//tile, C, tile) AoSoA slab."""
    B, H, C = kv.shape
    return kv.reshape(B, H // tile, tile, C).swapaxes(-1, -2)


def kv_write_token(storage: jax.Array, k_t: jax.Array, v_t: jax.Array,
                   pos: jax.Array, layout: Layout = Layout.AOS,
                   order: str = "bsh") -> jax.Array:
    """Write one token's k/v (B, Hkv, hd) at sequence slot ``pos``.

    ``pos`` is either a scalar (whole batch at one position — training-eval
    and uniform decode) or a vector (B,) of per-slot positions (continuous
    batching: every batch slot sits at its own depth).  Scalar writes lower
    to ``dynamic_update_slice``; vector writes to an XLA scatter."""
    kv = jnp.concatenate([k_t, v_t], axis=-1).astype(storage.dtype)
    pos = jnp.asarray(pos, jnp.int32)
    B, H, C = kv.shape
    if pos.ndim == 0:
        if order == "bsh":
            if layout is Layout.AOS:
                upd = kv[:, None]                     # (B, 1, Hkv, 2hd)
                return lax.dynamic_update_slice(storage, upd, (0, pos, 0, 0))
            if layout is Layout.SOA:
                upd = jnp.moveaxis(kv, -1, 0)[:, :, None]  # (2hd, B, 1, Hkv)
                return lax.dynamic_update_slice(storage, upd, (0, 0, pos, 0))
            upd = _aosoa_tilefold(kv, storage.shape[-1])[:, None]
            return lax.dynamic_update_slice(storage, upd, (0, pos, 0, 0, 0))
        if layout is Layout.AOS:
            upd = kv[:, :, None]                      # (B, Hkv, 1, 2hd)
            return lax.dynamic_update_slice(storage, upd, (0, 0, pos, 0))
        if layout is Layout.SOA:
            upd = jnp.moveaxis(kv, -1, 0)[:, :, :, None]  # (2hd, B, Hkv, 1)
            return lax.dynamic_update_slice(storage, upd, (0, 0, 0, pos))
        # AoSoA "bhs": sequence is the tiled dim -> address the slot as
        # (pos // tile, pos % tile) across the two storage axes.
        tile = storage.shape[-1]
        upd = kv[:, :, None, :, None]                 # (B, Hkv, 1, 2hd, 1)
        return lax.dynamic_update_slice(
            storage, upd, (0, 0, pos // tile, 0, pos % tile))

    # vector pos: one scatter per field-free storage form
    b = jnp.arange(B, dtype=jnp.int32)
    h = jnp.arange(H, dtype=jnp.int32)
    if order == "bsh":
        if layout is Layout.AOS:                      # (B, S, Hkv, 2hd)
            return storage.at[b, pos].set(kv)
        if layout is Layout.SOA:                      # (2hd, B, S, Hkv)
            return storage.at[:, b, pos].set(jnp.moveaxis(kv, -1, 0))
        upd = _aosoa_tilefold(kv, storage.shape[-1])  # (B, n, 2hd, t)
        return storage.at[b, pos].set(upd)            # (B, S, n, 2hd, t)
    if layout is Layout.AOS:                          # (B, Hkv, S, 2hd)
        return storage.at[b[:, None], h[None, :], pos[:, None]].set(kv)
    if layout is Layout.SOA:                          # (2hd, B, Hkv, S)
        return storage.at[:, b[:, None], h[None, :],
                          pos[:, None]].set(jnp.moveaxis(kv, -1, 0))
    tile = storage.shape[-1]                          # (B, Hkv, S//t, 2hd, t)
    return storage.at[b[:, None], h[None, :], (pos // tile)[:, None], :,
                      (pos % tile)[:, None]].set(kv)


def kv_pspec(layout: Layout, *, batch_axes, seq_axes,
             order: str = "bsh") -> P:
    """PartitionSpec for the cache storage given the serving sharding
    scheme (batch over DP axes, sequence flash-decode-sharded)."""
    ba = tuple(batch_axes) if batch_axes else None
    sa = tuple(seq_axes) if seq_axes else None
    space = (ba, sa, None) if order == "bsh" else (ba, None, sa)
    if layout is Layout.AOS:
        return P(*space, None)
    if layout is Layout.SOA:
        return P(None, *space)
    # AoSoA: the tiled (last-space) dim splits into (major, comp, lane);
    # any sharding of it lands on the tile-major axis (whole tiles).
    return P(*space[:-1], space[-1], None, None)
