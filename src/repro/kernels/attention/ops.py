"""Public attention ops: pallas flash for training/prefill, jnp fallback,
fused-AoS and split-SoA KV entry points."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.tuning.tiles import resolve_tile
from .kernel import DEFAULT_BLOCKS, TILE_KERNEL, flash_attention_pallas
from .ref import decode_ref, mha_ref


@partial(jax.jit, static_argnames=("causal", "window", "q_offset", "scale",
                                   "block_q", "block_k", "use_pallas"))
def _flash_attention_jit(q, k, v=None, *, causal, window, q_offset,
                         scale, block_q, block_k, use_pallas):
    if use_pallas:
        return flash_attention_pallas(
            q, k, v, causal=causal, window=window, q_offset=q_offset,
            scale=scale, block_q=block_q, block_k=block_k)
    if v is None:
        k, v = k[..., 0, :], k[..., 1, :]
    return mha_ref(q, k, v, causal=causal, window=window, q_offset=q_offset,
                   scale=scale)


def flash_attention(q, k, v=None, *, causal=True, window=None, q_offset=0,
                    scale=None, block_q=None, block_k=None,
                    use_pallas=True):
    """Flash attention over layout-polymorphic KV storage.  SOA path:
    ``(q, k, v)``; AOS path: ``(q, kv_fused, None)`` with kv
    ``(B, Hkv, S, 2, D)``.

    ``block_q``/``block_k`` default to the autotuner's ambient tile
    scope (kernel ``"attention"``, one ``(block_q, block_k)`` config);
    explicit values always win, and outside any scope the kernel
    defaults apply."""
    explicit = ((block_q or DEFAULT_BLOCKS[0],
                 block_k or DEFAULT_BLOCKS[1])
                if block_q is not None or block_k is not None else None)
    block_q, block_k = resolve_tile(TILE_KERNEL, explicit, DEFAULT_BLOCKS,
                                    shape=(q.shape[2], k.shape[2]))
    return _flash_attention_jit(
        q, k, v, causal=causal, window=window, q_offset=q_offset,
        scale=scale, block_q=block_q, block_k=block_k,
        use_pallas=use_pallas)


attention_decode = decode_ref
