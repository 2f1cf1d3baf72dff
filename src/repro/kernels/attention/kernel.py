"""Flash attention Pallas kernel — GQA / causal / sliding-window, with
layout-polymorphic KV storage (Ripple C1 applied to the KV cache).

TPU mapping: q tiles of (block_q, head_dim) live in VMEM; K/V stay in
``ANY`` (HBM) and are copied block-by-block into VMEM scratch buffers,
with running-softmax accumulation (online softmax).  block_q/block_k are
the VMEM knobs and should be multiples of 128 for MXU alignment.

KV layouts (DESIGN.md §5):
  * SOA — separate ``k`` and ``v`` arrays (B, Hkv, S, D): streaming reads
    are contiguous per tensor;
  * AOS — one fused array (B, Hkv, S, 2, D) interleaving k/v per position:
    one DMA fetches both, at the cost of a strided minor dim.

Causal masking supports a query-position offset so the same kernel serves
training (offset 0), chunked prefill (offset = chunk start) and scoring.
Sliding-window (``window``) implements gemma3 / recurrentgemma local
attention; the kv block loop is *clipped* to the causal/window range so
skipped blocks cost nothing (the paper's dependency-minimal scheduling,
at the kernel level).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode
from repro.tuning.tiles import register_tile_kernel

NEG_INF = -1e30

TILE_KERNEL = "attention"  # name in the autotuner's tile registry
DEFAULT_BLOCKS = (128, 128)


def tile_candidates(shape: tuple[int, ...]) -> tuple[tuple[int, int], ...]:
    """Feasible ``(block_q, block_k)`` pairs for query/kv sequence
    lengths ``(sq, skv)`` (the autotuner's search axis): MXU-aligned
    multiples of 64 that tile both sequences exactly."""
    sq, skv = shape
    return tuple((bq, bk)
                 for bq in (64, 128, 256) if bq <= sq and sq % bq == 0
                 for bk in (64, 128, 256) if bk <= skv and skv % bk == 0)


register_tile_kernel(TILE_KERNEL, tile_candidates)


def _attn_kernel(
    scale: float,
    causal: bool,
    window: int | None,
    block_q: int,
    block_k: int,
    skv: int,
    q_offset: int,
    fused_kv: bool,
    q_ref,
    *refs,
):
    # inputs (K/V or fused KV, in ANY), the output block, then one VMEM
    # scratch buffer per input
    n_in = 1 if fused_kv else 2
    kv_refs, o_ref, bufs = refs[:n_in], refs[n_in], refs[n_in + 1:]
    b = pl.program_id(0)
    h = pl.program_id(1)
    qi = pl.program_id(2)

    q = q_ref[0, 0].astype(jnp.float32)  # (block_q, D)
    d = q.shape[-1]
    n_kv_heads = kv_refs[0].shape[1]
    n_q_heads = pl.num_programs(1)
    hkv = h // max(1, n_q_heads // n_kv_heads)

    q_pos = q_offset + qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)

    # clip the kv loop to the causal / window range (block skipping)
    if causal:
        hi_pos = q_offset + (qi + 1) * block_q  # exclusive
        hi = (hi_pos + block_k - 1) // block_k
        hi = min(hi, skv // block_k) if isinstance(hi, int) else jnp.minimum(
            hi, skv // block_k)
    else:
        hi = skv // block_k
    if window is not None:
        lo_pos = q_offset + qi * block_q - window
        lo = jnp.maximum(lo_pos // block_k, 0) if not isinstance(
            lo_pos, int) else max(lo_pos // block_k, 0)
    else:
        lo = 0

    def load_kv(kb):
        start = kb * block_k
        for src, buf in zip(kv_refs, bufs):
            pltpu.sync_copy(src.at[b, hkv, pl.ds(start, block_k)], buf)
        if fused_kv:
            kv = bufs[0][...]  # (bk, 2, D)
            return kv[:, 0].astype(jnp.float32), kv[:, 1].astype(jnp.float32)
        return (bufs[0][...].astype(jnp.float32),
                bufs[1][...].astype(jnp.float32))

    def body(kb, carry):
        acc, m, l = carry
        k, v = load_kv(kb)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ()))) * scale  # (bq, bk)
        k_pos = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = jnp.ones(s.shape, dtype=bool)
        if causal:
            mask &= q_pos >= k_pos
        if window is not None:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=-1, keepdims=True)
        acc_new = acc * alpha + p @ v
        return acc_new, m_new, l_new

    acc = jnp.zeros((q.shape[0], d), jnp.float32)
    m = jnp.full((q.shape[0], 1), NEG_INF, jnp.float32)
    l = jnp.zeros((q.shape[0], 1), jnp.float32)
    acc, m, l = jax.lax.fori_loop(lo, hi, body, (acc, m, l))
    out = acc / jnp.maximum(l, 1e-20)
    o_ref[0, 0] = out.astype(o_ref.dtype)


def flash_attention_pallas(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array | None = None,
    *,
    causal: bool = True,
    window: int | None = None,
    q_offset: int = 0,
    scale: float | None = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """q: (B, Hq, Sq, D).  SOA: k,v each (B, Hkv, Skv, D).
    AOS: pass fused kv as ``k`` with shape (B, Hkv, Skv, 2, D), v=None."""
    fused = v is None
    B, Hq, Sq, D = q.shape
    skv = k.shape[2]
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    block_q = min(block_q, Sq)
    block_k = min(block_k, skv)
    assert Sq % block_q == 0 and skv % block_k == 0
    grid = (B, Hq, Sq // block_q)

    kern = functools.partial(
        _attn_kernel, scale, causal, window, block_q, block_k, skv,
        q_offset, fused)
    operands = [k] if fused else [k, v]
    in_specs = [pl.BlockSpec((1, 1, block_q, D), lambda b, h, i: (b, h, i, 0))]
    in_specs += [pl.BlockSpec(memory_space=pl.ANY)] * len(operands)
    scratch = [pltpu.VMEM((block_k, *x.shape[3:]), x.dtype) for x in operands]

    return pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, block_q, D), lambda b, h, i: (b, h, i, 0)),
        scratch_shapes=scratch,
        interpret=interpret_mode(interpret),
    )(q, *operands)
