"""Mamba-2 SSD intra-chunk Pallas kernel.

The chunked dual form splits SSD into (a) an intra-chunk quadratic part —
the FLOPs-dominant, MXU-friendly piece, computed here per (batch, head,
chunk) tile in VMEM — and (b) a cheap inter-chunk state scan left to XLA
(see ref.ssd_chunked).  The kernel also emits each chunk's outgoing state
contribution so the host-side scan needs no second data pass.

Tile: x (L, P), dt (L, 1), B/C (L, N) with L = chunk, all staged in
VMEM by BlockSpecs over head-major copies of x/dt (so the last two block
dims are (L, P) and (L, 1)); A rides in SMEM.  The in-chunk cumulative
sum is a lower-triangular matmul, and matmuls (L,N)x(N,L) and
(L,L)x(L,P) map to the MXU at L,P,N multiples of 128 (L=chunk is the
block knob).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode
from repro.tuning.tiles import register_tile_kernel

TILE_KERNEL = "ssd"       # name in the autotuner's tile registry
DEFAULT_CHUNK = 64


def tile_candidates(shape: tuple[int, ...]) -> tuple[int, ...]:
    """Feasible chunk lengths for a sequence of ``S`` positions (the
    autotuner's search axis): the chunk is the L of the intra-chunk
    quadratic part, so it trades MXU tile efficiency against the
    O(L^2) score matrix; exact tilings only."""
    (s,) = shape
    return tuple(c for c in (32, 64, 128, 256) if c <= s and s % c == 0)


register_tile_kernel(TILE_KERNEL, tile_candidates)


def _ssd_chunk_kernel(chunk: int,
                      x_ref, dt_ref, a_ref, b_ref, c_ref,
                      y_ref, s_ref):
    h = pl.program_id(2)
    L = chunk
    hi = jax.lax.Precision.HIGHEST

    x = x_ref[0, 0].astype(jnp.float32)                          # (L, P)
    dt = dt_ref[0, 0].astype(jnp.float32)                        # (L, 1)
    A = a_ref[h]                                                 # ()
    Bm = b_ref[0].astype(jnp.float32)                            # (L, N)
    C = c_ref[0].astype(jnp.float32)                             # (L, N)

    row = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    causal = row >= col
    tri = causal.astype(jnp.float32)
    da = dt * A                                                  # (L, 1)
    cs = jnp.dot(tri, da, precision=hi)                          # (L, 1)
    cs_row = jax.lax.dot_general(da, tri, (((0,), (1,)), ((), ())),
                                 precision=hi)                   # (1, L)
    decay = jnp.where(causal, jnp.exp(cs - cs_row), 0.0)         # (L, L)
    cb = jax.lax.dot_general(C, Bm, (((1,), (1,)), ((), ())))    # (L, L)
    scores = cb * decay
    dx = dt * x                                                  # (L, P)
    y = jnp.dot(scores, dx)                                      # (L, P)
    y_ref[0, 0] = y.astype(y_ref.dtype)

    # outgoing state contribution: sum_j exp(cs_L - cs_j) dt_j x_j B_j^T
    d2e = jnp.exp(jnp.sum(da) - cs)                              # (L, 1)
    w = (dt * d2e) * x                                           # (L, P)
    s = jax.lax.dot_general(w, Bm, (((0,), (0,)), ((), ())))     # (P, N)
    s_ref[0, 0, 0] = s.astype(s_ref.dtype)


def ssd_intra_chunk_pallas(x, dt, A, Bm, C, *, chunk: int = 64,
                           interpret: bool | None = None):
    """Returns (y_intra (B,S,H,P), s_chunk (B,nc,H,P,N)) — feed s_chunk to
    the inter-chunk scan in ref.ssd_chunked form."""
    B_, S, H, P = x.shape
    N = Bm.shape[-1]
    assert S % chunk == 0
    nc = S // chunk
    grid = (B_, nc, H)
    xh = jnp.moveaxis(x, 2, 1)                                   # (B,H,S,P)
    dth = jnp.moveaxis(dt, 2, 1)[..., None]                      # (B,H,S,1)

    kern = functools.partial(_ssd_chunk_kernel, chunk)
    y, s = pl.pallas_call(
        kern,
        out_shape=(
            jax.ShapeDtypeStruct((B_, H, S, P), x.dtype),
            jax.ShapeDtypeStruct((B_, nc, H, P, N), jnp.float32),
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, c, h: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, 1), lambda b, c, h: (b, h, c, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, chunk, N), lambda b, c, h: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, c, h: (b, c, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, 1, chunk, P), lambda b, c, h: (b, h, c, 0)),
            pl.BlockSpec((1, 1, 1, P, N), lambda b, c, h: (b, c, h, 0, 0)),
        ),
        interpret=interpret_mode(interpret),
    )(xh, dth, A.astype(jnp.float32), Bm, C)
    return jnp.moveaxis(y, 1, 2), s


def ssd_pallas(x, dt, A, Bm, C, D=None, init_state=None, *, chunk: int = 64):
    """Full SSD with the Pallas intra-chunk kernel + XLA inter-chunk scan."""
    B_, S, H, P = x.shape
    N = Bm.shape[-1]
    f32 = jnp.float32
    nc = S // chunk
    y_intra, s_chunk = ssd_intra_chunk_pallas(x, dt, A, Bm, C, chunk=chunk)

    dtc = dt.reshape(B_, nc, chunk, H).astype(f32)
    cs = jnp.cumsum(dtc * A, axis=2)
    total = jnp.exp(cs[:, :, -1, :])  # (B, nc, H)
    state0 = (jnp.zeros((B_, H, P, N), f32)
              if init_state is None else init_state.astype(f32))

    def step(state, inp):
        s_c, tot = inp
        return state * tot[..., None, None] + s_c, state

    final_state, entering = jax.lax.scan(
        step, state0, (jnp.moveaxis(s_chunk, 1, 0), jnp.moveaxis(total, 1, 0)))
    entering = jnp.moveaxis(entering, 0, 1)  # (B, nc, H, P, N)

    cc = C.reshape(B_, nc, chunk, N).astype(f32)
    in_decay = jnp.exp(cs)
    y_inter = jnp.einsum("bcin,bchpn,bcih->bcihp", cc, entering, in_decay)
    y = y_intra.astype(f32) + y_inter.reshape(B_, nc, chunk, H, P).reshape(
        B_, S, H, P)
    if D is not None:
        y = y + x.astype(f32) * D[None, None, :, None]
    return y.astype(x.dtype), final_state
