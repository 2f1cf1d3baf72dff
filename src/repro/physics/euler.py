"""2-D compressible Euler equations + FORCE flux (Toro) — paper §7.3/§8.

State is a 4-component record over the grid: conserved variables
``rho`` (density), ``E`` (total energy), ``mom`` (momentum vector, 2).
All functions operate on a *stacked* component-major array ``U`` of shape
``(4, *space)`` — which is exactly the SoA storage of the record, so the
SoA path is zero-copy while AoS pays a transpose (the paper's layout
effect, made structural).

On a TPU, :func:`update_dim` runs as one Pallas pass
(``repro.kernels.stencil.update``) whenever its float32 output fills an
(8, 128) tile; everywhere else, and for the 1-wide boundary strips of an
overlapped split, it is the jnp formula of this module.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.layout import RecordArray, RecordSpec, Vector

GAMMA = 1.4

EULER_SPEC = RecordSpec.create("rho", "E", Vector("mom", 2))

RHO, EN, MX, MY = 0, 1, 2, 3


def stack_state(state: RecordArray) -> jax.Array:
    """(4, *space) component-major view of an Euler state record
    (layout-generic: AoS/SoA are views, AoSoA relayouts)."""
    from repro.core.layout import Layout, relayout

    if state.layout is Layout.SOA:
        return state.data  # already (4, *space)
    if state.layout is Layout.AOS:
        return jnp.moveaxis(state.data, -1, 0)
    return relayout(state, Layout.SOA).data


def unstack_state(U: jax.Array, like: RecordArray) -> RecordArray:
    from repro.core.layout import Layout, relayout

    if like.layout is Layout.SOA:
        return RecordArray(U, like.spec, Layout.SOA)
    if like.layout is Layout.AOS:
        return RecordArray(jnp.moveaxis(U, 0, -1), like.spec, Layout.AOS)
    return relayout(RecordArray(U, like.spec, Layout.SOA), like.layout)


def pressure(U: jax.Array) -> jax.Array:
    ke = 0.5 * (U[MX] ** 2 + U[MY] ** 2) / U[RHO]
    return (GAMMA - 1.0) * (U[EN] - ke)


def sound_speed(U: jax.Array) -> jax.Array:
    return jnp.sqrt(GAMMA * pressure(U) / U[RHO])


def max_wavespeed(U: jax.Array) -> jax.Array:
    """max(|u_d| + c) over the grid — sets the CFL time step."""
    c = sound_speed(U)
    sx = jnp.abs(U[MX] / U[RHO]) + c
    sy = jnp.abs(U[MY] / U[RHO]) + c
    return jnp.maximum(sx.max(), sy.max())


def flux(U: jax.Array, dim: int) -> jax.Array:
    """Physical flux along grid dim (0=x, 1=y) of the stacked state."""
    p = pressure(U)
    m_d = U[MX + dim]
    u_d = m_d / U[RHO]
    return jnp.stack(
        [
            m_d,
            (U[EN] + p) * u_d,
            U[MX] * u_d + (p if dim == 0 else 0.0),
            U[MY] * u_d + (p if dim == 1 else 0.0),
        ],
        axis=0,
    )


def force_flux(UL: jax.Array, UR: jax.Array, dim: int, lam) -> jax.Array:
    """FORCE flux (first-ORder CEntred, Toro): mean of Lax-Friedrichs and
    Richtmyer fluxes at the interface.  ``lam = dt / dx``."""
    return force_flux_of(UL, UR, flux(UL, dim), flux(UR, dim), dim,
                         0.5 / lam, 0.5 * lam)


def force_flux_of(UL, UR, FL, FR, dim: int, half_over_lam, half_lam):
    """:func:`force_flux` from the physical fluxes ``FL``/``FR`` of the two
    sides and the coefficients ``0.5 / lam`` and ``0.5 * lam``: the same
    arithmetic, for a caller that holds the coefficients already (the
    update kernel reads them from SMEM, computed once outside)."""
    f_lf = 0.5 * (FL + FR) - half_over_lam * (UR - UL)
    u_rm = 0.5 * (UL + UR) - half_lam * (FR - FL)
    return 0.5 * (f_lf + flux(u_rm, dim))


def _shift(U: jax.Array, dim: int, off: int, n: int) -> jax.Array:
    """Slice of length n starting at ``off`` along space dim (axis dim+1)."""
    idx = [slice(None)] * U.ndim
    idx[dim + 1] = slice(off, off + n)
    return U[tuple(idx)]


def flux_difference_dim(U_haloed: jax.Array, dim: int, lam) -> jax.Array:
    """lam * (F_{i+1/2} - F_{i-1/2}) along ``dim``; input haloed by 1 in
    ``dim`` only."""
    n = U_haloed.shape[dim + 1] - 2
    Um = _shift(U_haloed, dim, 0, n + 1)  # cells i-1 .. (for faces)
    Up = _shift(U_haloed, dim, 1, n + 1)  # cells i ..
    F = force_flux(Um, Up, dim, lam)      # faces i-1/2 .. i+n-1/2 (n+1 faces)
    return lam * (_shift(F, dim, 1, n) - _shift(F, dim, 0, n))


def flux_difference(U_haloed: jax.Array, lam_x, lam_y) -> jax.Array:
    """Sum of directional flux differences (paper Table 4 kernel).

    Input haloed by 1 in BOTH space dims: shape (4, nx+2, ny+2)."""
    nx = U_haloed.shape[1] - 2
    ny = U_haloed.shape[2] - 2
    dx = flux_difference_dim(U_haloed[:, :, 1:-1], 0, lam_x)  # (4, nx, ny)
    dy = flux_difference_dim(U_haloed[:, 1:-1, :], 1, lam_y)
    return dx + dy


def update_dim(U_haloed: jax.Array, dim: int, lam) -> jax.Array:
    """Dimension-split FORCE update (paper Listing 12: update_state_x/y):
    U' = U - lam (F_{+} - F_{-}).  Haloed by 1 in ``dim`` only.

    On a TPU, a float32 update whose output fills at least one (8, 128)
    tile runs as one Pallas pass (``kernels/stencil/update.py``) that
    keeps the face fluxes in VMEM; elsewhere, and for thin boundary
    strips, the jnp formula below (the kernel's reference)."""
    from repro import kernels

    out = list(U_haloed.shape[1:])
    out[dim] -= 2
    if not kernels.interpret_mode():
        from repro.kernels.stencil import update

        if update.fits(tuple(out), U_haloed.dtype):
            return update.force_update_pallas(U_haloed, dim, lam,
                                              interpret=False)
    return _update_dim(U_haloed, dim, lam)


def _update_dim(U_haloed: jax.Array, dim: int, lam) -> jax.Array:
    """:func:`update_dim` as a jnp formula."""
    n = U_haloed.shape[dim + 1] - 2
    return _shift(U_haloed, dim, 1, n) - flux_difference_dim(U_haloed, dim, lam)


def update_full(U_haloed: jax.Array, lam_x, lam_y) -> jax.Array:
    """Unsplit FORCE update: U' = U - lam_x dF_x - lam_y dF_y in one shot.

    Haloed by 1 in BOTH space dims — one node whose input spans the full
    2-D extended shard, so a 2-D-partitioned run exercises the whole
    multi-axis transfer schedule and the N-axis overlapped lowering.
    Shape-polymorphic: (4, m+2, n+2) -> (4, m, n).  Stability: use
    dt <= cfl / (s * (1/dx + 1/dy)) rather than the split scheme's CFL."""
    center = U_haloed[:, 1:-1, 1:-1]
    return center - flux_difference(U_haloed, lam_x, lam_y)


def shock_bubble_init(nx: int, ny: int, *, mach: float = 3.81) -> jax.Array:
    """Initial conditions: Mach-3.81 shock hitting a low-density bubble
    (paper Fig. 11), on [0,2]x[0,1]."""
    x = (jnp.arange(nx) + 0.5) * (2.0 / nx)
    y = (jnp.arange(ny) + 0.5) * (1.0 / ny)
    X, Y = jnp.meshgrid(x, y, indexing="ij")

    # ambient air
    rho = jnp.ones((nx, ny))
    p = jnp.ones((nx, ny))
    u = jnp.zeros((nx, ny))
    v = jnp.zeros((nx, ny))

    # low-density bubble at (0.8, 0.5), r = 0.2
    bubble = (X - 0.8) ** 2 + (Y - 0.5) ** 2 < 0.2**2
    rho = jnp.where(bubble, 0.1, rho)

    # post-shock state (left of x = 0.3), normal shock relations, Ms = mach
    ms = mach
    g = GAMMA
    rho_r, p_r = 1.0, 1.0
    p_l = p_r * (2 * g * ms**2 - (g - 1)) / (g + 1)
    rho_l = rho_r * ((g + 1) * ms**2) / ((g - 1) * ms**2 + 2)
    c_r = jnp.sqrt(g * p_r / rho_r)
    u_l = ms * c_r * (1 - rho_r / rho_l)
    shock = X < 0.3
    rho = jnp.where(shock, rho_l, rho)
    p = jnp.where(shock, p_l, p)
    u = jnp.where(shock, u_l, u)

    E = p / (GAMMA - 1.0) + 0.5 * rho * (u**2 + v**2)
    return jnp.stack([rho, E, rho * u, rho * v], axis=0)
