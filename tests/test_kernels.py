"""Per-Pallas-kernel shape/dtype sweeps vs the pure-jnp ref.py oracles
(interpret mode executes the kernel body on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (Boundary, Layout, RecordArray, pad_boundary_only,
                        relayout)


# -- interpret mode follows the platform ----------------------------------------

@pytest.mark.parametrize("backend,requested,want", [
    ("cpu", None, True), ("cpu", False, False), ("cpu", True, True),
    ("tpu", None, False), ("tpu", False, False), ("tpu", True, ValueError),
])
def test_interpret_mode_follows_platform(monkeypatch, backend, requested,
                                         want):
    from repro.kernels import interpret_mode

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if want is ValueError:
        with pytest.raises(ValueError, match="TPU"):
            interpret_mode(requested)
    else:
        assert interpret_mode(requested) is want


# -- saxpy --------------------------------------------------------------------

@pytest.mark.parametrize("n", [128, 1000, 4096])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("bounds_check", [True, False])
def test_saxpy_sweep(rng, n, dtype, bounds_check):
    from repro.kernels.saxpy.ops import saxpy
    from repro.kernels.saxpy.ref import saxpy_ref
    if not bounds_check and n % 1024:
        pytest.skip("NBC variant requires exact tiling (paper's point)")
    x = jnp.asarray(rng.standard_normal(n), dtype)
    y = jnp.asarray(rng.standard_normal(n), dtype)
    out = saxpy(2.5, x, y, bounds_check=bounds_check)
    ref = saxpy_ref(2.5, x, y)
    tol = 1e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=tol,
                               atol=tol)


# -- particle -----------------------------------------------------------------

@pytest.mark.parametrize("n", [128, 1024, 4096])
@pytest.mark.parametrize("layout", [Layout.AOS, Layout.SOA, Layout.AOSOA])
def test_saxpy_record_sweep(rng, n, layout):
    from repro.kernels.saxpy.kernel import SAXPY_SPEC
    from repro.kernels.saxpy.ops import saxpy_record
    from repro.kernels.saxpy.ref import saxpy_record_ref
    rec = RecordArray.from_fields(
        SAXPY_SPEC,
        {"x": jnp.asarray(rng.standard_normal(n), jnp.float32),
         "y": jnp.asarray(rng.standard_normal(n), jnp.float32)},
        layout)
    out = saxpy_record(rec, 2.5, block=min(n, 1024))
    ref = saxpy_record_ref(rec, 2.5)
    assert out.layout is layout
    np.testing.assert_allclose(np.asarray(out.data), np.asarray(ref.data),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("n,block", [(256, 128), (1024, 512), (1024, 256)])
@pytest.mark.parametrize("layout", [Layout.AOS, Layout.SOA, Layout.AOSOA])
def test_particle_sweep(rng, n, block, layout):
    from repro.kernels.particle.ops import (PARTICLE_SPEC, particle_update,
                                            particle_update_ref)
    rec = RecordArray.from_fields(
        PARTICLE_SPEC,
        {"x": jnp.asarray(rng.standard_normal((n, 3), dtype=np.float32)),
         "v": jnp.asarray(rng.standard_normal((n, 3), dtype=np.float32))},
        layout)
    out = particle_update(rec, 0.25, block=block)
    ref = particle_update_ref(rec, 0.25)
    np.testing.assert_allclose(np.asarray(out.data), np.asarray(ref.data),
                               rtol=1e-6, atol=1e-6)


# -- stencil (FORCE flux) ------------------------------------------------------

@pytest.mark.parametrize("shape", [(32, 16), (64, 64)])
@pytest.mark.parametrize("layout", [Layout.AOS, Layout.SOA, Layout.AOSOA])
def test_flux_sweep(shape, layout):
    from repro.kernels.stencil.ops import flux_difference, flux_difference_ref
    from repro.physics.euler import EULER_SPEC, shock_bubble_init
    U = shock_bubble_init(*shape)
    d = U
    for ax in (1, 2):
        d = pad_boundary_only(d, axis=ax, width=1,
                              boundary=Boundary.TRANSMISSIVE)
    hal = relayout(RecordArray(d, EULER_SPEC, Layout.SOA), layout)
    out = flux_difference(hal, 0.1, 0.1)
    ref = flux_difference_ref(hal, 0.1, 0.1)
    o = out.data if isinstance(out, RecordArray) else out
    r = ref.data if isinstance(ref, RecordArray) else ref
    np.testing.assert_allclose(np.asarray(o), np.asarray(r), rtol=1e-4,
                               atol=1e-5)


# -- split FORCE update (euler.update_dim's kernel) ----------------------------

def _euler_state(shape, seed=0):
    """A float32 Euler state with varied density, velocity and pressure."""
    r = np.random.default_rng(seed)
    rho = 1.0 + r.random(shape)
    u, v = r.standard_normal(shape), r.standard_normal(shape)
    p = 1.0 + r.random(shape)
    E = p / 0.4 + 0.5 * rho * (u * u + v * v)
    return jnp.asarray(np.stack([rho, E, rho * u, rho * v]), jnp.float32)


@pytest.mark.parametrize("dim", [0, 1])
@pytest.mark.parametrize("out,block,boundary", [
    ((64, 256), (32, 128), Boundary.TRANSMISSIVE),    # tiles the interior
    ((62, 250), (32, 128), Boundary.TRANSMISSIVE),    # ragged both ways
    ((62, 250), (64, 1024), Boundary.PERIODIC),       # one ragged tile
    ((30, 256), (8, 128), Boundary.PERIODIC),         # 2x2 x interior, small
    ((32, 254), (16, 128), Boundary.TRANSMISSIVE),    # 2x2 y interior, small
    ((8, 128), (8, 128), Boundary.TRANSMISSIVE),      # one vreg
])
def test_force_update_kernel_matches_update_dim(dim, out, block, boundary):
    """The kernel in interpret mode against the jnp formula, with ``lam``
    traced: float32 rounding (1e-6 of the field's scale) at most."""
    from repro.kernels.stencil.update import force_update_pallas
    from repro.physics import euler

    U = pad_boundary_only(_euler_state(out), axis=dim + 1, width=1,
                          boundary=boundary)
    lam = jnp.float32(0.13)
    got = jax.jit(lambda u, l: force_update_pallas(u, dim, l, block=block,
                                                   interpret=True))(U, lam)
    want = jax.jit(lambda u, l: euler._update_dim(u, dim, l))(U, lam)
    assert got.shape == want.shape == (4, *out)
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got - want))) <= 1e-6 * scale


@pytest.mark.parametrize("dim,space,dtype,kernel", [
    (0, (3, 256), jnp.float32, False),     # a 1-wide boundary strip
    (1, (64, 3), jnp.float32, False),
    (1, (64, 129), jnp.float32, False),    # 127 lanes out: under one tile
    (0, (9, 128), jnp.bfloat16, False),    # the kernel is float32 only
    (0, (10, 128), jnp.float32, True),     # one (8, 128) tile out
    (1, (8, 130), jnp.float32, True),
])
def test_update_dim_takes_the_kernel_on_a_tpu_when_a_tile_fits(
        monkeypatch, dim, space, dtype, kernel):
    """On a TPU ``update_dim`` calls the kernel when the output fills an
    (8, 128) tile and keeps the jnp formula for thin strips."""
    from repro.kernels.stencil import update
    from repro.physics import euler

    calls = []
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(update, "force_update_pallas",
                        lambda u, d, lam, **kw: calls.append(kw) or u)
    U = _euler_state(space).astype(dtype)
    out = euler.update_dim(U, dim, 0.1)
    assert calls == ([{"interpret": False}] if kernel else [])
    if not kernel:
        want = list(space)
        want[dim] -= 2
        assert out.shape == (4, *want)


# -- eikonal (FIM) --------------------------------------------------------------

@pytest.mark.parametrize("n", [32, 64])
def test_eikonal_sweep(n):
    from repro.kernels.eikonal.ops import eikonal_fim_ref, eikonal_fim_sweep
    phi = jnp.full((n, n), 1e3, jnp.float32)
    src = jnp.zeros((n, n), bool).at[n // 2, n // 2].set(True)
    phi = jnp.where(src, 0.0, phi)
    ph = pad_boundary_only(pad_boundary_only(phi, axis=0, width=1),
                           axis=1, width=1)
    o1 = eikonal_fim_sweep(ph, src, 1.0 / n)
    o2 = eikonal_fim_ref(ph, src, 1.0 / n)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), rtol=1e-5,
                               atol=1e-6)


def _haloed_field(shape, source=None, seed=0):
    """A float32 field, its source mask and the field padded
    transmissively: random values with 5% sources and one negative cell
    that is not a source, or, with ``source``, one source in a far
    field."""
    rng = np.random.default_rng(seed)
    if source is None:
        mask = jnp.asarray(rng.uniform(size=shape) < 0.05).at[1, 1].set(False)
        phi = jnp.asarray(rng.uniform(size=shape), jnp.float32)
        phi = phi.at[1, 1].set(-0.25)
    else:
        mask = jnp.zeros(shape, bool).at[source].set(True)
        phi = jnp.full(shape, 4.0, jnp.float32)
    phi = jnp.where(mask, 0.0, phi)
    return mask, pad_boundary_only(pad_boundary_only(phi, axis=0, width=1),
                                   axis=1, width=1)


@pytest.mark.parametrize("shape,block,source", [
    ((64, 256), (32, 256), None),
    ((72, 256), (32, 256), None),
    ((40, 256), (64, 256), None),
    ((8, 128), None, None),
    ((48, 384), None, None),
    ((64, 256), None, (63, 255)),
    ((40, 300), (16, 128), None),
], ids=["band_multiple", "ragged_rows", "under_one_band", "one_tile",
        "non_square", "corner_source", "column_panels"])
def test_eikonal_stream_matches_godunov_update(shape, block, source):
    """The streaming sweep (``inner == 1``) is the jnp Godunov sweep bit
    for bit.  Both run jitted: XLA's CPU fusions contract multiply-adds,
    which op-by-op dispatch does not."""
    from repro.kernels.eikonal.kernel import (eikonal_fim_stream,
                                              godunov_update)
    mask, ph = _haloed_field(shape, source)
    h = 1.0 / shape[0]
    got = jax.jit(lambda p, m: eikonal_fim_stream(p, m, h, block=block))(
        ph, mask)
    want = jax.jit(lambda p, m: godunov_update(p, m, h))(ph, mask)
    assert got.shape == shape
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("block", [(8, 128), (16, 256)])
def test_eikonal_staged_inner_sweeps_match_ref(block):
    """``inner > 1`` keeps the staged kernel: frozen-halo sweeps per
    tile, which ``eikonal_fim_ref`` mirrors for the same block."""
    from repro.kernels.eikonal.ops import eikonal_fim_ref, eikonal_fim_sweep
    mask, ph = _haloed_field((32, 256), (16, 100))
    out = eikonal_fim_sweep(ph, mask, 1.0 / 32, inner=4, block=block)
    ref = eikonal_fim_ref(ph, mask, 1.0 / 32, inner=4, block=block)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_eikonal_sweep_takes_the_jnp_formula_for_a_thin_strip():
    """An ``inner == 1`` output thinner than one (8, 128) tile (the strips
    of an overlapped 2-D decomposition) is updated by the jnp formula."""
    from repro.kernels.eikonal.kernel import godunov_update
    from repro.kernels.eikonal.ops import eikonal_fim_sweep
    for shape in ((3, 256), (64, 3)):
        mask, ph = _haloed_field(shape)
        got = eikonal_fim_sweep(ph, mask, 1.0 / 64, inner=1)
        want = jax.jit(lambda p, m: godunov_update(p, m, 1.0 / 64))(ph, mask)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_eikonal_converges_to_distance():
    """Iterated FIM sweeps approach the euclidean distance field near the
    source (the paper's reinitialization use-case)."""
    from repro.kernels.eikonal.ops import eikonal_fim_sweep
    n = 64
    h = 1.0 / n
    phi = jnp.full((n, n), 1e3, jnp.float32)
    src = jnp.zeros((n, n), bool).at[n // 2, n // 2].set(True)
    phi = jnp.where(src, 0.0, phi)
    for _ in range(40):
        ph = pad_boundary_only(pad_boundary_only(phi, axis=0, width=1),
                               axis=1, width=1)
        phi = eikonal_fim_sweep(ph, src, h)
    ii, jj = np.meshgrid(np.arange(n), np.arange(n), indexing="ij")
    dist = np.hypot(ii - n // 2, jj - n // 2) * h
    band = dist < 0.2
    err = np.abs(np.asarray(phi) - dist)[band]
    assert err.max() < 3 * h, err.max()


# -- attention ------------------------------------------------------------------

@pytest.mark.parametrize("s,causal", [(128, True), (256, False)])
@pytest.mark.parametrize("hkv", [2, 4])
def test_flash_attention_sweep(rng, s, causal, hkv):
    from repro.kernels.attention.ops import flash_attention, mha_ref
    b, h, d = 2, 4, 32
    q = jnp.asarray(rng.standard_normal((b, h, s, d), dtype=np.float32)) * 0.3
    k = jnp.asarray(rng.standard_normal((b, hkv, s, d),
                                        dtype=np.float32)) * 0.3
    v = jnp.asarray(rng.standard_normal((b, hkv, s, d),
                                        dtype=np.float32)) * 0.3
    out = flash_attention(q, k, v, causal=causal)
    ref = mha_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3,
                               atol=2e-3)


def test_attention_decode_kernel(rng):
    from repro.kernels.attention.ops import attention_decode, decode_ref
    b, h, hkv, s, d = 2, 4, 2, 128, 32
    q = jnp.asarray(rng.standard_normal((b, h, 1, d),
                                        dtype=np.float32)) * 0.3
    k = jnp.asarray(rng.standard_normal((b, hkv, s, d),
                                        dtype=np.float32)) * 0.3
    v = jnp.asarray(rng.standard_normal((b, hkv, s, d),
                                        dtype=np.float32)) * 0.3
    lens = jnp.asarray([100, 64], jnp.int32)
    out = attention_decode(q, k, v, lens)
    ref = decode_ref(q, k, v, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3,
                               atol=2e-3)


# -- ssd -------------------------------------------------------------------------

@pytest.mark.parametrize("s,chunk", [(128, 32), (256, 64)])
def test_ssd_sweep(rng, s, chunk):
    from repro.kernels.ssd.ops import ssd, ssd_chunked, ssd_naive
    b, h, dh, ds = 2, 2, 16, 8
    x = jnp.asarray(rng.standard_normal((b, s, h, dh),
                                        dtype=np.float32)) * 0.3
    dt = jax.nn.softplus(jnp.asarray(
        rng.standard_normal((b, s, h), dtype=np.float32)))
    A = -jnp.exp(jnp.asarray(rng.standard_normal(h, dtype=np.float32)))
    B = jnp.asarray(rng.standard_normal((b, s, ds), dtype=np.float32)) * 0.3
    C = jnp.asarray(rng.standard_normal((b, s, ds), dtype=np.float32)) * 0.3
    D = jnp.asarray(rng.standard_normal(h, dtype=np.float32))
    y1, s1 = ssd(x, dt, A, B, C, D, chunk=chunk)
    y2, s2 = ssd_naive(x, dt, A, B, C, D)
    y3, s3 = ssd_chunked(x, dt, A, B, C, D, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=2e-3,
                               atol=2e-3)
    np.testing.assert_allclose(np.asarray(y3), np.asarray(y2), rtol=2e-3,
                               atol=2e-3)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=2e-3,
                               atol=2e-3)
