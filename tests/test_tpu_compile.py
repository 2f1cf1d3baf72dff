"""Compile the main path for a described TPU v5e at real widths.

Interpret mode accepts what Mosaic refuses (unaligned blocks, scalars read
from HBM, oversized VMEM), so each Pallas kernel — and every tile its
autotuner hook proposes — is compiled here for a v5e chip that is
described, not attached, and must come out as a ``tpu_custom_call``.  One
qwen1.5-4b decode-layer region compiles the same way.  Nothing runs.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.  The persistent compile cache is off around these
compiles (a compile for a described chip cannot be read back).
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.layout import Layout, RecordArray

GRID = 2048


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev_cache = jax.config.jax_enable_compilation_cache
    prev_log = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler here: nothing to check
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", prev_cache)
        cc.reset_cache()
        if prev_log is None:
            os.environ.pop("TPU_LOG_DIR", None)


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


# -- kernels (the table of the bring-up) ---------------------------------------

def _saxpy(s):
    from repro.kernels.saxpy.kernel import saxpy_pallas

    n = 16 << 20
    return (lambda x, y: saxpy_pallas(2.0, x, y, interpret=False),
            _sds(s, (n,)), _sds(s, (n,)))


def _record(spec, n, layout, kernel, s):
    shape = RecordArray.storage_shape(spec, (n,), layout)
    return (lambda d: kernel(RecordArray(d, spec, layout)).data,
            _sds(s, shape))


def _saxpy_record(s):
    from repro.kernels.saxpy.kernel import SAXPY_SPEC, saxpy_record_pallas

    return _record(SAXPY_SPEC, 16 << 20, Layout.SOA,
                   lambda r: saxpy_record_pallas(r, 2.0, interpret=False), s)


def _particle(s):
    from repro.kernels.particle.kernel import (PARTICLE_SPEC,
                                               particle_update_pallas)

    return _record(PARTICLE_SPEC, 1 << 20, Layout.AOSOA,
                   lambda r: particle_update_pallas(r, 0.1, interpret=False),
                   s)


def _flux(s, block=(8, 128)):
    from repro.kernels.stencil.kernel import flux_difference_pallas
    from repro.physics.euler import EULER_SPEC

    shape = RecordArray.storage_shape(EULER_SPEC, (GRID + 2, GRID + 2),
                                      Layout.SOA)
    return (lambda d: flux_difference_pallas(
                RecordArray(d, EULER_SPEC, Layout.SOA), 0.1, 0.1,
                block=block, interpret=False).data,
            _sds(s, shape))


def _eikonal(s, block=(8, 128)):
    from repro.kernels.eikonal.kernel import eikonal_fim_pallas

    return (lambda p, m: eikonal_fim_pallas(p, m, 1.0 / GRID, block=block,
                                            interpret=False),
            _sds(s, (GRID + 2, GRID + 2)), _sds(s, (GRID, GRID), jnp.bool_))


def _attention(s, blocks=(128, 128), fused=False):
    from repro.kernels.attention.kernel import flash_attention_pallas

    bq, bk = blocks
    q = _sds(s, (1, 8, 1024, 128), jnp.bfloat16)
    if fused:
        return (lambda q, kv: flash_attention_pallas(
                    q, kv, None, block_q=bq, block_k=bk, interpret=False),
                q, _sds(s, (1, 8, 1024, 2, 128), jnp.bfloat16))
    return (lambda q, k, v: flash_attention_pallas(
                q, k, v, block_q=bq, block_k=bk, interpret=False),
            q, q, q)


def _ssd(s):
    from repro.kernels.ssd.kernel import ssd_intra_chunk_pallas

    B, S, H, P, N = 1, 1024, 24, 64, 128
    return (lambda *a: ssd_intra_chunk_pallas(*a, interpret=False),
            _sds(s, (B, S, H, P)), _sds(s, (B, S, H)), _sds(s, (H,)),
            _sds(s, (B, S, N)), _sds(s, (B, S, N)))


KERNELS = {
    "saxpy": _saxpy,
    "saxpy_record_soa": _saxpy_record,
    "particle_aosoa": _particle,
    "flux_soa": _flux,
    "eikonal": _eikonal,
    "attention_soa": _attention,
    "attention_fused_kv": lambda s: _attention(s, fused=True),
    "ssd_intra_chunk": _ssd,
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, *args = KERNELS[name](one_chip)
    assert "tpu_custom_call" in _compiled_text(fn, *args)


# -- every tile the autotuner may propose must compile -------------------------

def _tile_cases():
    from repro.kernels.attention import kernel as attention
    from repro.kernels.eikonal import kernel as eikonal
    from repro.kernels.stencil import kernel as stencil

    cases = [("flux", b) for b in stencil.tile_candidates((GRID, GRID))]
    cases += [("eikonal", b) for b in eikonal.tile_candidates((GRID, GRID))]
    cases += [("attention", b)
              for b in attention.tile_candidates((1024, 1024))]
    return cases


@pytest.mark.parametrize("kernel,tile", _tile_cases(),
                         ids=lambda v: "x".join(map(str, v))
                         if isinstance(v, tuple) else v)
def test_tile_candidate_compiles_for_v5e(one_chip, kernel, tile):
    build = {"flux": _flux, "eikonal": _eikonal,
             "attention": _attention}[kernel]
    fn, *args = build(one_chip, tile)
    assert "tpu_custom_call" in _compiled_text(fn, *args)


# -- one qwen1.5-4b decode-layer region ---------------------------------------

def test_qwen_decode_layer_region_compiles_for_v5e(one_chip):
    """Embed, one decoder layer and the head at qwen1.5-4b's published
    widths, through the serving graph's region executable: the weights
    arrive as arguments (no HLO constant anywhere near their size) and
    the program fits one chip."""
    import repro.configs as configs
    from repro.analysis.hlo import HloCostModel, _shape_bytes
    from repro.core import Executor
    from repro.launch import steps
    from repro.models.lm import init_lm

    cfg = configs.get("qwen1.5-4b").with_(n_layers=1)
    params = jax.eval_shape(lambda k: init_lm(cfg, k)[0],
                            jax.random.PRNGKey(0))
    dg = steps.make_decode_graph(cfg, params, batch=4, max_seq=144)
    ex = Executor(dg.graph)
    state = {name: _sds(one_chip, ex._eff(t).storage_shape, t.dtype)
             for name, t in ex.tensors.items()}
    fn, _ = ex._region_executable(ex._regions[0])
    compiled = fn.jit_fn.lower(
        *ex._split_state(state, fn.donate_keys, fn.read_only)).compile()
    weight_bytes = sum(x.size * x.dtype.itemsize
                       for x in jax.tree.leaves(params))
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes >= weight_bytes
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 16 * 10**9)
    model = HloCostModel(compiled.as_text())
    assert max((_shape_bytes(op.result_sig)
                for comp in model.comps.values() for op in comp.ops
                if op.opcode == "constant"), default=0) < 1 << 20
