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


def _eikonal_stream(s, block=None):
    from repro.kernels.eikonal.kernel import eikonal_fim_stream

    return (lambda p, m: eikonal_fim_stream(p, m, 1.0 / GRID, block=block,
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


def _force_update(s, block=None):
    """Both split FORCE updates at GRID^2, as the Pallas kernel."""
    from repro.kernels.stencil.update import force_update_pallas

    def both(ux, uy, lam):
        return tuple(force_update_pallas(u, dim, lam, block=block,
                                         interpret=False)
                     for dim, u in enumerate((ux, uy)))

    return (both, _sds(s, (4, GRID + 2, GRID)), _sds(s, (4, GRID, GRID + 2)),
            _sds(s, ()))


KERNELS = {
    "saxpy": _saxpy,
    "saxpy_record_soa": _saxpy_record,
    "particle_aosoa": _particle,
    "flux_soa": _flux,
    "eikonal": _eikonal,
    "eikonal_stream": _eikonal_stream,
    "force_update_soa": _force_update,
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
    from repro.kernels.stencil import update

    cases = [("flux", b) for b in stencil.tile_candidates((GRID, GRID))]
    cases += [("force_update", b)
              for b in update.tile_candidates((GRID, GRID))]
    cases += [("eikonal", b) for b in eikonal.tile_candidates((GRID, GRID))]
    cases += [("attention", b)
              for b in attention.tile_candidates((1024, 1024))]
    cases += [("eikonal_stream", b)
              for b in eikonal.stream_candidates((GRID, GRID))]
    return cases


@pytest.mark.parametrize("kernel,tile", _tile_cases(),
                         ids=lambda v: "x".join(map(str, v))
                         if isinstance(v, tuple) else v)
def test_tile_candidate_compiles_for_v5e(one_chip, kernel, tile):
    build = {"flux": _flux, "eikonal": _eikonal, "force_update": _force_update,
             "attention": _attention, "eikonal_stream": _eikonal_stream}[kernel]
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


# -- the eikonal kernel's name, which the benchmark's roofline reads ----------

# bench/metrics/eikonal_sweep_hbm_roofline.py matches the kernel's op by this
EIKONAL_KERNEL = r"^%_eikonal_fim_jit[.\d]* = .*custom_call_target=\"tpu_custom_call\""


def test_eikonal_sweep_region_keeps_its_kernel_name_for_v5e(one_chip,
                                                            monkeypatch):
    """The FIM sweep in a device loop, as the benchmark builds it: the
    compiled region holds the Pallas call under the instruction name the
    roofline reader matches, with the node's name scope in its
    metadata."""
    import re

    from repro.core import (DistTensor, Executor, Graph, MaxReducer,
                            make_reduction_result)
    from repro.kernels.eikonal import kernel
    from repro.kernels.eikonal.ops import make_eikonal_graph

    monkeypatch.setattr(kernel, "interpret_mode",
                        lambda interpret=None: False)
    phi = DistTensor("phi", (GRID, GRID), halo=(1, 1))
    mask = DistTensor("mask", (GRID, GRID), dtype=jnp.bool_)
    res = make_reduction_result("res", init=1.0)
    loop = make_eikonal_graph(phi, mask, 1.0 / GRID, overlap=False,
                              use_pallas=True, graph=Graph(name="fim_sweep"))
    loop.then_reduce(phi, res, MaxReducer())
    loop.conditional(lambda s: s["res"] > 0)
    solve = Graph(name="fim_solve")
    solve.emplace(loop)
    ex = Executor(solve)
    state = {name: _sds(one_chip, ex._eff(t).storage_shape, t.dtype)
             for name, t in ex.tensors.items()}
    state["res"] = _sds(one_chip, (), res.dtype)
    fn, _ = ex._region_executable(ex._regions[0])
    text = fn.jit_fn.lower(
        *ex._split_state(state, fn.donate_keys, fn.read_only)
    ).compile().as_text()
    calls = [line.strip() for line in text.splitlines()
             if re.match(EIKONAL_KERNEL, line.strip())]
    assert calls, "no tpu_custom_call named _eikonal_fim_jit"
    assert all("/fim_sweep/sweep/" in c for c in calls), calls


def test_eikonal_solve_streams_its_sweep_for_v5e(one_chip, monkeypatch):
    """The benchmark's solve (``inner=1`` on the (2050, 2050) haloed field
    in the device loop): one streaming Pallas call a sweep under the name
    the roofline reader matches, fed by no full-field pad and no mask
    convert inside the loop, with the VMEM limit the shape rule sets."""
    import re

    from repro.core import (DistTensor, Executor, Graph, MaxReducer,
                            make_reduction_result)
    from repro.kernels.eikonal import kernel
    from repro.kernels.eikonal.ops import make_eikonal_graph

    monkeypatch.setattr(kernel, "interpret_mode",
                        lambda interpret=None: False)
    phi = DistTensor("phi", (GRID, GRID), halo=(1, 1))
    mask = DistTensor("mask", (GRID, GRID), dtype=jnp.bool_)
    prev = DistTensor("phi_prev", (GRID, GRID))
    change = DistTensor("change", (GRID, GRID))
    res = make_reduction_result("res", init=1.0)
    loop = make_eikonal_graph(phi, mask, 1.0 / GRID, inner=1, overlap=False,
                              use_pallas=True, graph=Graph(name="fim_sweep"))
    loop.then_split(lambda p, q, _d: (p, jnp.abs(p - q)), phi, prev, change,
                    writes=(1, 2))
    loop.then_reduce(change, res, MaxReducer())
    loop.conditional(lambda s: s["res"] > 0)
    solve = Graph(name="fim_solve")
    solve.emplace(loop)
    ex = Executor(solve)
    state = {name: _sds(one_chip, ex._eff(t).storage_shape, t.dtype)
             for name, t in ex.tensors.items()}
    state["res"] = _sds(one_chip, (), res.dtype)
    fn, _ = ex._region_executable(ex._regions[0])
    text = fn.jit_fn.lower(
        *ex._split_state(state, fn.donate_keys, fn.read_only)
    ).compile().as_text()
    lines = [line.strip() for line in text.splitlines()]
    calls = [line for line in lines if re.match(EIKONAL_KERNEL, line)]
    assert len(calls) == 1, [l for l in lines if "tpu_custom_call" in l]
    operands = re.search(r"custom-call\(([^)]*)\)", calls[0]).group(1)
    defs = {line.split(" = ")[0].lstrip("%"): line for line in lines
            if " = " in line}
    fed_by = [defs[o.strip().lstrip("%")] for o in operands.split(",")]
    assert not [d for d in fed_by if " pad(" in d or " convert(" in d], fed_by
    assert not [l for l in lines if re.search(r"f32\[20\d\d,\d+\].* pad\(", l)]
    limit = kernel.stream_vmem_limit(*kernel.stream_block((GRID, GRID)))
    sizes = re.search(r'"scoped_memory_configs":\[([^\]]*)\]', calls[0])
    assert f'"size":"{limit}"' in sizes.group(1), sizes.group(1)


# -- the split FORCE update's kernel, which its roofline reads -----------------

# bench/metrics/euler_update_hbm_roofline.py matches the kernel's op by this
FORCE_UPDATE_KERNEL = (r"^(ROOT )?%euler_force_update[.\d]* = "
                       r".*custom_call_target=\"tpu_custom_call\"")


@pytest.mark.parametrize("dim,space", [
    (0, (16386, 8192)),    # euler.chunk10, the x update
    (1, (16384, 8194)),    # euler.chunk10, the y update
    (0, (8192, 4096)),     # euler2x2.chunk10, the x interior (8190 out)
    (1, (8192, 4096)),     # euler2x2.chunk10, the y interior (4094 out)
], ids=["x", "y", "x_2x2_interior", "y_2x2_interior"])
def test_update_dim_runs_one_named_kernel_for_v5e(one_chip, monkeypatch,
                                                  dim, space):
    """``euler.update_dim`` on a TPU at the benchmark cells' shapes: one
    Pallas call under the name the roofline reader matches, and no
    full-grid face-flux temporaries (the XLA update's
    ``f32[1,16385,8192]`` fusion outputs)."""
    import re

    import repro.kernels
    from repro.physics import euler

    monkeypatch.setattr(repro.kernels, "interpret_mode",
                        lambda interpret=None: False)
    text = _compiled_text(lambda u, lam: euler.update_dim(u, dim, lam),
                          _sds(one_chip, (4, *space)), _sds(one_chip, ()))
    lines = [line.strip() for line in text.splitlines()]
    calls = [line for line in lines if re.match(FORCE_UPDATE_KERNEL, line)]
    assert len(calls) == 1, [l for l in lines if "custom_call" in l]
    faces = list(space)             # n + 1 faces along the update
    faces[dim] -= 1
    assert not [line for line in lines
                if "{},{}]".format(*faces) in line]


# -- the snapshot cell: its fused chunk and its named copy ----------------------

# bench/metrics/snapshot_copy_hbm_roofline.py matches the copy's op by this
SNAPSHOT_COPY = (r"^(ROOT )?%host_snapshot[.\d]* = "
                 r".*custom_call_target=\"tpu_custom_call\"")


def test_snapshot_graph_fuses_its_step_and_names_its_copy_for_v5e(
        one_chip, monkeypatch):
    """The ``euler.snapshot100`` graph at 16384 x 8192: ``run()`` takes
    the fused path with its snapshot node, the chunk's one program holds
    both ``euler_force_update`` calls, and the double-buffer copy of the
    state compiles to the ``host_snapshot`` kernel the roofline reader
    matches."""
    import importlib.util
    import json
    import re
    import sys
    from pathlib import Path

    import repro.kernels
    from repro.core.executor import ripple_host_snapshot

    root = Path(__file__).resolve().parents[1]
    monkeypatch.setattr(repro.kernels, "interpret_mode",
                        lambda interpret=None: False)
    monkeypatch.syspath_prepend(str(root / "bench"))
    spec = importlib.util.spec_from_file_location(
        "bench_model_euler_snapshot",
        root / "bench" / "models" / "euler_snapshot.py")
    model = importlib.util.module_from_spec(spec)
    monkeypatch.setitem(sys.modules, spec.name, model)
    spec.loader.exec_module(model)
    cfg = json.loads((root / "bench" / "configs"
                      / "euler-sb-16384x8192-snap100.json").read_text())

    ex = model.build_solver(cfg, model.HostRing(cfg))
    assert [n.every for _, n in ex._periodic_hosts] == [100]
    state = {name: _sds(one_chip, ex._eff(t).storage_shape, t.dtype)
             for name, t in ex.tensors.items()}
    for name, r in ex.results.items():
        state[name] = _sds(one_chip, (), r.dtype)
    fn = ex._build_fused_fn(dict(ex.plan.initial))
    text = fn.jit_fn.lower(
        *ex._split_state(state, fn.donate_keys, fn.read_only),
        _sds(one_chip, (), jnp.int32)).compile().as_text()
    lines = [line.strip() for line in text.splitlines()]
    updates = [line for line in lines if re.match(FORCE_UPDATE_KERNEL, line)]
    assert len(updates) == 2, [l for l in lines if "custom_call" in l]

    copy = ripple_host_snapshot.lower(
        [state["u"]], (True,)).compile().as_text()
    calls = [line.strip() for line in copy.splitlines()
             if re.match(SNAPSHOT_COPY, line.strip())]
    assert len(calls) == 1, copy
    assert "host_snapshot" in calls[0].split("metadata=")[-1]
