"""Smoke run of Ripple's main paths on a TPU, through the entry points a
user calls.

  python chip_smoke.py               # one chip: phases ripple and serve
  python chip_smoke.py --four-chips  # Euler halo exchange on a 2x2 mesh

Phase ``ripple`` runs the paper's kernel graphs through ``Executor`` at
Table 2-5 sizes with the Pallas kernels (``use_pallas=True``), checks
each against the same graph on the XLA reference path, then runs 20
steps of the Euler shock-bubble solver.  Phase ``serve`` serves
qwen1.5-4b at its published widths (40 layers, bf16, random weights from
``--seed``) through ``Batcher`` and checks it against the legacy jit
loop.  ``--four-chips`` runs only the multi-chip path: Euler 2048x2048
split over a 2x2 mesh with overlapped halo exchange, against the same
graph on one device.

Every check raises on failure.  Times printed here are smoke timings
(the first call includes compilation), not benchmark results.  The last
line of standard output is one JSON object naming the device; the script
exits non-zero, before printing it, when JAX finds no TPU.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# paper sizes: Table 2 SAXPY 100M, Table 3 particles 10M (rounded to
# 10*2^20 so the 512-particle block tiles it), Tables 4/5 at 2048^2
SAXPY_N = 100 << 20
PARTICLE_N = 10 << 20
GRID = 2048
EULER_SHAPE = (2048, 1024)
EULER_STEPS = 20
FOUR_CHIP_SHAPE = (2048, 2048)
FOUR_CHIP_STEPS = 10

# tolerances of the Pallas graphs against the XLA reference graphs, as a
# fraction of the reference's largest magnitude (float32 throughout:
# Mosaic and XLA may fuse and round differently, by a few ulp)
KERNEL_RTOL = 1e-5
MASS_DRIFT_MAX = 1e-5
# serve: bf16 prefill logits, as a fraction of the largest legacy logit,
# and the legacy top-2 gap above which greedy tokens must agree
LOGIT_RTOL = 5e-2
TOKEN_MARGIN = 0.5
# a compiled Pallas kernel shows in a TPU executable's HLO as this op
KERNEL_MARKER = "tpu_custom_call"


def log(msg: str) -> None:
    print(msg, flush=True)


def _rel_diff(a, b) -> tuple[float, float]:
    import jax.numpy as jnp

    a = jnp.asarray(a, jnp.float32)
    b = jnp.asarray(b, jnp.float32)
    return (float(jnp.max(jnp.abs(a - b))),
            max(float(jnp.max(jnp.abs(b))), 1.0))


# -- phase ripple --------------------------------------------------------------

def _kernel_graph(name: str, build, make_inputs, out_key: str,
                  steady_calls: int = 3) -> None:
    """Run ``build(use_pallas)`` through ``Executor`` on both paths from the
    same inputs, compare ``out_key`` after one call, and time the Pallas
    graph (first call, then steady calls)."""
    import jax

    from repro.core import Executor

    ref_ex = Executor(build(False))
    ref = ref_ex(ref_ex.init_state(**make_inputs()))[out_key]

    ex = Executor(build(True))
    state = ex.init_state(**make_inputs())
    t0 = time.perf_counter()
    state = ex(state)
    jax.block_until_ready(state[out_key])
    first = time.perf_counter() - t0
    diff, scale = _rel_diff(state[out_key], ref)
    del ref
    hlo = ex.region_hlo(state)
    steady = []
    for _ in range(steady_calls):
        t0 = time.perf_counter()
        state = ex(state)
        jax.block_until_ready(state[out_key])
        steady.append(time.perf_counter() - t0)
    custom = KERNEL_MARKER in hlo
    log(f"[ripple] {name}: max |pallas - reference| = {diff:.3e} "
        f"(tolerance {KERNEL_RTOL * scale:.3e}); tpu_custom_call in "
        f"region HLO: {custom}")
    log(f"[ripple] {name}: smoke timing, not a benchmark: first call "
        f"{first:.3f} s, steady {min(steady):.6f} s/call")
    if not custom:
        raise AssertionError(f"{name}: no Pallas kernel in the region HLO")
    if not diff <= KERNEL_RTOL * scale:
        raise AssertionError(f"{name}: pallas differs from the reference "
                             f"by {diff} (scale {scale})")


def _saxpy(seed: int, n: int) -> None:
    import jax

    from repro.core import DistTensor, Graph, Layout, RecordArray
    from repro.kernels.saxpy.kernel import SAXPY_SPEC
    from repro.kernels.saxpy.ops import saxpy_record

    def build(use_pallas):
        r = DistTensor("r", (n,), spec=SAXPY_SPEC, layout=Layout.SOA)
        g = Graph(name="saxpy_record")
        g.split(lambda rec: saxpy_record(rec, 2.0, use_pallas=use_pallas),
                r, writes=(0,))
        return g

    def inputs():
        data = jax.random.normal(jax.random.PRNGKey(seed), (2, n))
        return {"r": RecordArray(data, SAXPY_SPEC, Layout.SOA)}

    _kernel_graph(f"saxpy-record n={n} SoA", build, inputs, "r")


def _particles(seed: int, n: int) -> None:
    import jax

    from repro.core import DistTensor, Graph, Layout, RecordArray
    from repro.kernels.particle.kernel import PARTICLE_SPEC
    from repro.kernels.particle.ops import particle_update

    def build(use_pallas):
        p = DistTensor("p", (n,), spec=PARTICLE_SPEC, layout=Layout.AOSOA,
                       pin_layout=True)
        g = Graph(name="particles")
        g.split(lambda rec: particle_update(rec, 0.25,
                                            use_pallas=use_pallas),
                p, writes=(0,))
        return g

    def inputs():
        data = jax.random.normal(jax.random.PRNGKey(seed + 1), (6, n))
        soa = RecordArray(data, PARTICLE_SPEC, Layout.SOA)
        return {"p": soa.with_layout(Layout.AOSOA)}

    _kernel_graph(f"particles n={n} AoSoA", build, inputs, "p")


def _flux(n: int) -> None:
    from repro.core import DistTensor, Layout, RecordArray
    from repro.kernels.stencil.ops import make_flux_difference_graph
    from repro.physics.euler import EULER_SPEC, shock_bubble_init

    def build(use_pallas):
        u = DistTensor("u", (n, n), spec=EULER_SPEC, layout=Layout.SOA,
                       halo=(1, 1))
        out = DistTensor("flux", (n, n), spec=EULER_SPEC, layout=Layout.SOA)
        return make_flux_difference_graph(u, out, 0.1, 0.1, overlap=False,
                                          use_pallas=use_pallas)

    def inputs():
        return {"u": RecordArray(shock_bubble_init(n, n), EULER_SPEC,
                                 Layout.SOA)}

    _kernel_graph(f"FORCE flux {n}x{n} SoA", build, inputs, "flux")


def _eikonal(seed: int, n: int) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core import DistTensor
    from repro.kernels.eikonal.ops import make_eikonal_graph

    def build(use_pallas):
        phi = DistTensor("phi", (n, n), halo=(1, 1))
        mask = DistTensor("mask", (n, n), dtype=jnp.bool_)
        return make_eikonal_graph(phi, mask, 1.0 / n, overlap=False,
                                  use_pallas=use_pallas)

    def inputs():
        k1, k2 = jax.random.split(jax.random.PRNGKey(seed + 2))
        mask = jax.random.uniform(k1, (n, n)) < 0.01
        phi = jnp.where(mask, 0.0, jax.random.uniform(k2, (n, n)))
        return {"phi": phi, "mask": mask}

    _kernel_graph(f"eikonal FIM sweep {n}x{n}", build, inputs, "phi")


def _euler(nx: int, ny: int, steps: int) -> None:
    import jax.numpy as jnp
    import numpy as np

    from euler2d import build_solver
    from repro.physics.euler import MX, RHO, pressure, shock_bubble_init

    dx, dy = 2.0 / nx, 1.0 / ny
    cfl = 0.4
    ex, _ = build_solver(nx, ny)
    u0 = shock_bubble_init(nx, ny)
    mass0 = float(np.sum(np.asarray(u0[RHO]), dtype=np.float64)) * dx * dy
    # transmissive boundaries: the only mass flux through the boundary is
    # the uniform post-shock inflow on the left edge, rho*u per unit length
    inflow = float(u0[MX, 0, 0]) * ny * dy
    state = ex.init_state(u=u0)
    t_total, t0 = 0.0, time.perf_counter()
    for step in range(steps):
        state = ex(state)
        # the step's dt, from the max-wavespeed reduction it ran with
        t_total += cfl * min(dx, dy) / float(state["smax"])
        if step == 0:
            first = time.perf_counter() - t0
            t0 = time.perf_counter()
    wall = time.perf_counter() - t0
    u = np.asarray(state["u"])
    mass = float(np.sum(u[RHO], dtype=np.float64)) * dx * dy
    drift = abs(mass - mass0 - inflow * t_total) / mass0
    log(f"[ripple] euler shock-bubble {nx}x{ny}, {steps} steps: rho in "
        f"[{u[RHO].min():.4f}, {u[RHO].max():.4f}], min pressure "
        f"{float(pressure(jnp.asarray(u)).min()):.4f}, mass drift net of "
        f"the boundary inflow {drift:.3e} (limit {MASS_DRIFT_MAX:.0e})")
    log(f"[ripple] euler: smoke timing, not a benchmark: first step "
        f"{first:.3f} s, then {wall / max(steps - 1, 1):.6f} s/step")
    if not np.isfinite(u).all():
        raise AssertionError("euler: non-finite state")
    if not (u[RHO] > 0).all() or not (np.asarray(pressure(u)) > 0).all():
        raise AssertionError("euler: non-positive density or pressure")
    if not drift < MASS_DRIFT_MAX:
        raise AssertionError(f"euler: mass drift {drift}")


def ripple_phase(seed: int, *, saxpy_n: int = SAXPY_N,
                 particle_n: int = PARTICLE_N, grid: int = GRID,
                 euler_shape: tuple = EULER_SHAPE,
                 euler_steps: int = EULER_STEPS) -> None:
    """The paper's kernel graphs with Pallas kernels vs the reference
    graphs, then the Euler solver."""
    _saxpy(seed, saxpy_n)
    _particles(seed, particle_n)
    _flux(grid)
    _eikonal(seed, grid)
    _euler(*euler_shape, euler_steps)
    gc.collect()


# -- phase serve ---------------------------------------------------------------

def serve_phase(cfg, seed: int, *, n_requests: int = 4,
                prompt_len: int = 128, gen: int = 16) -> None:
    """Serve ``n_requests`` equal-length prompts through ``Batcher`` and
    check prefill logits, greedy tokens and the trace count against the
    legacy jit loop."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import Executor
    from repro.launch import steps as S
    from repro.launch.serve import legacy_generate
    from repro.models.blocks import ShardCtx
    from repro.models.lm import init_lm, lm_logits
    from repro.runtime.batcher import Batcher

    t0 = time.perf_counter()
    params, _ = init_lm(cfg, jax.random.PRNGKey(seed), tp=1)
    jax.block_until_ready(params)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    log(f"[serve] {cfg.name}: {cfg.n_layers} layers, d_model "
        f"{cfg.d_model}, {n_params / 1e9:.3f} B params in "
        f"{cfg.param_dtype}; init {time.perf_counter() - t0:.1f} s")
    max_seq = prompt_len + gen
    rng = np.random.default_rng(seed)
    prompts = rng.integers(0, cfg.vocab_size,
                           (n_requests, prompt_len)).astype(np.int32)

    t0 = time.perf_counter()
    batcher = Batcher(cfg, params, batch=n_requests, max_seq=max_seq,
                      log=log)
    reqs = [batcher.submit(p, max_new_tokens=gen) for p in prompts]
    batcher.run()
    t_serve = time.perf_counter() - t0
    tokens = np.stack([r.generated for r in reqs])
    traces = batcher.cache_stats()["decode"]["trace_events"]
    log(f"[serve] Batcher: {n_requests} requests x {gen} tokens in "
        f"{batcher.steps} decode steps; smoke timing, not a benchmark: "
        f"{t_serve:.2f} s including compilation; decode traces {traces}")

    # ripple prefill logits: the prefill graph's last hidden state through
    # the model's head (the graph itself keeps only the argmax)
    pg = S.make_prefill_graph(cfg, params, prompt_len=prompt_len,
                              max_seq=max_seq)
    pex = Executor(pg.graph)
    head = jax.jit(lambda p, h: lm_logits(p, h, cfg, ShardCtx()))
    ripple_logits = []
    for p in prompts:
        pst = pex(pex.init_state(prompt=jnp.asarray(p)[None],
                                 **pg.weights.state(params)))
        ripple_logits.append(head(params, pst["hlast"])[0])
    ripple_logits = jnp.stack(ripple_logits)

    legacy = legacy_generate(cfg, params, {"tokens": jnp.asarray(prompts)},
                             gen, max_seq)
    vocab = cfg.vocab_size   # padded vocab entries are masked in both
    diff, scale = _rel_diff(ripple_logits[:, :vocab],
                            legacy.prefill_logits[:, :vocab])
    log(f"[serve] prefill logits: max |ripple - legacy| = {diff:.4f} "
        f"(tolerance {LOGIT_RTOL * scale:.4f})")
    # greedy streams must agree up to each row's first mismatch, and a
    # mismatch is only admissible where legacy's top-2 gap is a near-tie
    agree, ties = 0, []
    for row in range(n_requests):
        for t in range(gen):
            if tokens[row, t] == legacy.tokens[row, t]:
                agree += 1
                continue
            ties.append((row, t, float(legacy.gaps[row, t])))
            break
    log(f"[serve] greedy tokens: {agree}/{tokens.size} agree before any "
        f"divergence; divergences at (row, step, legacy top-2 gap): "
        f"{ties} (margin {TOKEN_MARGIN})")
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    log(f"[serve] device peak_bytes_in_use: {peak}")
    if not diff <= LOGIT_RTOL * scale:
        raise AssertionError(f"serve: prefill logits differ by {diff}")
    bad = [t for t in ties if t[2] > TOKEN_MARGIN]
    if bad:
        raise AssertionError(f"serve: greedy tokens diverge where legacy's "
                             f"top-2 gap exceeds the margin: {bad}")
    if traces != 1:
        raise AssertionError(f"serve: decode traced {traces} times")


# -- four chips ----------------------------------------------------------------

def four_chip_phase(*, shape: tuple = FOUR_CHIP_SHAPE,
                    steps: int = FOUR_CHIP_STEPS) -> None:
    """Euler on a 2x2 mesh with overlapped halo exchange vs one device."""
    import jax
    import numpy as np

    from euler2d import build_solver
    from repro.physics.euler import shock_bubble_init

    nx, ny = shape
    results = {}
    for n_devices in (1, 4):
        ex, _ = build_solver(nx, ny, n_devices=n_devices,
                             px=2 if n_devices > 1 else 1,
                             overlap=n_devices > 1)
        state = ex.init_state(u=shock_bubble_init(nx, ny))
        t0 = time.perf_counter()
        state = ex(state)
        jax.block_until_ready(state["u"])
        first = time.perf_counter() - t0
        t0 = time.perf_counter()
        for _ in range(steps - 1):
            state = ex(state)
        jax.block_until_ready(state["u"])
        wall = time.perf_counter() - t0
        results[n_devices] = np.asarray(state["u"])
        log(f"[four-chips] euler {nx}x{ny} on {n_devices} device(s): "
            f"smoke timing, not a benchmark: first step {first:.3f} s, "
            f"then {wall / max(steps - 1, 1):.6f} s/step")
        if n_devices > 1:
            ht = ex.plan.halo_transfers
            log(f"[four-chips] plan.halo_transfers: {len(ht)} blocks, "
                f"{sum(h.overlapped for h in ht)} overlapped, "
                f"{sum(1 for h in ht if h.mesh_axis)} ppermutes; "
                f"overlap fallbacks {len(ex.plan.overlap_fallbacks)}")
            for h in ht:
                log("[four-chips]   " + h.describe())
    one, four = results[1], results[4]
    diff = float(np.max(np.abs(four - one)))
    scale = max(float(np.max(np.abs(one))), 1.0)
    log(f"[four-chips] max |2x2 mesh - one device| over {steps} steps = "
        f"{diff:.3e} (tolerance {KERNEL_RTOL * scale:.3e})")
    if not np.isfinite(four).all():
        raise AssertionError("four-chips: non-finite state")
    if not diff <= KERNEL_RTOL * scale:
        raise AssertionError(f"four-chips: mesh state differs by {diff}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only Euler on a 2x2 mesh vs one device")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and data")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    want = 4 if args.four_chips else 1
    if devices[0].platform != "tpu" or len(devices) < want:
        print(f"chip_smoke: needs {want} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT / "src"), str(ROOT / "examples")]
    from repro import configs
    from repro.compile_cache import enable_compile_cache

    log(f"[chip_smoke] compile cache: {enable_compile_cache()}")
    log(f"[chip_smoke] {len(devices)} x {devices[0].device_kind}")
    t0 = time.perf_counter()
    if args.four_chips:
        four_chip_phase()
    else:
        ripple_phase(args.seed)
        log(f"[chip_smoke] phase ripple passed "
            f"({time.perf_counter() - t0:.1f} s)")
        serve_phase(configs.get("qwen1.5-4b"), args.seed)
        log(f"[chip_smoke] phase serve passed "
            f"({time.perf_counter() - t0:.1f} s)")
    dev = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
