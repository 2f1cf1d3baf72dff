"""Async-region overlap gain: host callbacks hidden behind device work.

The event-driven executor (``Executor(async_regions=True)``, the
default) submits host-callback regions to a worker pool and keeps
dispatching device regions instead of blocking on each callback.  On a
host-callback-interleaved chain whose host time per step is calibrated
to roughly equal its device time per step, the sync path pays
``device + host`` per step while the async path pays ``max(device,
host)`` — a ~2x headroom, gated here at >= 1.3x.

This is the BENCH_7 perf-smoke gate (hard asserts, see ``main``):

* async steady-state per-step >= ``min_speedup`` x faster than
  ``async_regions=False`` on the same graph over the 8-device CPU mesh;
* async and sync final states are BITWISE equal (same cached
  executables, same device dispatch order — the async runtime may only
  move *host* work, never change values).

Runs in a subprocess (fig13 idiom) so the 8-virtual-device XLA flag is
set before jax imports regardless of what ``benchmarks.run`` already
imported.

  PYTHONPATH=src python -m benchmarks.overlap_gain [--json BENCH_7.json]
"""

import argparse
import json
import os
import subprocess
import sys
import time

from .common import Csv

_CHILD = r"""
import os, sys, json, time
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")
import jax, jax.numpy as jnp, numpy as np
from repro.core import (DistTensor, Executor, ExecutionKind, Graph,
                        make_mesh)

K_HOST = 4          # host callbacks interleaved per step
N = 1 << 22         # f32 elements, sharded 8 ways
STEPS = 12
SLEEP_MS = [0.0]    # mutable so calibration does not change the graph


def _bump(r):
    # enough flops per segment that device time is measurable on CPU
    return r * 1.0001 + jnp.sin(r) * 1e-3


def _probe(r, m):
    return m + jnp.mean(r[: 1024])[None]


def _host_read(m):
    # read via numpy, NOT an eager jnp op: eager ops enqueue a device
    # computation BEHIND everything already dispatched, which would
    # serialize the callback with the whole in-flight frontier
    float(np.asarray(m)[0])
    time.sleep(SLEEP_MS[0] * 1e-3)    # stand-in for logging/metrics IO


def build():
    mesh = make_mesh((8,), ("d",))
    r = DistTensor("r", (N,), partition=("d",))
    m = DistTensor("m", (1,))
    g = Graph(name="overlap-chain")
    for _ in range(K_HOST):
        g.then_split(_bump, r, writes=(0,))
        g.then_split(_probe, r, m, writes=(1,))
        g.then(_host_read, exec_kind=ExecutionKind.Cpu, args=(m,))
    return g, mesh


def bench(async_regions, steps=STEPS):
    g, mesh = build()
    ex = Executor(g, mesh=mesh, donate=False, async_regions=async_regions)
    st = ex.run(ex.init_state(), 2)   # warm: trace/compile + entry layouts
    jax.block_until_ready(jax.tree.leaves(st))
    t0 = time.perf_counter()
    st = ex.run(st, steps)
    jax.block_until_ready(jax.tree.leaves(st))
    return (time.perf_counter() - t0) / steps * 1e3, ex


# calibrate: host work per step ~= device work per step — the point of
# maximum headroom (sync pays 2x device, async ~1x device + overhead)
device_ms, _ = bench(False)
SLEEP_MS[0] = max(device_ms / K_HOST, 0.2)

sync_ms, _ = bench(False)
async_ms, _ = bench(True)

# bitwise equality: identical step counts from identical init
outs = {}
for mode in (False, True):
    g, mesh = build()
    ex = Executor(g, mesh=mesh, donate=False, async_regions=mode)
    st = ex.run(ex.init_state(), 3)
    jax.block_until_ready(jax.tree.leaves(st))
    outs[mode] = {k: np.asarray(v) for k, v in st.items()}
for k in outs[False]:
    np.testing.assert_array_equal(outs[True][k], outs[False][k],
                                  err_msg=f"async != sync on {k!r}")

print("JSON" + json.dumps(dict(
    n_devices=jax.device_count(), n=N, k_host=K_HOST, steps=STEPS,
    device_ms_per_step=device_ms, sleep_ms_per_cb=SLEEP_MS[0],
    sync_ms_per_step=sync_ms, async_ms_per_step=async_ms,
    speedup=sync_ms / max(async_ms, 1e-9), bitwise_equal=True)))
"""


def main(min_speedup: float = 1.3, json_path=None) -> list[dict]:
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    # virtual CPU devices by design: the child never contends for a chip
    # that this (parent) process may hold
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                         capture_output=True, text=True, timeout=1800)
    if res.returncode != 0:
        print(res.stdout)
        print(res.stderr)
        raise RuntimeError("overlap_gain child failed")
    r = json.loads(res.stdout.split("JSON", 1)[1])
    csv = Csv("devices", "host_cbs_per_step", "device_ms_per_step",
              "sleep_ms_per_cb", "sync_ms_per_step", "async_ms_per_step",
              "speedup", "bitwise_equal")
    csv.row(r["n_devices"], r["k_host"], r["device_ms_per_step"],
            r["sleep_ms_per_cb"], r["sync_ms_per_step"],
            r["async_ms_per_step"], r["speedup"], r["bitwise_equal"])
    if json_path:
        with open(json_path, "w") as fh:
            json.dump(dict(r, min_speedup=min_speedup,
                           unix_time=time.time()), fh, indent=2)
        print(f"[overlap_gain] wrote {json_path}")
    # hard gates (CI perf-smoke): the async runtime must actually hide
    # host time, and must never change values
    assert r["bitwise_equal"], "async/sync state mismatch"
    assert r["speedup"] >= min_speedup, (
        f"async overlap gain {r['speedup']:.2f}x < {min_speedup}x "
        f"(sync {r['sync_ms_per_step']:.2f}ms, "
        f"async {r['async_ms_per_step']:.2f}ms)")
    return csv.dicts()


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", metavar="PATH", default=None)
    ap.add_argument("--min-speedup", type=float, default=1.3)
    args = ap.parse_args()
    try:
        main(min_speedup=args.min_speedup, json_path=args.json)
    except AssertionError as exc:
        print(f"[overlap_gain] FAILED: {exc}", file=sys.stderr)
        sys.exit(1)
