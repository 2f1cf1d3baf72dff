"""Euler shock-bubble with periodic host snapshots (Ripple paper section
8's problem, with the host nodes of section 5.3.4) for the benchmark.

The graph is ``euler_sb``'s step, imported unchanged with its data and
its plain reference, beside one host node at the first level: every
``snapshot_every`` passes of a call (passes 0, k, 2k, ...) it receives
the state ``u``, keeps it in a two-slot host ring for output, and
computes in-situ diagnostics in float64: total mass, x and y momentum
and energy, and the smallest density and pressure (a positivity check).
It writes no file.  ``Executor.run`` runs the passes between snapshots
as fused chunks of the device-only step and hands each due node one
double-buffer copy of ``u``.

The check, after the window with the state freed:

- ``snapshot_rel_err``: every snapshot of set-up's first call (steps 0
  and k of a 2k-step call) against the float32 reference at that step,
  by ``euler_sb``'s comparison (worst field's max |snapshot - reference|
  over its max |reference|);
- ``snapshot_torn``: the snapshot taken at pass 0 of set-up's second
  call against the state that set-up's first call returned, read back
  before the second call: max |difference|, which only a torn double
  buffer can move off 0;
- ``returned_rel_err``: that returned state against the float32
  reference at the call's last step, by the same comparison as
  ``snapshot_rel_err``: the chunk after the last snapshot ran its steps
  from that snapshot's state.

A missing snapshot or state reads NaN.  The reference runs once, on from
one compared step to the next.  Readings and limits: PERF.md.
"""

from __future__ import annotations

import importlib.util
import os
import sys
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np


def _sibling(name: str):
    """The model file ``name`` beside this one, loaded once."""
    key = f"bench_model_{name}"
    if key not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            key, Path(__file__).with_name(f"{name}.py"))
        sys.modules[key] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sys.modules[key])
    return sys.modules[key]


euler_sb = _sibling("euler_sb")

SNAPSHOT_LIMIT = euler_sb.STATE_LIMIT
TORN_LIMIT = 0.0
DIAG_ROWS = 64          # rows of the grid in one block of the diagnostics
DIAG_WORKERS = min(8, os.cpu_count() or 1)   # host threads they run on
TOTALS = ("mass", "energy", "momentum_x", "momentum_y")   # u's fields


# -- the host node ----------------------------------------------------------------

def _block_diagnostics(u: np.ndarray, r0: int) -> tuple:
    """Rows ``r0`` to ``r0 + DIAG_ROWS`` of a snapshot: the four fields'
    float64 sums, the smallest density, and the smallest E minus the
    kinetic energy, computed in float64."""
    rho, e, mu, mv = (u[i, r0:r0 + DIAG_ROWS] for i in range(4))
    sums = [np.sum(x, dtype=np.float64) for x in (rho, e, mu, mv)]
    internal = np.multiply(mu, mu, dtype=np.float64)
    internal += np.multiply(mv, mv, dtype=np.float64)
    internal *= 0.5
    np.divide(internal, rho, out=internal)
    np.subtract(e, internal, out=internal)
    return sums, float(rho.min()), float(internal.min())


def diagnostics(u: np.ndarray, gamma: float, area: float, pool) -> dict:
    """In-situ diagnostics of one snapshot ``(4, nx, ny)`` (rho, E, rho u,
    rho v), in float64 blocks of rows on the threads of ``pool``: each
    conserved total (a sum times the cell area) and the smallest density
    and pressure, p = (gamma - 1) (E - (rho u^2 + rho v^2) / (2 rho))."""
    blocks = list(pool.map(partial(_block_diagnostics, u),
                           range(0, u.shape[1], DIAG_ROWS)))
    sums = np.sum([b[0] for b in blocks], axis=0)
    out = {name: float(t) * area for name, t in zip(TOTALS, sums)}
    out["rho_min"] = min(b[1] for b in blocks)
    out["p_min"] = (gamma - 1.0) * min(b[2] for b in blocks)
    return out


class HostRing:
    """The host side of the snapshot node: the two newest snapshots, each
    numbered in the order taken, and how many snapshots failed the
    positivity check."""

    def __init__(self, cfg: dict):
        self.gamma = cfg["gamma"]
        self.area = (cfg["domain"][0] / cfg["nx"]) * (cfg["domain"][1]
                                                      / cfg["ny"])
        self.slots: deque = deque(maxlen=2)
        self.taken = 0
        self.nonpositive = 0    # snapshots whose rho_min or p_min is <= 0
        self.pool = ThreadPoolExecutor(DIAG_WORKERS,
                                       thread_name_prefix="snapshot-diag")

    def snapshot(self, rec) -> None:
        """The host node: the state into the ring, then its diagnostics."""
        u = np.asarray(rec.data)
        self.slots.append((self.taken, u))
        self.taken += 1
        d = diagnostics(u, self.gamma, self.area, self.pool)
        if not (d["rho_min"] > 0 and d["p_min"] > 0):
            self.nonpositive += 1

    def close(self) -> None:
        """Stop the diagnostics' threads."""
        self.pool.shutdown()

    def since(self, mark: int) -> list[np.ndarray]:
        """The snapshots numbered ``mark`` and after that the ring still
        holds, in order."""
        return [u for j, u in self.slots if j >= mark]


def build_solver(cfg: dict, ring: HostRing):
    """``euler_sb``'s step graph with the snapshot node beside it at the
    first level, reading ``u`` every ``snapshot_every`` passes.  Returns
    the ``Executor``."""
    from repro.core import ExecutionKind, Executor, Graph

    step = euler_sb.build_solver(cfg).graph
    g = Graph(name="euler_snapshot")
    g.emplace(ring.snapshot, exec_kind=ExecutionKind.Cpu,
              args=(step.all_tensors()["u"],), every=cfg["snapshot_every"])
    g.emplace(step)
    return Executor(g)


# -- the comparison --------------------------------------------------------------

def references(cfg: dict, seed: int, steps: list[int], dtype=jnp.float32):
    """``(step, state)`` of ``euler_sb``'s plain reference in ``dtype`` at
    each of ``steps`` (ascending), each run on from the one before."""
    geom = (cfg["domain"][0] / cfg["nx"], cfg["domain"][1] / cfg["ny"],
            cfg["cfl"], cfg["gamma"])
    u, at = euler_sb.initial_state(cfg, seed).astype(dtype), 0
    for s in steps:
        u, at = euler_sb.reference_steps(u, geom, s - at), s
        yield s, u


def rel_err(prog, ref) -> float:
    """``euler_sb``'s comparison: the worst field's max |prog - ref| over
    its max |ref|; NaN where there is no ``prog``."""
    if prog is None:
        return float("nan")
    prog = jax.device_put(prog, jax.devices()[0])
    return float(jnp.max(euler_sb._field_errors(prog, ref)))


# -- the session the harness drives ---------------------------------------------

class Session:
    """One run of a cell: ``Session(...)`` is the set-up, ``call`` one
    call of the window, ``check`` the comparison after it."""

    def __init__(self, cfg: dict, mix: dict, seed: int, spans, stream):
        if cfg["mesh"] is not None:
            raise ValueError("the snapshot cell runs on one chip")
        self.cfg, self.mix, self.seed, self.spans = cfg, mix, seed, spans
        self.every = cfg["snapshot_every"]
        self.ring = HostRing(cfg)
        self.ex = build_solver(cfg, self.ring)
        self.state = self.ex.init_state(u=euler_sb.initial_state(cfg, seed))
        self.steps = self.calls = 0
        self.first: dict = {}       # set-up's first call: step -> snapshot
        self.returned = None        # what that call returned, read back
        self.torn = None            # set-up's second call, pass 0
        self.call(next(stream))     # compiles, or loads from the cache

    def call(self, item: dict) -> dict:
        """``Executor.run`` for ``item["steps"]`` steps, then the host
        readback of the diagnostics.  A call that raises counts as failed
        and leaves no state for later calls."""
        steps = item["steps"]
        if self.state is None:
            return {"steps": 0, "snapshots": 0, "failed": 1}
        if self.calls == 1:
            self.returned = np.asarray(self.state["u"])
        mark, bad = self.ring.taken, self.ring.nonpositive
        try:
            with self.spans("run_call"):
                self.state = self.ex.run(self.state, steps=steps)
            with self.spans("diag_readback"):
                diag = [float(self.state[k]) for k in self.mix["diagnostics"]]
        except Exception as exc:    # a failed call is counted, not fatal
            print(f"bench: call {self.calls} failed: {exc!r}",
                  file=sys.stderr)
            self.state = None
            return {"steps": 0, "snapshots": 0, "failed": 1}
        taken = self.ring.since(mark)
        if self.calls == 0:
            self.first = {self.steps + i * self.every: u
                          for i, u in enumerate(taken)}
        elif self.calls == 1 and taken:
            self.torn = taken[0]
        self.steps += steps
        self.calls += 1
        ok = np.isfinite(diag).all() and self.ring.nonpositive == bad
        return {"steps": steps, "snapshots": self.ring.taken - mark,
                "failed": int(not ok)}

    def check(self) -> list[tuple[str, float, float]]:
        """Free the program's state, then compare the snapshots and the
        returned state with the reference, and the second call's first
        snapshot with the state read back: ``[(name, value, limit)]``."""
        self.state = self.ex = None
        self.ring.close()
        last = self.mix["steps_per_call"]
        steps = list(range(0, last, self.every)) + [last]
        progs = [self.first.get(s) for s in steps[:-1]] + [self.returned]
        errs = [rel_err(prog, ref) for prog, (_, ref) in
                zip(progs, references(self.cfg, self.seed, steps))]
        if self.torn is None or self.returned is None:
            torn = float("nan")
        else:
            torn = float(np.max(np.abs(self.torn - self.returned)))
        return [("snapshot_rel_err", float(np.max(errs[:-1])),
                 SNAPSHOT_LIMIT),
                ("snapshot_torn", torn, TORN_LIMIT),
                ("returned_rel_err", errs[-1], SNAPSHOT_LIMIT)]


def control(cfg: dict, mix: dict, seed: int, dtype) -> list[tuple[str, float]]:
    """The control: the reference in ``dtype`` put in the place of the
    snapshots and of the returned state, read by the same comparisons (a
    reference has no double buffer to tear, so ``snapshot_torn`` has no
    reading)."""
    last = mix["steps_per_call"]
    steps = list(range(0, last, cfg["snapshot_every"])) + [last]
    errs = [rel_err(low, ref) for (_, ref), (_, low) in
            zip(references(cfg, seed, steps),
                references(cfg, seed, steps, dtype))]
    return [("snapshot_rel_err", float(np.max(errs[:-1]))),
            ("returned_rel_err", errs[-1])]
