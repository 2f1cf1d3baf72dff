"""Graph executor (paper §6) — compiles a Ripple Graph to jitted SPMD code.

The paper schedules graph nodes dynamically with a heterogeneous
work-stealing pool.  Under SPMD/XLA that role collapses into *lowering
decisions* (DESIGN.md §2/§4), which this executor makes explicitly:

* graph nodes are scheduled from their real data dependencies
  (``core/schedule.py``): the dependency DAG's antichains of independent
  device nodes fuse into shared waves and consecutive waves into one jit
  *segment*, so XLA's latency-hiding scheduler can overlap independent
  nodes, their collectives, and compute across the paper's level
  boundaries (the paper's "compact GPU pipelines");
  ``Executor(schedule="sequential")`` is the legacy program-order
  lowering, and ``Executor.plan.describe_dag()`` renders the DAG, its
  segment/wave placement, and the transfers hoisted to segment entries;
* a **region compiler** sits on top of the segment schedule (paper §5.3:
  graphs are built once, executed many): maximal runs of consecutive
  device / device-loop segments are grouped into *regions*
  (``core/schedule.py``'s ``group_regions``), each region lowers to ONE
  jitted program — the boundary relayout steps and halo assembly are
  traced inside it as pure functions (``core/layout.py``'s
  ``relayout_data``, ``core/halo.py``'s exchange/assembly) instead of
  being dispatched eagerly from Python between segment calls — and
  compiled regions live in a process-wide executable cache keyed by a
  structural *plan signature* (graph structure × shapes/dtypes × layouts
  × mesh × schedule mode × donation), so a re-instantiated ``Executor``
  over an identical graph (the serving pattern) reuses the compiled
  executables with zero new traces.  ``run(steps)`` is retrace-free: the
  fused fori fast path takes ``steps`` as a dynamic argument (distinct
  step counts share one trace) and the non-fused path loops over cached
  region executables with no eager relayout dispatch while consecutive
  iterations agree on layout.  On the chip the benchmark's
  ``dispatch_ms.euler`` reads the host time of each executable call (the
  ``ripple.dispatch`` spans, see ``repro.tracing``);
* a segment with partitioned tensors is lowered through one ``shard_map``
  — the paper's one-node-per-partition becomes one program per shard;
* ``concurrent_padded_access`` + ``overlap=True`` splits the stencil into
  interior/boundary programs so the halo ppermutes fly during interior
  compute (paper Fig. 7) — for any number of mesh-partitioned halo axes
  and padded args: all edge strips are sent up front, corner blocks ride
  the two-phase extended-edge exchange, and one boundary program per
  (axis, side) consumes them (``core/halo.py``'s transfer schedule);
  ``Executor.plan.halo_transfers`` lists the scheduled blocks per segment
  and ``plan.overlap_fallbacks`` every declined overlap request (the
  genuinely-degraded ones also warn once);
* ``exclusive_padded_access`` captures the pre-update halo first and
  threads it as a data dependency (paper Fig. 9's extra edges);
* host (Cpu) nodes and ``sync()`` break segments — the host work runs
  between jit calls (heterogeneous execution).  By default the region
  loop is **event-driven** (``async_regions=True``): device regions are
  dispatched without blocking (JAX dispatch is already asynchronous),
  host callbacks run on a shared ``ThreadPoolExecutor`` as futures so
  only true data dependents wait on them, and when donation is on each
  callback reads a device-side snapshot of its arguments (double
  buffering: step N+1's relayouts/halo sends may overwrite the donated
  buffers while step N's callback still reads).  Barrier regions
  (``sync()``, opaque callbacks) and ``host_loop`` regions drain the
  in-flight callbacks first; ``run()``/``__call__`` drain before
  returning, re-raising the FIRST callback exception in program order
  and cancelling its successors.  ``Executor(async_regions=False)`` is
  the synchronous escape hatch (bitwise-identical results);
  ``core/schedule.py``'s ``region_dag``/``region_waves`` give regions —
  not just nodes — explicit dependencies, rendered by
  ``plan.describe()`` as ready waves;
* a host node with a period (``every=k`` where it is added) runs on the
  passes ``i`` of a ``run()`` with ``i % k == 0``.  Where every host node
  is such a node and reads only what its pass starts with (the
  scheduler's ``ScheduleDag.periodic_hosts``), ``run()`` takes the
  ``fused+host`` path: the passes between due passes run as chunks of
  the fused fori_loop executable (one trace for every chunk length), and
  each due node gets the state between two chunks, through the pool
  with one double-buffer copy (``ripple_host_snapshot``) under donation;
* a graph with ``conditional`` becomes a ``lax.while_loop`` (device) or a
  host do/while (if it contains host nodes); device loops trace straight
  into their enclosing region, host loops run a cached sub-``Executor``;
* state buffers are donated to each region call (the paper's
  allocator-reuse, C6): steps update state in place — only buffers whose
  layout (hence shape) is stable across the region are donated, so XLA
  can actually alias them;
* a **layout solver** (paper §4.2's polymorphic layout made a compiler
  decision) assigns each record tensor a storage layout *per jit segment*:
  a user pin (``DistTensor.pin_layout``) is always honored, a node-level
  preference (``preferred_layout`` / ``layout=`` on graph methods) is
  honored next, padded (halo) access clamps AoSoA back to a per-axis
  layout, and otherwise the declared layout stands.  Where the producing
  and consuming segments disagree, the executor inserts an explicit
  relayout step at the segment boundary (``LayoutPlan.relayouts`` lists
  them for introspection).  Outside a call, every state dict is kept in
  the plan's *initial* layouts (the trailing conversions are undone on
  exit), so state dicts are interchangeable between calls and re-inits.
  Device-only graphs always collapse into a single jit segment, so the
  layout choice is naturally uniform there — layout changes never happen
  inside a jitted loop body.
"""

from __future__ import annotations

import enum as enum_lib
import functools
import hashlib
import math
import re
import sys
import threading
import types
import warnings
from concurrent.futures import ThreadPoolExecutor, \
    TimeoutError as FuturesTimeout
from contextlib import contextmanager
from dataclasses import dataclass, field as dfield
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from .. import kernels as kernels_lib
from .. import tracing
from ..runtime.faults import (HostTimeoutError, TransientError,
                              trip as _fault_trip)
from ..tuning.tiles import tile_scope
from . import halo as halo_lib
from . import schedule as schedule_lib
from .graph import AccessMode, Graph, Node, TensorArg
from .layout import (Layout, RecordArray, relayout, relayout_data,
                     storage_candidates)
from .schedule import Region, ScheduleDag
from .tensor import DistTensor, ReductionResult

__all__ = ["Executor", "execute", "make_mesh", "LayoutPlan", "RelayoutStep",
           "HaloTransfer", "OverlapFallback", "DegradationEvent",
           "HostTimeoutError", "solve_layouts",
           "layout_candidates", "plan_signature", "ExecutableCacheEntry",
           "clear_executable_cache", "executable_cache_stats"]

def make_mesh(shape, axis_names) -> Mesh:
    """``jax.make_mesh`` with Auto axis types over the first
    ``prod(shape)`` devices."""
    return jax.make_mesh(tuple(shape), tuple(axis_names),
                         axis_types=(AxisType.Auto,) * len(axis_names))


@dataclass
class _HaloEntry:
    dim: int
    storage_axis: int
    width: int
    mesh_axis: Optional[str]  # None -> boundary-pad only


def _halo_plan(t: DistTensor, mesh: Optional[Mesh]) -> list[_HaloEntry]:
    plan = []
    for d, w in enumerate(t.halo):
        if w == 0:
            continue
        ax = t.partition[d]
        if mesh is None or ax is None or mesh.shape[ax] == 1:
            plan.append(_HaloEntry(d, t.storage_axis(d), w, None))
        else:
            plan.append(_HaloEntry(d, t.storage_axis(d), w, ax))
    return plan


def _halo_axes(entries: list[_HaloEntry]) -> list[halo_lib.HaloAxis]:
    return [halo_lib.HaloAxis(e.storage_axis, e.width, e.mesh_axis)
            for e in entries]


def _apply_halo(data: jax.Array, t: DistTensor, mesh: Optional[Mesh]) -> jax.Array:
    """Extend a shard by all its halos through the transfer schedule: all
    axes' edge strips are sent up front, corner blocks ride the two-phase
    extended-edge exchange (value-equal to the old sequential per-axis
    exchange->concatenate chain, but nothing serializes on compute)."""
    entries = _halo_plan(t, mesh)
    if not entries:
        return data
    with jax.named_scope("halo_exchange"):
        return halo_lib.exchange_multi(
            data, _halo_axes(entries),
            boundary=t.boundary, constant=t.boundary_constant)


def _slice(x, axis, start, size):
    idx = [slice(None)] * x.ndim
    idx[axis] = slice(start, start + size)
    return x[tuple(idx)]


def _shard_storage_shape(t: DistTensor,
                         mesh: Optional[Mesh]) -> tuple[int, ...]:
    """Per-shard storage shape of ``t``'s state entry (for transfer-block
    byte accounting)."""
    space = t.space if mesh is None else t.shard_space(mesh)
    if not t.is_record:
        return space
    return RecordArray.storage_shape(t.spec, space, t.layout)


# -- event-driven async region runtime ----------------------------------------

class _HostTaskCancelled(Exception):
    """Raised inside a pooled host task whose predecessor failed: the
    task's callback never runs (cancellation cascades down the
    host-order chain) and the drain skips it instead of reporting it."""


_HOST_POOL: Optional[ThreadPoolExecutor] = None
_HOST_POOL_LOCK = threading.Lock()


def _host_pool() -> ThreadPoolExecutor:
    """Process-wide pool for host-node callbacks (lazy singleton — one
    pool for every Executor, so constructing many executors never leaks
    threads).  Deadlock-free by construction: chained tasks only ever
    wait on earlier-submitted tasks, and the pool consumes its queue
    FIFO, so the earliest unfinished task always holds a worker."""
    global _HOST_POOL
    with _HOST_POOL_LOCK:
        if _HOST_POOL is None:
            _HOST_POOL = ThreadPoolExecutor(
                max_workers=4, thread_name_prefix="ripple-host")
        return _HOST_POOL


@partial(jax.jit, static_argnums=(1,))
def ripple_host_snapshot(arrays, kernels):
    """The double-buffer copies of one host node's arguments, as one
    executable; ``kernels[i]`` picks the ``host_snapshot`` DMA kernel
    for ``arrays[i]``."""
    # loaded here, like ``update_dim``'s kernel: only graphs with host
    # nodes under donation pay for the Pallas import
    from ..kernels.snapshot import snapshot_copy

    with jax.named_scope("host_snapshot"):
        return [snapshot_copy(a, k) for a, k in zip(arrays, kernels)]


def _chip_bytes(a: jax.Array) -> int:
    """Bytes of ``a`` that one chip holds (its shard)."""
    return math.prod(a.sharding.shard_shape(a.shape)) * a.dtype.itemsize


def _snapshot_for_host(vals) -> list:
    """Device-side copies of a host node's resolved arguments — the
    double buffer under donation: the callback reads the snapshot while
    the next executable call donates (and XLA overwrites) the original
    buffers.  One call of ``ripple_host_snapshot`` copies every array
    among them, async-dispatched, so it rides the device stream *before*
    the overwrite without blocking the dispatcher.  On a TPU an array of
    rank >= 2 on one device goes through the ``host_snapshot`` kernel,
    the name the device trace shows.  ``host.copy_bytes`` counts the
    bytes one chip copies."""
    arrays = _host_arg_leaves(vals)
    if not arrays:
        return list(vals)
    tpu = not kernels_lib.interpret_mode()
    kernels = tuple(tpu and a.ndim >= 2 and len(a.sharding.device_set) == 1
                    for a in arrays)
    copies = iter(ripple_host_snapshot(arrays, kernels))
    tracing.count("host.copy_bytes", sum(map(_chip_bytes, arrays)))
    out = []
    for v in vals:
        if isinstance(v, RecordArray):
            v = RecordArray(next(copies), v.spec, v.layout)
        elif isinstance(v, jax.Array):
            v = next(copies)
        out.append(v)
    return out


def _host_arg_leaves(vals) -> list:
    """The device arrays among resolved host args (what the pooled task
    blocks on before invoking the callback)."""
    leaves = []
    for v in vals:
        if isinstance(v, RecordArray):
            leaves.append(v.data)
        elif isinstance(v, jax.Array):
            leaves.append(v)
    return leaves


class _AsyncRun:
    """The in-flight host-callback futures of ONE ``run()``/``__call__``
    epoch (the event-driven dispatcher's mutable state).

    Each non-barrier host region is submitted to the shared pool instead
    of blocking the dispatcher; the task first waits on the previous
    host task (program order for side effects — the host-order edges of
    the region DAG), then blocks on its own argument arrays (its only
    true data dependency), then runs the callback.  ``donate=True``
    snapshots the arguments at submit time so later donating region
    calls cannot delete the buffers out from under a still-running
    callback.  ``max_inflight`` bounds the pipeline depth.

    ``host_timeout`` (seconds, None = no watchdog) arms the hung-
    callback watchdog: any wait on an in-flight future — the inflight
    cap, a barrier/epoch drain, or a successor's host-order wait —
    gives up after that long, raises :class:`HostTimeoutError`
    (transient), sets the cancel event so every not-yet-started task
    exits immediately as cancelled, and leaves this context drained and
    reusable.  Python threads cannot be killed, so a truly hung
    callback keeps occupying one pool slot until it returns — but the
    dispatcher, the epoch, and the executor all stay live."""

    max_inflight = 32

    def __init__(self, donate: bool, host_timeout: Optional[float] = None):
        self.donate = donate
        self.host_timeout = host_timeout
        self.tasks: list = []    # (region_index, Future), dispatch order
        self._prev = None        # tail of the host-order chain
        self._cancelled = threading.Event()

    def submit(self, region_index: int, fn, vals,
               label: Optional[str] = None) -> None:
        """Queue ``fn(*vals)`` behind the previous host task; ``label``
        names the node on its ``ripple.host`` span."""
        self.check()
        _fault_trip("executor.dispatch", detail=f"region{region_index}")
        self.hold(self.max_inflight - 1, reason="inflight")
        if self.donate:
            vals = _snapshot_for_host(vals)
        # the task lets go of its arguments (the snapshot) when it
        # returns, before its future completes
        held = [(vals, _host_arg_leaves(vals))]
        prev = self._prev
        timeout = self.host_timeout
        cancelled = self._cancelled

        def task():
            vals, leaves = held.pop()
            if cancelled.is_set():
                raise _HostTaskCancelled()
            # Future.exception() blocks until prev completes — this IS
            # the host-order chain; a failed predecessor cancels us.
            # Under the watchdog the wait is bounded: a predecessor
            # still running after host_timeout counts as failed.
            if prev is not None:
                try:
                    if prev.exception(timeout=timeout) is not None:
                        raise _HostTaskCancelled()
                except FuturesTimeout:
                    raise _HostTaskCancelled() from None
            if cancelled.is_set():
                raise _HostTaskCancelled()
            jax.block_until_ready(leaves)
            _fault_trip("executor.host", detail=f"region{region_index}")
            if fn is not None:
                with tracing.span("ripple.host", region=region_index,
                                  node=label):
                    fn(*vals)

        fut = _host_pool().submit(tracing.carry(task))
        self._prev = fut
        self.tasks.append((region_index, fut))

    def _timed_result(self, region_index: int, fut):
        """``fut.result`` under the watchdog; a timeout cancels every
        not-yet-started task and raises :class:`HostTimeoutError`."""
        try:
            return fut.result(timeout=self.host_timeout)
        except FuturesTimeout:
            self._cancelled.set()
            err = HostTimeoutError(
                f"host callback of region {region_index} still running "
                f"after {self.host_timeout}s — cancelling successors")
            err.site = "executor.host"
            raise err from None

    def hold(self, n: int, reason: str = "slot") -> None:
        """Wait for the oldest callbacks, each under a
        ``ripple.host_wait`` span of ``reason``, until at most ``n`` are
        in flight."""
        while len(self.tasks) > n:
            region_index, fut = self.tasks[0]
            try:
                with tracing.span("ripple.host_wait", reason=reason):
                    self._timed_result(region_index, fut)
            except _HostTaskCancelled:
                pass
            self.tasks.pop(0)

    def check(self) -> None:
        """Surface an already-failed callback without waiting on the
        rest — the dispatcher calls this before issuing each region so a
        failure stops new work promptly."""
        for _, fut in self.tasks:
            if fut.done():
                exc = fut.exception()
                if exc is not None and \
                        not isinstance(exc, _HostTaskCancelled):
                    raise exc

    def drain(self) -> None:
        """Wait for every in-flight callback; re-raise the FIRST failure
        in dispatch order (cancelled successors are skipped) — the
        exception a synchronous run would have raised.  Under the
        watchdog each wait is bounded: the first timeout cancels all
        not-yet-started tasks (which then finish promptly as cancelled)
        and the drain reports :class:`HostTimeoutError`."""
        first = None
        for region_index, fut in self.tasks:
            try:
                self._timed_result(region_index, fut)
            except _HostTaskCancelled:
                pass
            except BaseException as exc:
                if first is None:
                    first = exc
        self.tasks.clear()
        self._prev = None
        if first is not None:
            raise first

    def abort(self) -> None:
        """Exception-path cleanup: wait out every in-flight callback
        swallowing their errors (another exception is already flying) —
        no orphaned tasks, no deadlock.  Bounded waits under the
        watchdog: a still-hung callback is abandoned to the pool (its
        successors are cancelled) rather than deadlocking the abort."""
        self._cancelled.set()
        for _, fut in self.tasks:
            try:
                fut.result(timeout=self.host_timeout)
            except BaseException:
                pass
        self.tasks.clear()
        self._prev = None


# -- layout solver (paper §4.2 as a per-segment compiler pass) -----------------

@dataclass(frozen=True)
class RelayoutStep:
    """An explicit layout conversion the executor inserts at a segment
    boundary: ``tensor`` is converted ``src -> dst`` before ``segment``."""

    segment: int
    tensor: str
    src: Layout
    dst: Layout


@dataclass(frozen=True)
class HaloTransfer:
    """One scheduled halo block of a segment's exchange (plan introspection).

    ``block`` names which sides of which space dims the block extends —
    ``((1, 'low'),)`` is an edge strip, ``((0, 'low'), (1, 'high'))`` a
    corner.  ``mesh_axis`` is the axis the block's final hop permutes over
    (``None`` — a local boundary fill, no transfer); ``phase`` is when the
    send is issued (1 = up-front edge strips, 2+ = extended-edge corner
    hops); ``overlapped`` marks blocks whose flight is hidden behind the
    node's interior program.  ``pairs`` counts the shards of one mesh-axis
    group that send the block: all ``n`` on a periodic axis, ``n - 1``
    otherwise, where the edge shard fills its block locally."""

    segment: int
    node: str
    tensor: str
    phase: int
    block: tuple[tuple[int, str], ...]   # ((space_dim, 'low'|'high'), ...)
    mesh_axis: Optional[str]
    width: int
    overlapped: bool
    nbytes: int = 0                      # per-shard block payload size
    pairs: int = 0                       # ppermute source-target pairs

    def describe(self) -> str:
        where = "+".join(f"{'-' if s == 'low' else '+'}d{d}"
                         for d, s in self.block)
        via = f"ppermute[{self.mesh_axis}]" if self.mesh_axis else "fill"
        mode = "overlapped" if self.overlapped else "sync"
        return (f"seg{self.segment} {self.node}: {self.tensor} {where} "
                f"w={self.width} via {via} phase{self.phase} ({mode})")


@dataclass(frozen=True)
class OverlapFallback:
    """A node that asked for ``overlap=True`` but was lowered through the
    synchronous halo path, and why (no more silent drops)."""

    segment: int
    node: str
    reason: str


@dataclass(frozen=True)
class DegradationEvent:
    """One recorded transition of the Executor's graceful-degradation
    ladder (never silent — rendered by ``plan.describe()`` exactly like
    :class:`OverlapFallback`).  ``action`` is ``"demote"`` or
    ``"promote"``; ``frm``/``to`` are ladder level names
    (:data:`Executor.LADDER`); ``site`` names the failing injection/
    failure site that drove a demotion (``""`` for promotions);
    ``passes`` is the executor's lifetime pass counter at the
    transition."""

    passes: int
    action: str
    frm: str
    to: str
    site: str
    reason: str

    def describe(self) -> str:
        """One line: what moved, which way, and why."""
        return (f"pass {self.passes}: {self.action} {self.frm} -> "
                f"{self.to} — {self.reason}")


@dataclass
class LayoutPlan:
    """Solver output plus the executor's halo-transfer schedule.

    ``initial`` is what :meth:`Executor.init_state` materializes (the first
    consuming segment's choice, so the common case needs zero relayouts);
    ``relayouts`` are the boundary conversions of one sequential pass.
    ``halo_transfers`` lists every scheduled halo block per segment
    (:meth:`transfers_for_segment`), ``overlap_fallbacks`` every declined
    overlap request with its reason — both filled in by the Executor.
    ``dag`` is the graph's dependency DAG with its segment placement
    (``core/schedule.py``); :meth:`describe_dag` renders it together with
    the relayout steps and halo blocks hoisted to each segment entry.
    ``regions`` is the region compiler's grouping of segments into fused
    executables, ``region_edges`` the region-level dependency DAG the
    event-driven dispatcher honors (``core/schedule.py``'s
    :func:`~repro.core.schedule.region_dag`; :meth:`region_waves`
    layers it into ready waves), ``signature`` the plan-signature
    digest keying the process-wide executable cache, and ``cache`` the
    live cache entry (builds / reuse hits / trace events) — all
    rendered by :meth:`describe_dag`.  ``tuning`` is the measured autotuner's
    :class:`~repro.tuning.search.TuningDecision` when the Executor was
    constructed with ``tune="load"``/``"auto"`` (None when tuning is
    off); :meth:`describe_tuning` renders what was measured, what was
    chosen, and why, and :meth:`describe` renders the whole plan."""

    per_segment: list[dict[str, Layout]] = dfield(default_factory=list)
    initial: dict[str, Layout] = dfield(default_factory=dict)
    relayouts: list[RelayoutStep] = dfield(default_factory=list)
    halo_transfers: list[HaloTransfer] = dfield(default_factory=list)
    overlap_fallbacks: list[OverlapFallback] = dfield(default_factory=list)
    dag: Optional[ScheduleDag] = None
    regions: list[Region] = dfield(default_factory=list)
    region_edges: list["schedule_lib.RegionEdge"] = dfield(
        default_factory=list)
    signature: str = ""
    cache: Optional["ExecutableCacheEntry"] = None
    tuning: Optional[Any] = None
    degradations: list[DegradationEvent] = dfield(default_factory=list)

    def transfers_for_segment(self, segment: int) -> list[HaloTransfer]:
        """The scheduled halo blocks entering one segment (see
        :class:`HaloTransfer`)."""
        return [h for h in self.halo_transfers if h.segment == segment]

    def region_waves(self) -> list[list[int]]:
        """Ready waves of region indices under the region-level DAG —
        regions sharing a wave have no dependency path between them, so
        the event-driven runtime may overlap them (also rendered by
        :meth:`describe_dag` as the "region ready waves" block)."""
        return schedule_lib.region_waves(self.regions, self.region_edges)

    def describe_dag(self) -> str:
        """Render the dependency DAG with its segment/wave placement,
        relayout steps, hoisted halo blocks, region grouping, and
        executable-cache state (see ``core/schedule.py``)."""
        if self.dag is None:
            return "(no dependency DAG recorded)"
        return self.dag.describe(plan=self)

    def describe_transfers(self) -> str:
        """One line per scheduled halo block plus every declined overlap
        request with its reason."""
        if not self.halo_transfers:
            return "(no scheduled halo transfers)"
        lines = [h.describe() for h in self.halo_transfers]
        lines += [f"seg{f.segment} {f.node}: overlap fallback — {f.reason}"
                  for f in self.overlap_fallbacks]
        return "\n".join(lines)

    def describe_tuning(self) -> str:
        """Render the measured autotuner's decision for this plan: the
        baseline-vs-tuned steady-state times, every candidate measured
        (layout per state key, tile per kernel) and which won.  With
        tuning off, says so and how to turn it on."""
        if self.tuning is None:
            return ("(no measured tuning: heuristic layout solver and "
                    "default kernel tiles — construct the Executor with "
                    "tune=\"auto\" to measure)")
        return self.tuning.describe()

    def describe_degradations(self) -> str:
        """One line per recorded ladder transition (demotions with the
        failing site and reason, promotions after clean passes); says so
        when the run never degraded."""
        if not self.degradations:
            return "(no degradation-ladder transitions)"
        return "\n".join("ladder " + d.describe() for d in self.degradations)

    def describe(self) -> str:
        """The full plan, human-readable: schedule + transfers + regions
        + cache state (:meth:`describe_dag`), the degradation-ladder
        transitions (:meth:`describe_degradations`), then the tuning
        report (:meth:`describe_tuning`)."""
        return (f"{self.describe_dag()}\n{self.describe_degradations()}\n"
                f"{self.describe_tuning()}")


_NATIVE_COMBINE = {"add": lax.psum, "max": lax.pmax, "min": lax.pmin}

_FOLD_COMBINE = {
    "mul": jnp.multiply,
    "and": jnp.bitwise_and,
    "or": jnp.bitwise_or,
    "xor": jnp.bitwise_xor,
    "minimum": jnp.minimum,   # NaN-propagating elementwise per IEEE/jnp
    "maximum": jnp.maximum,
}


def _combine_over_axes(local, axes, combine: str):
    """Cross-shard combine for a reduction result.

    ``add``/``max``/``min`` ride the native psum/pmax/pmin collectives.
    The remaining Ripple combiners (mul, bitwise and/or/xor, NaN-propagating
    minimum/maximum) have no lax primitive, so the per-shard scalars are
    all-gathered (tiny: one scalar per mesh shard) and folded locally —
    every shard computes the identical fold, keeping the result replicated
    exactly like the psum path."""
    if combine in _NATIVE_COMBINE:
        return _NATIVE_COMBINE[combine](local, axes)
    op = _FOLD_COMBINE[combine]
    gathered = lax.all_gather(local, axes)  # (n_shards, *local.shape)
    return functools.reduce(op, [gathered[i]
                                 for i in range(gathered.shape[0])])


def _segment_nodes(kind: str, payload):
    """All nodes a segment executes (loop bodies recursively)."""
    if kind == "device":
        for level in payload:
            yield from level
    elif kind in ("loop", "host_loop"):
        yield from _graph_nodes(payload)
    elif kind == "host":
        yield payload


def _graph_nodes(g: Graph):
    for node in g.nodes():
        if node.subgraph is not None:
            yield from _graph_nodes(node.subgraph)
        else:
            yield node


def _clamp_layout(t: DistTensor, lay: Layout) -> Layout:
    """AoSoA cannot carry halo/partition on the tiled (last) dim; fall back
    to SoA (the per-axis layout the halo machinery favors) when it would
    (feasibility rule: ``core/layout.py``'s :func:`storage_candidates`)."""
    if lay is not Layout.AOSOA or not t.is_record:
        return lay
    if lay not in storage_candidates(t.space, t.halo, t.partition):
        return Layout.SOA
    return lay


def solve_layouts(
    segments,
    tensors: dict[str, DistTensor],
    overrides: Optional[dict[str, Layout]] = None,
    segment_overrides: Optional[dict[int, dict[str, Layout]]] = None,
) -> LayoutPlan:
    """Choose a storage layout per record tensor per segment.

    Decision order per tensor (first match wins):

    1. ``segment_overrides`` — the joint autotuner's PER-SEGMENT choice
       (segment index -> key -> layout): mixed-segment assignments are
       value-exact because ``_build_region_fn`` traces the boundary
       relayouts this plan records;
    2. ``overrides`` — a plan-uniform forced choice (a parent executor's
       decision for loop sub-executors, or the tuner's uniform axis);
    3. ``DistTensor.pin_layout`` — the user's pin;
    4. the first node-level preference (``TensorArg.layout``) in node
       order, clamped by halo/partition feasibility;
    5. the tensor's declared layout (clamped the same way).

    Segments are the executor's host-boundary segmentation, so a
    device-only graph is one segment and gets one uniform decision.
    """
    overrides = overrides or {}
    segment_overrides = segment_overrides or {}

    def choose(seg_idx, nodes) -> dict[str, Layout]:
        seg_over = segment_overrides.get(seg_idx, {})
        hints: dict[str, Layout] = {}
        seen: set[str] = set()
        no_aosoa: set[str] = set()
        for node in nodes:
            for a in node.args:
                if isinstance(a, TensorArg):
                    t, hint = a.tensor, a.layout
                elif isinstance(a, DistTensor):
                    t, hint = a, None
                else:
                    continue
                if not t.is_record:
                    continue
                seen.add(t.name)
                # feasibility is per ACCESS handle: halo widths are
                # access-level (storage_key excludes them), so any haloed
                # access vetoes AoSoA for the shared storage
                if _clamp_layout(t, Layout.AOSOA) is not Layout.AOSOA:
                    no_aosoa.add(t.name)
                if hint is not None and t.name not in hints:
                    hints[t.name] = hint
        out: dict[str, Layout] = {}
        for name in seen:
            t = tensors[name]
            if name in seg_over:
                out[name] = seg_over[name]
            elif name in overrides:
                out[name] = overrides[name]
            elif t.pin_layout:
                # an infeasible pin is a user error, surfaced at
                # construction (mesh or not), never worked around
                if t.layout is Layout.AOSOA and (
                        name in no_aosoa
                        or _clamp_layout(t, Layout.AOSOA)
                        is not Layout.AOSOA):
                    raise ValueError(
                        f"{name}: pinned AOSOA layout is infeasible — the "
                        f"tensor carries a halo or partition on the tiled "
                        f"(last) space dim")
                out[name] = t.layout
            else:
                lay = _clamp_layout(t, hints.get(name, t.layout))
                if lay is Layout.AOSOA and name in no_aosoa:
                    lay = Layout.SOA
                out[name] = lay
        return out

    per_segment = [choose(i, list(_segment_nodes(k, p)))
                   for i, (k, p) in enumerate(segments)]

    plan = LayoutPlan(per_segment=per_segment)
    current: dict[str, Layout] = {}
    for i, seg in enumerate(per_segment):
        for name, lay in seg.items():
            cur = current.get(name)
            if cur is None:
                plan.initial[name] = lay
            elif cur is not lay:
                plan.relayouts.append(RelayoutStep(i, name, cur, lay))
            current[name] = lay
    for name, t in tensors.items():
        if t.is_record and name not in plan.initial:
            plan.initial[name] = t.layout
    return plan


# -- plan signature (structural identity of a compiled plan) -------------------
#
# The process-wide executable cache must never alias two plans that could
# compute different values, and should alias plans from *re-instantiated*
# executors over an identical graph (the serving pattern: build the graph,
# build an Executor, serve; rebuild on the next request).  Node names are
# excluded (they come from a global counter and differ per build); node
# *functions* are keyed by module/qualname + code object + closure/default
# values, so a rebuilt graph using the same function definitions matches.
# Anything the signature cannot prove equal falls back to ``id(...)``:
# a conservative cache miss, never a wrong hit.

_SIG_DEPTH = 6


def _module_singleton(fn) -> bool:
    """True if ``fn`` IS the attribute its module/qualname names — a
    stable process-wide singleton (e.g. ``jnp.sum``)."""
    mod = sys.modules.get(getattr(fn, "__module__", None) or "")
    if mod is None:
        return False
    obj = mod
    try:
        for part in fn.__qualname__.split("."):
            obj = getattr(obj, part)
    except AttributeError:
        return False
    return obj is fn


def _code_sig(code: types.CodeType):
    consts = tuple(_code_sig(c) if isinstance(c, types.CodeType) else repr(c)
                   for c in code.co_consts)
    return (code.co_name, code.co_argcount, code.co_code, consts,
            code.co_names)


def _all_code_names(code: types.CodeType) -> set:
    """Every global name referenced by ``code`` or its nested code
    objects (inner lambdas/defs share the enclosing fn's globals)."""
    names = set(code.co_names)
    for c in code.co_consts:
        if isinstance(c, types.CodeType):
            names |= _all_code_names(c)
    return names


def _globals_sig(fn, code: types.CodeType, depth: int):
    """Key the VALUES of the module globals a function reads — a node fn
    like ``def f(x): return x * SCALE`` must miss the cache when SCALE
    changed between Executor builds (co_names alone keys the name, not
    the value).  Module-valued names are keyed by module name (cheap)."""
    g = getattr(fn, "__globals__", None)
    if g is None:
        return ()
    out = []
    for name in sorted(_all_code_names(code)):
        if name in g:
            v = g[name]
            if isinstance(v, types.ModuleType):
                out.append((name, ("module", v.__name__)))
            else:
                out.append((name, _sig_value(v, depth)))
    return tuple(out)


def _fn_sig(fn, depth: int = 0):
    if depth > _SIG_DEPTH:
        return ("deep-fn", id(fn))
    if isinstance(fn, functools.partial):
        return ("partial", _fn_sig(fn.func, depth + 1),
                _sig_value(fn.args, depth + 1),
                _sig_value(fn.keywords, depth + 1))
    # a bound method proxies __code__/__closure__ from the underlying
    # function — the receiver carries state, so it must be keyed too
    self_obj = getattr(fn, "__self__", None)
    if self_obj is not None:
        func = getattr(fn, "__func__", None)
        return ("bound", _sig_value(self_obj, depth + 1),
                _fn_sig(func, depth + 1) if func is not None else None)
    code = getattr(fn, "__code__", None)
    if code is None:
        mod = getattr(fn, "__module__", None)
        qn = getattr(fn, "__qualname__", None) or getattr(fn, "__name__", None)
        if qn is not None and _module_singleton(fn):
            return ("singleton", mod, qn)
        return ("callable", mod, qn, id(fn))
    cells = []
    for c in (fn.__closure__ or ()):
        try:
            cells.append(_sig_value(c.cell_contents, depth + 1))
        except ValueError:          # empty cell
            cells.append(("empty-cell",))
    # globals are keyed by VALUE one level deep (the node fn itself and
    # its closure-level callees); deeper library internals would explode
    # the walk and are keyed by code identity alone
    globs = _globals_sig(fn, code, depth + 1) if depth < 2 else ()
    return ("fn", fn.__module__, fn.__qualname__, _code_sig(code),
            tuple(cells), _sig_value(fn.__defaults__ or (), depth + 1),
            _sig_value(fn.__kwdefaults__ or {}, depth + 1), globs)


def _tensor_sig(t: DistTensor):
    spec = (None if t.spec is None
            else tuple((f.name, f.size) for f in t.spec.fields))
    return ("dt", t.name, t.space, str(jnp.dtype(t.dtype)), spec,
            t.layout.name, t.pin_layout, t.partition, t.halo,
            t.boundary.name, t.boundary_constant, t.subblocks)


def _sig_value(v, depth: int = 0):
    if depth > _SIG_DEPTH:
        return ("deep", id(v))
    if v is None or isinstance(v, (bool, int, float, str, bytes)):
        return v
    if isinstance(v, enum_lib.Enum):
        return ("enum", type(v).__name__, v.name)
    if isinstance(v, (tuple, list)):
        return ("seq", tuple(_sig_value(x, depth + 1) for x in v))
    if isinstance(v, dict):
        return ("map", tuple(sorted(
            (str(k), _sig_value(x, depth + 1)) for k, x in v.items())))
    if isinstance(v, DistTensor):
        return _tensor_sig(v)
    if isinstance(v, ReductionResult):
        return ("res", v.name, str(jnp.dtype(v.dtype)), v.init)
    if isinstance(v, (np.ndarray, jax.Array)):
        # size/shape/dtype are metadata (no device transfer); only small
        # arrays are materialized for value-keying
        if v.size > 1024:
            return ("bigarr", tuple(v.shape), str(v.dtype), id(v))
        a = np.asarray(v)
        return ("arr", a.shape, str(a.dtype), a.tobytes())
    if callable(v):
        return _fn_sig(v, depth + 1)
    return ("obj", type(v).__module__, type(v).__qualname__, id(v))


def _node_sig(node: Node):
    args = []
    for a in node.args:
        if isinstance(a, TensorArg):
            args.append(("targ", _tensor_sig(a.tensor), a.mode.name,
                         None if a.layout is None else a.layout.name))
        elif isinstance(a, DistTensor):
            args.append(("t", _tensor_sig(a)))
        elif isinstance(a, ReductionResult):
            args.append(("r", a.name, str(jnp.dtype(a.dtype)), a.init))
        else:
            args.append(("v", _sig_value(a)))
    red = (None if node.reducer is None else
           (node.reducer.name, node.reducer.combine,
            _fn_sig(node.reducer.local)))
    res = (None if node.result is None else
           (node.result.name, str(jnp.dtype(node.result.dtype)),
            node.result.init))
    sub = None if node.subgraph is None else _graph_sig(node.subgraph)
    return (node.kind, node.exec_kind.name, node.overlap, node.writes,
            tuple(args), None if node.fn is None else _fn_sig(node.fn),
            red, res, sub)


def _graph_sig(g: Graph):
    levels = tuple(tuple(_node_sig(n) for n in level) for level in g.levels)
    cond = None if g.condition is None else _fn_sig(g.condition)
    # the name scopes the graph's nodes lower under carry its name
    return ("graph", g.name, levels, cond)


def _segments_sig(segments):
    out = []
    for kind, payload in segments:
        if kind == "device":
            out.append(("device", tuple(
                tuple(_node_sig(n) for n in wave) for wave in payload)))
        elif kind == "host":
            out.append(("host", _node_sig(payload)))
        else:  # loop / host_loop: payload is the subgraph
            out.append((kind, _graph_sig(payload)))
    return tuple(out)


def _mesh_sig(mesh: Optional[Mesh]):
    if mesh is None:
        return None
    devices = [d for d in mesh.devices.flat]
    return (tuple(mesh.shape.items()),
            tuple(int(d.id) for d in devices),
            devices[0].platform if devices else None)


def plan_signature(executor: "Executor") -> tuple:
    """Structural identity of a compiled plan: graph names and structure
    (node kinds, args, function code + closures — NOT auto-generated
    node names; the graph names are in the executables' names and name
    scopes), tensor shapes/dtypes/layouts, mesh, schedule mode, per-
    segment layout decisions, kernel tile overrides, and donation.  Two
    executors with equal signatures compute identical values for
    identical inputs, so their compiled region executables are
    interchangeable.  Tile overrides are part of the key because they
    change the Pallas programs traced into a region executable (the
    autotuner relies on this: candidate configurations never alias).
    v3 additionally keys the joint autotuner's per-segment layout
    overrides explicitly — a per-segment tuned assignment and a
    plan-uniform one that happen to agree still key identically through
    the per-segment decision tuples, but a FORCED per-segment override
    never aliases an unforced plan."""
    plan = executor.plan
    return ("ripple-plan-v3", executor.graph.name, executor.schedule,
            executor.donate,
            _mesh_sig(executor.mesh), _segments_sig(executor._segments),
            tuple(tuple(sorted((n, l.name) for n, l in seg.items()))
                  for seg in plan.per_segment),
            tuple(sorted((n, l.name) for n, l in plan.initial.items())),
            tuple(sorted(
                (si, n, l.name)
                for si, d in executor._segment_overrides.items()
                for n, l in d.items())),
            tuple(sorted((str(k), _sig_value(v))
                         for k, v in executor._tile_config.items())))


def layout_candidates(executor: "Executor") -> dict[str, tuple[Layout, ...]]:
    """The measured autotuner's layout search space (``repro.tuning``).

    For every record state key that is neither user-pinned nor already
    forced by a layout override: the halo-feasible storage layouts
    (``core/layout.py``'s :func:`storage_candidates`, additionally
    clamped by every *access* of the key — any haloed access vetoes
    AoSoA for the shared storage, exactly the PR-1 solver's rule — and
    validated against the mesh).  Keys with a single feasible layout
    are omitted: there is nothing to search."""
    no_aosoa: set[str] = set()
    seen: set[str] = set()
    for kind, payload in executor._segments:
        for node in _segment_nodes(kind, payload):
            for a in node.args:
                t = a.tensor if isinstance(a, TensorArg) else a
                if not isinstance(t, DistTensor) or not t.is_record:
                    continue
                seen.add(t.name)
                if _clamp_layout(t, Layout.AOSOA) is not Layout.AOSOA:
                    no_aosoa.add(t.name)
    out: dict[str, tuple[Layout, ...]] = {}
    for name in sorted(seen):
        t = executor.tensors[name]
        if t.pin_layout or name in executor._layout_overrides:
            continue
        cands = []
        for lay in storage_candidates(t.space, t.halo, t.partition):
            if lay is Layout.AOSOA and name in no_aosoa:
                continue
            if executor.mesh is not None:
                try:
                    t.with_(layout=lay).validate_mesh(executor.mesh)
                except ValueError:
                    continue
            cands.append(lay)
        if len(cands) > 1:
            out[name] = tuple(cands)
    return out


# -- process-wide executable cache ---------------------------------------------

@dataclass
class ExecutableCacheEntry:
    """All compiled executables of one plan signature.

    ``executables`` maps ``('region', index, entry-layout-sig)`` /
    ``('fused', entry-layout-sig)`` keys to jitted callables.  ``builds``
    counts executables constructed, ``hits`` counts fetches that found an
    executable some *other* fetch already built (the re-instantiated-
    executor reuse path), and ``trace_events`` counts actual jit traces
    (the callables bump it from inside their Python bodies, which only
    run while tracing) — steady-state ``run()`` must not move it."""

    executables: dict[Any, Callable] = dfield(default_factory=dict)
    builds: int = 0
    hits: int = 0
    trace_events: int = 0


# Entries pin their builder Executor (the jitted callables close over it)
# for process lifetime — that retention IS the serving-pattern feature
# (compiled programs survive Executor re-instantiation), but a process
# cycling through many distinct plans should call clear_executable_cache()
# when a plan generation is retired.
_EXECUTABLE_CACHE: dict[tuple, ExecutableCacheEntry] = {}


def clear_executable_cache() -> None:
    """Drop every cached plan executable (tests / memory pressure /
    retiring a plan generation in a long-lived process)."""
    _EXECUTABLE_CACHE.clear()


def executable_cache_stats() -> dict:
    """Aggregate stats over the process-wide executable cache."""
    entries = list(_EXECUTABLE_CACHE.values())
    return {
        "plans": len(entries),
        "executables": sum(len(e.executables) for e in entries),
        "builds": sum(e.builds for e in entries),
        "hits": sum(e.hits for e in entries),
        "trace_events": sum(e.trace_events for e in entries),
    }


# -- overlap decision (paper Fig. 7 generalized) -------------------------------

# (node name, reason) pairs already warned about — "warn once" holds across
# the sub-executors a loop segment re-creates for the same node
_warned_overlap: set[tuple[str, str]] = set()


@dataclass(frozen=True)
class _OverlapDecision:
    """Whether an ``overlap=True`` split node gets the interior/boundary
    lowering: ``strips`` = ((space_dim, max halo width), ...) ascending,
    or None with a ``reason`` (``warn`` when real transfers get degraded
    to the synchronous path rather than there being nothing to hide)."""

    strips: Optional[tuple[tuple[int, int], ...]]
    reason: Optional[str] = None
    warn: bool = False


def _decide_overlap(node: Node, mesh: Optional[Mesh], eff) -> _OverlapDecision:
    if mesh is None:
        return _OverlapDecision(
            None, "graph has no mesh — nothing to overlap", False)
    padded = [eff(t) for _, t, mode in node.tensor_args() if mode.padded]
    if not padded:
        return _OverlapDecision(
            None, "no padded-access tensor arg to overlap", True)
    strips: dict[int, int] = {}
    for t in padded:
        for e in _halo_plan(t, mesh):
            if e.mesh_axis is not None:
                strips[e.dim] = max(strips.get(e.dim, 0), e.width)
    if not strips:
        return _OverlapDecision(
            None, "no mesh-partitioned halo axis (single shard along every "
            "haloed dim)", False)
    ref = padded[0]
    tensors = [eff(t) for _, t, _ in node.tensor_args()]
    for d in sorted(strips):
        w = strips[d]
        ax_name = ref.partition[d]
        for t in tensors:
            if len(t.space) <= d or t.space[d] != ref.space[d] \
                    or t.partition[d] != ax_name:
                return _OverlapDecision(
                    None, f"arg {t.name!r} does not align with "
                    f"partitioned halo dim {d} of {ref.name!r}", True)
            try:
                t.storage_axis(d)
            except ValueError as exc:
                return _OverlapDecision(None, str(exc), True)
        m = ref.space[d] // mesh.shape[ax_name]
        if m <= 2 * w:
            return _OverlapDecision(
                None, f"shard extent {m} along dim {d} leaves no interior "
                f"behind boundary strips of width {w}", True)
    return _OverlapDecision(tuple(sorted(strips.items())))


class Executor:
    """Compile + run a Graph against an optional mesh.

    ``schedule`` selects how graph nodes become jit segments:

    * ``"dag"`` (default) — dependency-DAG scheduling
      (``core/schedule.py``): antichains of independent device nodes fuse
      into shared waves/segments, and host / loop nodes break the chain
      only where a dependency path forces it;
    * ``"sequential"`` — the legacy program-order lowering (every level a
      barrier, every host node a break) — the escape hatch and the
      reference semantics the property tests compare against.

    Maximal runs of device/loop segments become one jitted executable
    each (a *region*), with the boundary relayouts traced inside, cached
    process-wide by plan signature.  The host time of each executable
    call is the ``ripple.dispatch`` span, which the benchmark's
    ``dispatch_ms.euler`` reads on the chip.

    Both schedules produce bitwise-identical state for any valid graph;
    the DAG schedule just gives XLA more to overlap per dispatch.

    ``tune`` selects the measured autotuner (``repro.tuning``):

    * ``"off"`` (default) — heuristic layout solver, default kernel
      tiles (exactly the pre-tuner behavior);
    * ``"load"`` — apply a tuned configuration from the persistent
      cache when one exists for this plan signature × device × jax
      version; fall back to heuristics on a miss (never measures —
      safe for latency-sensitive construction paths);
    * ``"auto"`` — like ``"load"``, but on a cache miss run the JOINT
      search: propose the cross product of per-key halo-feasible
      layouts × per-kernel ``tile_candidates()`` (plus per-segment
      layout refinements), rank every proposal with the HLO cost model
      so only the cheapest fraction is ever measured, time the
      survivors with real executions of the region executables (each
      candidate's timing loop stops early once it is statistically
      dominated), commit the argmin into the plan, and persist it, so
      the *next* construction — this process or another — pays zero
      measurements.

    ``tune_budget`` bounds the ``"auto"`` search — a
    ``repro.tuning.TuneBudget`` (or a dict of its fields): the fraction
    of proposals measured, the early-stop domination factor, and how
    many consecutive non-improving candidates end the search.
    ``plan.describe_tuning()`` renders the decision, including the
    proposed / pruned / measured counts and any per-segment layout
    assignments; ``tile_overrides`` forces specific kernel tiles
    (kernel name -> tile config, what the tuner itself uses to stage
    candidates); ``segment_layout_overrides`` pins layouts for
    individual segments (segment index -> key -> layout, the tuner's
    per-segment decision axis); and ``tune_inputs`` optionally supplies
    ``init_state`` overrides for the tuner's timed executions so
    measurement runs on realistic data.

    Example::

        ex = Executor(graph, tune="auto")     # measures once, persists
        print(ex.plan.describe_tuning())      # what won, and why
        ex2 = Executor(graph, tune="auto")    # cache hit: 0 measurements
    """

    def __init__(self, graph: Graph, mesh: Optional[Mesh] = None,
                 donate: bool = True,
                 layout_overrides: Optional[dict[str, Layout]] = None,
                 schedule: str = "dag",
                 async_regions: bool = True,
                 tune: str = "off",
                 tune_budget: Optional[Any] = None,
                 tile_overrides: Optional[dict[str, Any]] = None,
                 tune_inputs: Optional[dict[str, Any]] = None,
                 segment_layout_overrides: Optional[
                     dict[int, dict[str, Layout]]] = None,
                 host_timeout: Optional[float] = None,
                 degrade: bool = True,
                 demote_after: int = 2, promote_after: int = 8):
        if schedule not in ("dag", "sequential"):
            raise ValueError(
                f"schedule must be 'dag' or 'sequential', got {schedule!r}")
        if tune not in ("off", "load", "auto"):
            raise ValueError(
                f"tune must be 'off', 'load' or 'auto', got {tune!r}")
        self.graph = graph
        self.mesh = mesh
        self.donate = donate
        self.schedule = schedule
        # event-driven region dispatch (host callbacks on the pool, no
        # inter-region block_until_ready); False = synchronous escape
        # hatch with bitwise-identical results.  Not part of the plan
        # signature: both modes run the SAME cached executables.
        self.async_regions = bool(async_regions)
        self.tune = tune
        # hung-callback watchdog (seconds; None = wait forever): bounds
        # every wait on a pooled host callback — see _AsyncRun
        self.host_timeout = host_timeout
        # graceful-degradation ladder: repeated TRANSIENT failures at
        # one site demote the runtime one level at a time
        # (async_regions -> sync -> sequential schedule -> heuristic
        # layouts), and promote_after consecutive clean passes promote
        # back up; every transition lands in plan.degradations.
        self.degrade = bool(degrade)
        self.demote_after = int(demote_after)
        self.promote_after = int(promote_after)
        self.tensors = graph.all_tensors()
        self.results = graph.all_results()
        self.dag = schedule_lib.build_dag(graph)
        # device-side names: each node lowers under its graph/label name
        # scope, and the executables are named after the graph
        self._scopes = graph.node_scopes()
        self._exe_name = "ripple_" + re.sub(r"\W", "_", graph.name)
        # the user's configured operating point — the top of the ladder
        # (level 0); _apply_ladder_level restores toward these
        self._cfg_schedule = schedule
        self._cfg_async = bool(async_regions)
        self._user_layout_overrides = dict(layout_overrides or {})
        self._user_segment_overrides = {
            int(i): dict(v)
            for i, v in (segment_layout_overrides or {}).items()}
        self._user_tile_config = dict(tile_overrides or {})
        self.ladder_level = 0
        self._site_failures: dict[str, int] = {}
        self._clean_passes = 0
        self._pass_counter = 0
        self._degradations: list[DegradationEvent] = []
        self._apply_schedule(schedule)
        self._sharded = mesh is not None and any(
            ax is not None for t in self.tensors.values()
            for ax in t.partition)
        self._layout_overrides = dict(layout_overrides or {})
        self._segment_overrides = {
            int(i): dict(v)
            for i, v in (segment_layout_overrides or {}).items()}
        self._tile_config = dict(tile_overrides or {})
        self._tune_inputs = dict(tune_inputs or {})
        self._tune_budget = tune_budget
        self._build_plan()
        if tune != "off":
            from ..tuning.search import resolve_tuning

            decision = resolve_tuning(self, tune, budget=tune_budget)
            if decision.applied:
                # rebuild the plan under the measured-best configuration
                # (relayout steps, halo schedule, signature and cache
                # entry all follow the tuned layouts/tiles — including
                # the per-segment assignments of the joint search)
                self._layout_overrides.update(decision.layouts)
                for si, d in decision.segment_layouts.items():
                    self._segment_overrides.setdefault(
                        int(si), {}).update(d)
                self._tile_config.update(decision.tiles)
                self._build_plan()
            self.plan.tuning = decision

    #: Ladder levels, fastest first: the configured operating point,
    #: then synchronous region dispatch, then the sequential reference
    #: schedule, then heuristic (un-tuned) layouts and tiles.  Demotion
    #: moves one level down after ``demote_after`` transient failures at
    #: one site; ``promote_after`` consecutive clean passes move one
    #: level back up.  Every transition is a DegradationEvent in
    #: ``plan.degradations``.
    LADDER = ("async_regions", "sync", "sequential", "heuristic")

    def _apply_schedule(self, schedule: str) -> None:
        """(Re)build the segment schedule — shared by __init__ and the
        ladder's "sequential" demotion/repromotion."""
        self.schedule = schedule
        if schedule == "dag":
            self._segments = schedule_lib.dag_segments(self.dag)
        else:
            self._segments = schedule_lib.sequential_segments(self.graph)
            schedule_lib.place_units(self.dag, self._segments)

    def _apply_ladder_level(self, level: int) -> None:
        """Reconfigure the runtime for one ladder level.  Level 0 is the
        user's configured operating point; deeper levels stack: 1 turns
        async region dispatch off, 2 additionally falls back to the
        sequential reference schedule, 3 additionally drops tuned
        layout/tile overrides back to the heuristics.  Plan rebuilds
        reuse the process-wide executable cache keyed by the resulting
        signature, so bouncing between levels retraces nothing after
        the first visit."""
        self.ladder_level = level
        self.async_regions = self._cfg_async and level < 1
        want_schedule = self._cfg_schedule if level < 2 else "sequential"
        want_overrides = dict(self._layout_overrides) if level < 3 \
            else dict(self._user_layout_overrides)
        want_tiles = dict(self._tile_config) if level < 3 \
            else dict(self._user_tile_config)
        want_seg = {i: dict(v) for i, v in (
            self._segment_overrides if level < 3
            else self._user_segment_overrides).items()}
        rebuild = (want_schedule != self.schedule
                   or want_overrides != self._layout_overrides
                   or want_tiles != self._tile_config
                   or want_seg != self._segment_overrides)
        if level >= 3:
            # drop the tuned configuration (keep it recoverable for
            # re-promotion in _tuned_layouts/_tuned_tiles)
            self._tuned_layouts = dict(self._layout_overrides)
            self._tuned_tiles = dict(self._tile_config)
            self._tuned_segment_overrides = {
                i: dict(v) for i, v in self._segment_overrides.items()}
        elif getattr(self, "_tuned_layouts", None) is not None:
            want_overrides = dict(self._tuned_layouts)
            want_tiles = dict(self._tuned_tiles)
            want_seg = {i: dict(v) for i, v in
                        self._tuned_segment_overrides.items()}
            rebuild = rebuild or want_overrides != self._layout_overrides \
                or want_seg != self._segment_overrides
            self._tuned_layouts = None
            self._tuned_tiles = None
            self._tuned_segment_overrides = None
        if rebuild:
            tuning = self.plan.tuning
            self._apply_schedule(want_schedule)
            self._layout_overrides = want_overrides
            self._tile_config = want_tiles
            self._segment_overrides = want_seg
            self._build_plan()
            self.plan.tuning = tuning

    def record_failure(self, exc: BaseException, site: str = "") -> bool:
        """Ladder bookkeeping for one failed pass: transient failures
        (``TransientError`` — injected chaos, host watchdog timeouts,
        preemptions) count per ``site``; ``demote_after`` of them at one
        site demote the executor one ladder level.  Deterministic
        errors never move the ladder.  Returns True when a demotion
        happened.  Called automatically by ``__call__``/``run``; public
        so external drivers (Batcher, Supervisor) can attribute
        failures they caught themselves."""
        if not self.degrade or not isinstance(exc, TransientError):
            return False
        site = site or getattr(exc, "site", "") or "executor"
        self._clean_passes = 0
        n = self._site_failures.get(site, 0) + 1
        self._site_failures[site] = n
        if n < self.demote_after \
                or self.ladder_level >= len(self.LADDER) - 1:
            return False
        frm = self.LADDER[self.ladder_level]
        self._apply_ladder_level(self.ladder_level + 1)
        self._site_failures[site] = 0
        self._degradations.append(DegradationEvent(
            self._pass_counter, "demote", frm,
            self.LADDER[self.ladder_level], site,
            f"{n} transient failures at {site} ({exc})"))
        self.plan.degradations = self._degradations
        return True

    def _note_clean_pass(self) -> None:
        """One successful top-level pass: after ``promote_after`` in a
        row at a degraded level, promote one level back up."""
        self._pass_counter += 1
        if self.ladder_level == 0:
            return
        self._clean_passes += 1
        if self._clean_passes < self.promote_after:
            return
        frm = self.LADDER[self.ladder_level]
        self._apply_ladder_level(self.ladder_level - 1)
        self._clean_passes = 0
        self._site_failures.clear()
        self._degradations.append(DegradationEvent(
            self._pass_counter, "promote", frm,
            self.LADDER[self.ladder_level], "",
            f"{self.promote_after} clean passes"))
        self.plan.degradations = self._degradations

    def _build_plan(self) -> None:
        """Solve layouts under the current overrides and derive everything
        that depends on them: halo/overlap schedule, region grouping,
        plan signature, executable-cache entry.  Run once at
        construction, and a second time when the autotuner commits a
        configuration that differs from the heuristics."""
        self.plan = solve_layouts(self._segments, self.tensors,
                                  overrides=self._layout_overrides,
                                  segment_overrides=self._segment_overrides)
        self.plan.dag = self.dag
        # physical layout of each record tensor's state entry right now
        self._state_layouts: dict[str, Layout] = dict(self.plan.initial)
        if self.mesh is not None:
            for name, t in self.tensors.items():
                lays = {self.plan.initial.get(name, t.layout)}
                lays.update(seg[name] for seg in self.plan.per_segment
                            if name in seg)
                for lay in lays:
                    (t.with_(layout=lay) if t.is_record
                     else t).validate_mesh(self.mesh)
        self._overlap_decisions: dict[str, _OverlapDecision] = {}
        self._collect_halo_schedule()
        # bytes one pass sends through collectives, per chip on average:
        # each scheduled block of a device segment, from each shard that
        # sends it (a device loop's trip count is known only on the
        # device, so its blocks are not counted)
        self._halo_pass_bytes = sum(
            h.nbytes * h.pairs / self.mesh.shape[h.mesh_axis]
            for h in self.plan.halo_transfers
            if h.mesh_axis is not None
            and self._segments[h.segment][0] == "device")
        # region compiler: segment runs -> fused executables, cached
        # process-wide by plan signature
        self._regions = schedule_lib.group_regions(
            [k for k, _ in self._segments])
        self.plan.regions = self._regions
        # region-level DAG: lifted from the unit edges so regions — not
        # just nodes — carry explicit dependencies; the async dispatcher
        # uses the per-region barrier bit, describe() the ready waves
        self.plan.region_edges = schedule_lib.region_dag(self.dag,
                                                         self._regions)
        self._region_access = schedule_lib.region_access(self.dag,
                                                         self._regions)
        # run()'s fused path: None where the graph cannot take it, else
        # its leading periodic host nodes as (region index, node), [] for
        # a device-only graph; with host nodes only where no pass
        # converts a layout, so the fused chunks between them chain
        hosts = self.dag.periodic_hosts()
        if hosts and any(lay is not self.plan.initial.get(n, lay)
                         for seg in self.plan.per_segment
                         for n, lay in seg.items()):
            hosts = None
        seg_region = {si: r.index for r in self._regions
                      for si in r.segments}
        self._periodic_hosts = None if hosts is None else [
            (seg_region[u.segment], u.node) for u in hosts]
        self._plan_sig = plan_signature(self)
        self.plan.signature = hashlib.sha1(
            repr(self._plan_sig).encode()).hexdigest()[:12]
        self._cache = _EXECUTABLE_CACHE.setdefault(
            self._plan_sig, ExecutableCacheEntry())
        self.plan.cache = self._cache
        # the ladder's transition log survives plan rebuilds (a demotion
        # to "sequential"/"heuristic" re-solves the whole plan)
        self.plan.degradations = self._degradations
        self._fetched: set = set()        # executable keys this instance saw
        self._sub_execs: dict[int, "Executor"] = {}   # per loop segment
        self.eager_relayouts = 0   # conversions dispatched outside a trace

    def _collect_halo_schedule(self) -> None:
        """Static pass: record every scheduled halo transfer per segment in
        ``plan.halo_transfers``, decide overlap per node, and surface every
        declined ``overlap=True`` in ``plan.overlap_fallbacks`` (warning
        once when the fallback actually degrades scheduling)."""
        mesh = self.mesh
        for si, (kind, payload) in enumerate(self._segments):
            seg_layouts = self.plan.per_segment[si]

            def eff(t, _lays=seg_layouts):
                if t.is_record:
                    lay = _lays.get(t.name, t.layout)
                    if lay is not t.layout:
                        return t.with_(layout=lay)
                return t

            for node in _segment_nodes(kind, payload):
                if node.kind not in ("split", "op"):
                    continue
                dec = None
                if node.kind == "split" and node.overlap:
                    dec = _decide_overlap(node, mesh, eff)
                    self._overlap_decisions[node.name] = dec
                    if dec.strips is None:
                        self.plan.overlap_fallbacks.append(
                            OverlapFallback(si, node.name, dec.reason))
                        key = (node.name, dec.reason)
                        if dec.warn and key not in _warned_overlap:
                            _warned_overlap.add(key)
                            warnings.warn(
                                f"node {node.name!r}: overlap=True falls "
                                f"back to synchronous halo exchange — "
                                f"{dec.reason}", RuntimeWarning,
                                stacklevel=3)
                overlapped = dec is not None and dec.strips is not None
                for _, t, mode in node.tensor_args():
                    if not mode.padded:
                        continue
                    eff_t = eff(t)
                    entries = _halo_plan(eff_t, mesh)
                    if not entries:
                        continue
                    axes = _halo_axes(entries)
                    shard = _shard_storage_shape(eff_t, mesh)
                    itemsize = np.dtype(eff_t.dtype).itemsize
                    for phase, bkey, shape in halo_lib.schedule_blocks(
                            shard, axes):
                        last, _side = bkey[-1]
                        ax = entries[last].mesh_axis
                        pairs = 0 if ax is None else mesh.shape[ax]
                        if eff_t.boundary is not halo_lib.Boundary.PERIODIC:
                            pairs = max(pairs - 1, 0)
                        self.plan.halo_transfers.append(HaloTransfer(
                            si, node.name, t.name, phase,
                            tuple((entries[j].dim, s) for j, s in bkey),
                            ax, entries[last].width, overlapped,
                            nbytes=math.prod(shape) * itemsize, pairs=pairs))

    # -- layout plumbing ---------------------------------------------------
    def _eff_in(self, t: DistTensor, layouts: dict[str, Layout]) -> DistTensor:
        """The tensor handle under an explicit layout assignment (region
        lowering threads the assignment; nothing reads mutable state)."""
        if not t.is_record:
            return t
        lay = layouts.get(t.name, t.layout)
        return t if lay is t.layout else t.with_(layout=lay)

    def _eff(self, t: DistTensor) -> DistTensor:
        """The tensor handle in its *current physical* layout."""
        return self._eff_in(t, self._state_layouts)

    def _layouts_for_segment(self, i: int) -> dict[str, Layout]:
        """The full layout assignment a segment's body is lowered under."""
        return {**self.plan.initial, **self.plan.per_segment[i]}

    def _apply_segment_layouts(self, state: dict, seg: int) -> dict:
        """Insert the solver's relayout steps before segment ``seg``:
        convert every tensor whose physical layout disagrees with the
        segment's chosen layout (paper: explicit layout-interop nodes)."""
        return self._convert_layouts(state, self.plan.per_segment[seg])

    def _restore_initial_layouts(self, state: dict) -> dict:
        """Undo trailing conversions so that outside a call every state
        dict is in the plan's initial layouts — state dicts stay
        interchangeable between calls, re-inits, and ``read``."""
        return self._convert_layouts(state, self.plan.initial)

    def _convert_layouts(self, state: dict,
                         targets: dict[str, Layout]) -> dict:
        for name, lay in targets.items():
            t = self.tensors[name]
            cur = self._state_layouts.get(name, t.layout)
            if cur is lay:
                continue
            with tracing.span("ripple.relayout", tensor=name,
                              bytes=state[name].nbytes):
                arr = relayout(RecordArray(state[name], t.spec, cur), lay)
                data = arr.data
                self._state_layouts[name] = lay
                self.eager_relayouts += 1
                if self.mesh is not None:
                    data = jax.device_put(data,
                                          self._eff(t).sharding(self.mesh))
            state[name] = data
        return state

    def _state_specs(self, state: dict, layouts: dict[str, Layout]) -> dict:
        """PartitionSpec per state entry under a layout assignment."""
        return {k: (self._eff_in(self.tensors[k], layouts).pspec()
                    if k in self.tensors else P())
                for k in state}

    # -- state management ------------------------------------------------
    def init_state(self, **overrides) -> dict[str, Any]:
        """Allocate all tensors/results (zeros unless overridden).

        Record tensors are materialized directly in the layout the solver
        chose for their first consuming segment; a RecordArray override in
        another layout is relayouted on the way in."""
        with tracing.span("ripple.init_state", graph=self.graph.name):
            return self._init_state(overrides)

    def _init_state(self, overrides: dict) -> dict[str, Any]:
        self._state_layouts = dict(self.plan.initial)
        state: dict[str, Any] = {}
        for name, t in self.tensors.items():
            eff = self._eff(t)
            if name in overrides:
                v = overrides[name]
                if isinstance(v, RecordArray):
                    data = relayout(v, eff.layout).data
                elif t.is_record:
                    v = jnp.asarray(v)
                    src = self._infer_override_layout(t, v.shape)
                    data = relayout(RecordArray(v, t.spec, src),
                                    eff.layout).data
                else:
                    data = jnp.asarray(v)
                if self.mesh is not None:
                    data = jax.device_put(data, eff.sharding(self.mesh))
                state[name] = data
            else:
                v = eff.init(self.mesh)
                state[name] = v.data if isinstance(v, RecordArray) else v
        for name, r in self.results.items():
            v = jnp.asarray(r.init, dtype=r.dtype)
            if self.mesh is not None:
                # replicated, as every call returns it: the next call's
                # inputs then match the first's, which traces once
                v = jax.device_put(v, NamedSharding(self.mesh, P()))
            state[name] = v
        return state

    def _infer_override_layout(self, t: DistTensor, shape) -> Layout:
        """Which layout a raw (non-RecordArray) record override is stored
        in, by matching the storage shape against each layout's.  The two
        plausible sources are the solver's initial layout (an executor-
        produced state entry outside a call is always in it) and the
        declared layout (hand-built arrays).  When those differ and the
        shape matches both, guessing could silently scramble the data, so
        we refuse and ask for a RecordArray; otherwise the unique
        matching candidate wins."""
        def fits(lay):
            return tuple(shape) == RecordArray.storage_shape(
                t.spec, t.space, lay)

        preferred = list(dict.fromkeys(
            [self.plan.initial.get(t.name, t.layout), t.layout]))
        matches = [lay for lay in preferred if fits(lay)]
        if len(matches) == 1:
            return matches[0]
        if len(matches) > 1:
            raise ValueError(
                f"{t.name}: override shape {tuple(shape)} is ambiguous "
                f"between layouts {[m.name for m in matches]} for space "
                f"{t.space} — pass a RecordArray to make it explicit")
        others = [lay for lay in Layout
                  if lay not in preferred and fits(lay)]
        if len(others) == 1:
            return others[0]
        if others:
            raise ValueError(
                f"{t.name}: override shape {tuple(shape)} is ambiguous "
                f"between layouts {[m.name for m in others]} for space "
                f"{t.space} — pass a RecordArray to make it explicit")
        raise ValueError(
            f"{t.name}: override shape {tuple(shape)} matches no layout's "
            f"storage shape for space {t.space} "
            f"(pass a RecordArray to make the layout explicit)")

    def state_shardings(self, state: dict) -> dict:
        """NamedSharding per state entry (None entries without a mesh) —
        what ``jax.device_put`` placement of a checkpoint should use."""
        if self.mesh is None:
            return {k: None for k in state}
        out = {}
        for k in state:
            t = self.tensors.get(k)
            spec = self._eff(t).pspec() if t is not None else P()
            out[k] = NamedSharding(self.mesh, spec)
        return out

    def read(self, state: dict, t: DistTensor):
        """Wrap a state entry back into its RecordArray view (in the
        tensor's current physical layout; accessors hide the difference)."""
        return self._eff(t).wrap(state[t.name])

    # -- schedule introspection -------------------------------------------
    def describe_dag(self) -> str:
        """Render the dependency DAG, its segment/wave placement under the
        active schedule, the relayouts / halo blocks hoisted to each
        segment entry, the region grouping, and the executable-cache
        state (see ``core/schedule.py``)."""
        return self.plan.describe_dag()

    def describe_tuning(self) -> str:
        """Render the measured autotuner's decision for this plan
        (``plan.describe_tuning()``): baseline vs tuned steady-state
        times, every measured candidate, and what was committed."""
        return self.plan.describe_tuning()

    def cache_stats(self) -> dict:
        """Live executable-cache stats for this plan signature.

        ``trace_events`` counts actual jit traces of this plan's
        executables; a steady-state ``run()`` must leave it unchanged.
        ``hits`` counts executables this (or another) Executor fetched
        without building — the re-instantiated-executor reuse path."""
        c = self._cache
        return {"signature": self.plan.signature,
                "executables": len(c.executables), "builds": c.builds,
                "hits": c.hits, "trace_events": c.trace_events}

    # -- node lowering (called inside shard_map / plain trace) ----------------
    def _resolve_args(self, node: Node, state: dict, sharded: bool,
                      layouts: dict[str, Layout]):
        """Build the python args passed to a node fn; haloed where needed."""
        mesh = self.mesh if sharded else None
        vals = []
        for i, a in enumerate(node.args):
            if isinstance(a, ReductionResult):
                vals.append(state[a.name])
                continue
            t = None
            mode = AccessMode.DEFAULT
            if isinstance(a, TensorArg):
                t, mode = a.tensor, a.mode
            elif isinstance(a, DistTensor):
                t = a
            if t is None:
                vals.append(a)
                continue
            t = self._eff_in(t, layouts)
            data = state[t.name]
            if mode.padded:
                data = _apply_halo(data, t, mesh)
            vals.append(t.wrap(data) if t.is_record else data)
        return vals

    def _lower_split(self, node: Node, state: dict, sharded: bool,
                     layouts: dict[str, Layout]) -> None:
        writes = node.default_writes()
        write_tensors = []
        for i in writes:
            a = node.args[i]
            write_tensors.append(a.tensor if isinstance(a, TensorArg) else a)

        dec = self._overlap_decisions.get(node.name)
        if node.overlap and sharded and dec is not None \
                and dec.strips is not None:
            self._lower_split_overlapped(node, state, write_tensors,
                                         dec.strips, layouts)
            return

        vals = self._resolve_args(node, state, sharded, layouts)
        out = node.fn(*vals)
        self._store_writes(node, state, write_tensors, out, layouts)

    def _store_writes(self, node, state, write_tensors, out, layouts) -> None:
        if not write_tensors:
            return
        if len(write_tensors) == 1:
            out = (out,)
        if len(out) != len(write_tensors):
            raise ValueError(
                f"{node.name}: fn returned {len(out)} values for "
                f"{len(write_tensors)} writes")
        for t, v in zip(write_tensors, out):
            state[t.name] = self._coerce_write(t, v, layouts)

    def _coerce_write(self, t, v, layouts: dict[str, Layout]):
        """Raw storage for one written value.  A RecordArray output that
        disagrees with the segment's assigned layout for the write tensor
        is converted in-trace — a node fn returns records in whatever
        layout it computed them (usually its input's), and the plan's
        per-key layout choice (heuristic or tuned) must win."""
        if isinstance(v, RecordArray):
            if t.is_record:
                want = layouts.get(t.name, t.layout)
                if v.layout is not want:
                    v = relayout(v, want)
            return v.data
        return jnp.asarray(v)

    def _lower_split_overlapped(self, node: Node, state: dict,
                                write_tensors,
                                strips: tuple[tuple[int, int], ...],
                                layouts: dict[str, Layout]) -> None:
        """Interior/boundary split over N partitioned halo axes: every
        halo block's ppermute is issued up front (phase 1 edge strips,
        phase 2+ corner hops), the interior program runs on the unextended
        shard while they fly, then one boundary-strip program per
        (axis, side) consumes the received blocks and the results are
        stitched (paper Fig. 7 generalized to the multi-dimensional
        transfer space of §5.4).

        ``strips`` is ((space_dim, W), ...) ascending; ``fn`` must be a
        shape-polymorphic stencil mapping (m + 2w) -> m cells along every
        haloed dim.  fn sees, per variant, exactly the sub-region of the
        extended array that its output cells read, so overlap output ==
        synchronous output value-for-value."""
        mesh = self.mesh
        strip_dims = [d for d, _ in strips]
        w_strip = dict(strips)

        # Resolve every arg once: all transfer-schedule sends are issued
        # here, before any variant program is traced.
        preps: list[tuple[str, Any]] = []
        for a in node.args:
            if isinstance(a, ReductionResult):
                preps.append(("raw", state[a.name]))
                continue
            if isinstance(a, TensorArg):
                t, mode = a.tensor, a.mode
            elif isinstance(a, DistTensor):
                t, mode = a, AccessMode.DEFAULT
            else:
                preps.append(("raw", a))
                continue
            t = self._eff_in(t, layouts)
            data = state[t.name]
            entries = ({e.dim: e for e in _halo_plan(t, mesh)}
                       if mode.padded else {})
            dims = sorted(set(entries) | set(strip_dims))
            axes = [halo_lib.HaloAxis(
                t.storage_axis(d),
                entries[d].width if d in entries else 0,
                entries[d].mesh_axis if d in entries else None)
                for d in dims]
            if any(ax.width for ax in axes):
                with jax.named_scope("halo_exchange"):
                    blocks = halo_lib.exchange_blocks(
                        data, axes, boundary=t.boundary,
                        constant=t.boundary_constant)
            else:
                blocks = {(): data}
            preps.append(("tensor", (t, dims, axes, blocks)))

        def ranges_for(variant, dims, axes, blocks):
            """Per-axis extended-coordinate input range for one variant.

            A variant's output domain is: the full boundary slab along its
            own dim, the interior along every earlier strip dim (those
            slabs were peeled off by earlier variants), the full extent
            elsewhere; the input range widens it by this arg's own halo."""
            vd = None if variant == "interior" else variant[0]
            out = []
            for d, ax in zip(dims, axes):
                m = blocks[()].shape[ax.axis]
                w, big_w = ax.width, w_strip.get(d, 0)
                if d == vd:
                    out.append((0, big_w + 2 * w) if variant[1] == "low"
                               else (m - big_w, m + 2 * w))
                elif big_w and (vd is None or d < vd):
                    out.append((big_w, m - big_w + 2 * w))
                else:
                    out.append((0, m + 2 * w))
            return out

        def run(variant):
            vals = []
            for kind, payload in preps:
                if kind == "raw":
                    vals.append(payload)
                    continue
                t, dims, axes, blocks = payload
                with jax.named_scope("halo_assemble"):
                    data = halo_lib.assemble_region(
                        blocks, axes,
                        ranges_for(variant, dims, axes, blocks))
                vals.append(t.wrap(data) if t.is_record else data)
            out = node.fn(*vals)
            if len(write_tensors) == 1:
                out = (out,)
            if len(out) != len(write_tensors):
                raise ValueError(
                    f"{node.name}: fn returned {len(out)} values for "
                    f"{len(write_tensors)} writes")
            return [self._coerce_write(wt, v, layouts)
                    for wt, v in zip(write_tensors, out)]

        interior = run("interior")
        strip_outs = {
            (k, side): run((d, side))
            for k, (d, _) in enumerate(strips) for side in ("low", "high")}

        for wi, wt in enumerate(write_tensors):
            wt_eff = self._eff_in(wt, layouts)

            def stitch(k: int):
                if k == len(strips):
                    return interior[wi]
                d = strips[k][0]
                return jnp.concatenate(
                    [strip_outs[(k, "low")][wi], stitch(k + 1),
                     strip_outs[(k, "high")][wi]],
                    axis=wt_eff.storage_axis(d))

            with jax.named_scope("halo_stitch"):
                state[wt.name] = stitch(0)

    def _lower_reduce(self, node: Node, state: dict, sharded: bool,
                      layouts: dict[str, Layout]) -> None:
        t, field = node.args
        data = state[t.name]
        if t.is_record and field is not None:
            data = self._eff_in(t, layouts).wrap(data).field(field)
        local = node.reducer.local(data)
        if sharded:
            # in the partition's order: a set's would follow the process's
            # string hashing, and so would the program and its cache key
            axes = tuple(dict.fromkeys(
                ax for ax in t.partition
                if ax is not None and self.mesh.shape[ax] > 1))
            if axes:
                local = _combine_over_axes(local, axes,
                                           node.reducer.combine)
        state[node.result.name] = jnp.asarray(local, dtype=node.result.dtype)

    def _lower_levels(self, levels, state: dict, sharded: bool,
                      layouts: dict[str, Layout]) -> dict:
        state = dict(state)
        for level in levels:
            # paper: nodes on a level are independent -> lower all against the
            # same input snapshot, then merge (XLA runs them in parallel).
            snapshot = dict(state)
            for node in level:
                with jax.named_scope(self._scopes.get(id(node), node.name)):
                    self._lower_node(node, snapshot, state, sharded,
                                     layouts)
        return state

    def _lower_node(self, node: Node, snapshot: dict, state: dict,
                    sharded: bool, layouts: dict[str, Layout]) -> None:
        """Lower one node against the level's input ``snapshot``, merging
        what it writes into ``state``."""
        if node.kind == "split":
            tmp = dict(snapshot)
            self._lower_split(node, tmp, sharded, layouts)
            for k, v in tmp.items():
                if k not in snapshot or v is not snapshot[k]:
                    state[k] = v
        elif node.kind == "reduce":
            tmp = dict(snapshot)
            self._lower_reduce(node, tmp, sharded, layouts)
            state[node.result.name] = tmp[node.result.name]
        elif node.kind == "op":
            tmp = dict(snapshot)
            vals = self._resolve_args(node, tmp, sharded, layouts)
            writes = node.default_writes()
            wt = []
            for i in writes:
                a = node.args[i]
                wt.append(a.tensor if isinstance(a, TensorArg) else a)
            out = node.fn(*vals) if node.fn is not None else None
            if wt:
                self._store_writes(node, tmp, wt, out, layouts)
                for t in wt:
                    state[t.name] = tmp[t.name]
        else:
            raise ValueError(f"unexpected node kind {node.kind}")

    # -- loop (conditional subgraph) lowering --------------------------------
    def _sub_executor(self, i: int) -> "Executor":
        """The sub-Executor of loop segment ``i`` — built ONCE per segment
        and cached (it used to be re-constructed, and its segments
        re-jitted, on every host_loop pass)."""
        sub = self._sub_execs.get(i)
        if sub is None:
            _kind, payload = self._segments[i]
            sub = self._sub_execs[i] = Executor(
                payload, self.mesh, donate=False,
                layout_overrides=self.plan.per_segment[i],
                schedule=self.schedule, async_regions=self.async_regions,
                tile_overrides=self._tile_config)
        return sub

    def _lower_loop(self, sub_graph: Graph, seg: int, state: dict) -> dict:
        """Trace a device ``loop`` segment (a ``lax.while_loop`` over the
        sub-graph's segments) directly into the enclosing program — no
        extra jit wrapper, so a region containing loops is still one
        executable.  The sub-executor must agree with the enclosing plan:
        layouts are loop-invariant inside one compiled while body."""
        sub = self._sub_executor(seg)
        sharded = sub._sharded   # sub-specific: the loop body may be
        # unpartitioned even when the enclosing graph is sharded

        def body_fn(s):
            for k, (kind, payload) in enumerate(sub._segments):
                if kind != "device":
                    raise ValueError("device loop with host segment")
                s = sub._lower_levels(payload, s, sharded,
                                      sub._layouts_for_segment(k))
            return s

        if sharded:
            specs = sub._state_specs(state, sub.plan.initial)

            def shard_body(s):
                # while semantics: predicate gates the FIRST iteration
                # too (an initially-false condition runs nothing)
                return lax.while_loop(sub_graph.condition, body_fn, s)

            fn = jax.shard_map(shard_body, mesh=self.mesh,
                               in_specs=(specs,), out_specs=specs,
                               check_vma=False)
            return fn(state)
        return lax.while_loop(sub_graph.condition, body_fn, state)

    # -- region compiler -----------------------------------------------------
    def _layout_sig(self, layouts: dict[str, Layout]) -> tuple:
        return tuple(sorted((n, lay.name) for n, lay in layouts.items()))

    def _segment_chain(self, seg_indices, entry_layouts: dict[str, Layout]):
        """Static layout evolution through a run of segments: per segment
        the boundary conversions to trace and the full layout assignment
        its body is lowered under; plus the exit layouts."""
        current = dict(entry_layouts)
        chain = []
        for si in seg_indices:
            targets = self.plan.per_segment[si]
            conv = [(n, current[n], lay)
                    for n, lay in sorted(targets.items())
                    if current[n] is not lay]
            current.update(targets)
            chain.append((si, conv, dict(current)))
        return chain, current

    def _traced_convert(self, state: dict, conv, layouts) -> dict:
        """Apply boundary relayouts INSIDE a trace (pure ops; the sharding
        constraint mirrors what the eager path's device_put enforced)."""
        for name, src, dst in conv:
            t = self.tensors[name]
            with jax.named_scope("relayout"):
                data = relayout_data(state[name], t.spec, src, dst)
                if self.mesh is not None:
                    data = lax.with_sharding_constraint(
                        data, self._eff_in(t, layouts).sharding(self.mesh))
            state[name] = data
        return state

    def _state_split(self, written, entry_layouts, exit_layouts):
        """``(donated, read_only)`` state keys of one executable.

        Only keys whose storage shape is stable across it (same layout at
        entry and exit) can be aliased.  Of those, a key the executable
        never writes is read-only: it is passed in but neither donated
        nor returned, so the caller keeps its buffer and XLA copies
        nothing (returning an undonated input would copy it on every
        call — a model's weights, for one).  The other stable keys are
        donated, and jax never warns about unusable donations."""
        stable = frozenset(
            k for k in list(self.tensors) + list(self.results)
            if k not in entry_layouts
            or entry_layouts[k] is exit_layouts.get(k, entry_layouts[k]))
        read_only = frozenset(k for k in stable if k not in written)
        return stable - read_only, read_only

    @staticmethod
    def _split_state(state: dict, donate_keys, read_only) -> tuple:
        """``state`` as the (donated, kept, read-only) executable args."""
        parts = ({}, {}, {})
        for k, v in state.items():
            parts[0 if k in donate_keys else 2 if k in read_only
                  else 1][k] = v
        return parts

    def _fetch(self, key, build: Callable) -> Callable:
        """One executable from the plan-wide cache, building on miss.
        A fetch that finds an executable this instance never requested
        counts as a reuse hit (the re-instantiated-executor path)."""
        fn = self._cache.executables.get(key)
        if fn is None:
            fn = self._cache.executables[key] = build()
            self._cache.builds += 1
        elif key not in self._fetched:
            self._cache.hits += 1
        self._fetched.add(key)
        return fn

    def _build_region_fn(self, region: Region,
                         entry_layouts: dict[str, Layout]) -> Callable:
        """Lower one device region to a single jitted executable: for each
        segment in the run, the boundary relayouts (traced, not eagerly
        dispatched) then the segment body — device levels under one
        shard_map, loop segments as inlined while_loops."""
        chain, exit_layouts = self._segment_chain(region.segments,
                                                  entry_layouts)
        donate_keys, read_only = self._state_split(
            self._region_access[region.index][1], entry_layouts,
            exit_layouts)
        cache_entry = self._cache
        sharded = self._sharded

        def region_call(donated, kept, consts):
            cache_entry.trace_events += 1   # Python body runs per trace only
            with tracing.span("ripple.trace", graph=self.graph.name,
                              region=region.index):
                state = {**donated, **kept, **consts}
                for si, conv, layouts in chain:
                    state = self._traced_convert(dict(state), conv, layouts)
                    kind, payload = self._segments[si]
                    if kind == "device":
                        if sharded:
                            specs = self._state_specs(state, layouts)
                            fn = jax.shard_map(
                                partial(self._lower_levels, payload,
                                        sharded=True, layouts=layouts),
                                mesh=self.mesh, in_specs=(specs,),
                                out_specs=specs, check_vma=False)
                            state = fn(state)
                        else:
                            state = self._lower_levels(payload, state, False,
                                                       layouts)
                    else:  # 'loop'
                        state = self._lower_loop(payload, si, state)
                return {k: v for k, v in state.items() if k not in read_only}

        region_call.__name__ = f"{self._exe_name}_region{region.index}"
        jfn = jax.jit(region_call,
                      donate_argnums=(0,) if self.donate else ())
        tile_config = self._tile_config

        def invoke(state):
            args = self._split_state(state, donate_keys, read_only)
            # the (tuned) tile config only matters while the body traces;
            # steady-state calls hit the jit cache and never read it
            with tile_scope(tile_config):
                return {**args[2], **jfn(*args)}

        invoke.jit_fn = jfn
        invoke.donate_keys = donate_keys
        invoke.read_only = read_only
        invoke.exit_layouts = exit_layouts
        return invoke

    def _region_executable(self, region: Region):
        """The compiled executable for a region at the CURRENT entry
        layouts (cached process-wide), plus its exit layouts."""
        entry = {n: self._state_layouts[n] for n in self.plan.initial}
        key = ("region", region.index, self._layout_sig(entry))
        fn = self._fetch(key, lambda: self._build_region_fn(region, entry))
        return fn, fn.exit_layouts

    def region_hlo(self, state: dict, index: int = 0) -> str:
        """Compiled HLO text of a device region's executable for ``state``
        (benchmark/analysis introspection; reuses the jit cache)."""
        region = self._regions[index]
        if region.kind != "device":
            raise ValueError(f"region {index} is {region.kind!r}, "
                             f"not a device region")
        fn, _ = self._region_executable(region)
        args = self._split_state(state, fn.donate_keys, fn.read_only)
        with tile_scope(self._tile_config):
            return fn.jit_fn.lower(*args).compile().as_text()

    # -- public execution -----------------------------------------------------
    @contextmanager
    def _layout_epoch(self):
        """Invariant bracket: incoming states are in the plan's initial
        layouts, and whatever happens inside (including an exception),
        the bookkeeping ends at initial again — any state the caller
        still holds outside a call is in the initial layouts."""
        self._state_layouts = dict(self.plan.initial)
        try:
            yield
        finally:
            self._state_layouts = dict(self.plan.initial)

    @contextmanager
    def _epoch(self, settle: Optional[Callable[[], Any]] = None):
        """One dispatcher epoch around a call's passes.  It yields the
        call's ``_AsyncRun``: a fresh one when the event-driven runtime
        is on and a host region exists to overlap, else None (the passes
        run synchronously).  On success it drains the pooled callbacks
        under ``ripple.host_wait`` (reason ``drain``: the first callback
        exception re-raises there) and counts a clean pass; on any
        exception it aborts the callbacks, records the failure for the
        ladder and re-raises.  Where callbacks are in flight, ``settle``
        returns the state the device must finish first, so that the
        drain holds only host work the device's steps did not hide."""
        with self._layout_epoch():
            overlap = self.async_regions and any(
                r.kind == "host" for r in self._regions)
            ctx = _AsyncRun(self.donate, self.host_timeout) if overlap \
                else None
            try:
                yield ctx
                if ctx is not None:
                    if settle is not None and ctx.tasks:
                        jax.block_until_ready(settle())
                    with tracing.span("ripple.host_wait", reason="drain"):
                        ctx.drain()
            except BaseException as exc:
                if ctx is not None:
                    ctx.abort()
                self.record_failure(exc)
                raise
            self._note_clean_pass()

    def __call__(self, state: dict) -> dict:
        """One pass over the region schedule: pass 0 of a ``run()``, so
        every periodic host node is due."""
        return self._traced_run(state, 1, "regions")

    def _run_regions_once(self, state: dict,
                          ctx: Optional[_AsyncRun] = None,
                          index: int = 0) -> dict:
        """Pass ``index`` of a call over the region schedule (a periodic
        host node runs only where it is due): each device region is ONE
        cached executable call (its relayouts and halo glue run inside
        the trace); host work runs eagerly between regions.  Layout
        bookkeeping is runtime-driven, so repeated passes re-dispatch
        nothing when consecutive iterations agree on layout.

        With a dispatcher context (``async_regions=True``) the pass is
        event-driven: device regions are issued without any
        ``block_until_ready`` (the device stream serializes them through
        their data dependencies), non-barrier host regions become pooled
        futures that block only on their OWN argument arrays, and only
        barrier/host_loop regions drain the in-flight callbacks.
        Device dispatch order is program order either way, so results
        are bitwise identical to the synchronous path."""
        for region in self._regions:
            if ctx is not None:
                ctx.check()
            if region.kind == "device":
                # trips BEFORE the executable call: the caller's state
                # dict is never half-donated, so a retry is safe
                _fault_trip("executor.region",
                            detail=f"region{region.index}")
                fn, exit_layouts = self._region_executable(region)
                with tracing.span("ripple.dispatch", graph=self.graph.name,
                                  region=region.index):
                    state = fn(state)
                self._state_layouts.update(exit_layouts)
            elif region.kind == "host":
                si = region.start
                node: Node = self._segments[si][1]
                if node.every is not None and index % node.every:
                    continue
                state = self._apply_segment_layouts(dict(state), si)
                self._host_node(region.index, node, state, ctx)
            else:  # host_loop
                si = region.start
                state = self._apply_segment_layouts(dict(state), si)
                if ctx is not None:
                    # the sub-executor writes state eagerly
                    with tracing.span("ripple.host_wait", reason="host_loop"):
                        ctx.drain()
                sub_graph: Graph = self._segments[si][1]
                sub = self._sub_executor(si)
                # while semantics: check before the first iteration too
                while _read_condition(sub_graph, state):
                    state = sub(state)
        return state

    def _host_node(self, region_index: int, node: Node, state: dict,
                   ctx: Optional[_AsyncRun]) -> None:
        """Run one host region's node on ``state``: submitted to the
        pool (``ctx``, unless the region is a barrier), or inline once
        the device has finished what ``state`` holds."""
        label = self._scopes.get(id(node), node.name)
        if ctx is not None and not self._region_access[region_index][2]:
            vals = self._resolve_args(
                node, state, False, self._state_layouts) \
                if node.args else []
            ctx.submit(region_index, node.fn, vals, label)
            return
        with tracing.span("ripple.host_wait", reason="barrier"):
            if ctx is not None:
                ctx.drain()   # barrier: side-effect order vs pool
            jax.block_until_ready(jax.tree_util.tree_leaves(state))
        _fault_trip("executor.host", detail=f"region{region_index}")
        if node.fn is not None:
            vals = self._resolve_args(
                node, state, False, self._state_layouts) \
                if node.args else []
            with tracing.span("ripple.host", region=region_index,
                              node=label):
                node.fn(*vals)

    def run(self, state: dict, steps: int) -> dict:
        """Execute the whole graph ``steps`` times (graphs are built once,
        executed many — paper §5.3).  Device-only graphs without a
        condition run as one fori_loop with ``steps`` a DYNAMIC argument
        (distinct step counts share a single trace).  Graphs whose host
        nodes are all leading periodic ones (``every=k``) run the same
        fori_loop in chunks between the passes where a node is due
        (``fused+host``).  Everything else loops over the cached region
        executables, pass by pass."""
        if steps <= 0:
            return state
        # the scheduler owns the fusability decision: only a DAG whose
        # vertices are device nodes and leading periodic host nodes
        # lowers its passes to device code, whatever the schedule mode
        # (any other host node must run between jit calls every step,
        # so it breaks the fori fusion)
        hosts = self._periodic_hosts
        if self.graph.condition is not None or hosts is None:
            return self._traced_run(state, steps, "regions")
        return self._traced_run(state, steps,
                                "fused+host" if hosts else "fused")

    def _traced_run(self, state: dict, steps: int, path: str) -> dict:
        """``steps`` passes along ``path`` under the ``ripple.run`` span."""
        with tracing.span("ripple.run", new_run=True, graph=self.graph.name,
                          steps=steps, path=path):
            if path == "regions":
                state = self._run_passes(state, steps)
            else:
                state = self._run_fused(state, steps)
            tracing.count("halo.bytes", self._halo_pass_bytes * steps)
            return state

    def _run_passes(self, state: dict, steps: int) -> dict:
        """``steps`` passes over the region schedule."""
        with self._epoch() as ctx:
            for i in range(steps):
                state = self._run_regions_once(dict(state), ctx, i)
            return self._restore_initial_layouts(dict(state))

    def _build_fused_fn(self, entry_layouts: dict[str, Layout]) -> Callable:
        """Fused path executable: entry relayouts traced up front, then
        the device segments' levels inside one fori_loop whose trip
        count is a runtime argument — NOT closed over, so ``run(s, 3)``
        and ``run(s, 1000)`` share one trace.  (Device-only graphs have a
        single segment, so layouts are loop-invariant by construction; a
        graph with periodic host nodes takes this path only where no
        segment converts a layout, and its host segments run between
        calls.)"""
        current = dict(entry_layouts)
        convs = []
        for si, (kind, _) in enumerate(self._segments):
            if kind != "device":
                continue
            for n, lay in sorted(self.plan.per_segment[si].items()):
                if current[n] is not lay:
                    convs.append((n, current[n], lay))
                    current[n] = lay
        body_layouts = dict(current)
        levels = [lv for kind, seg in self._segments if kind == "device"
                  for lv in seg]
        donate_keys, read_only = self._state_split(
            schedule_lib.graph_access(self.graph)[1], entry_layouts,
            body_layouts)
        cache_entry = self._cache
        sharded = self._sharded

        def call(donated, kept, consts, steps):
            cache_entry.trace_events += 1
            with tracing.span("ripple.trace", graph=self.graph.name,
                              region="fused"):
                state = self._traced_convert({**donated, **kept}, convs,
                                             body_layouts)

                def loop(st, cs, n):
                    # read-only entries ride outside the loop carry
                    def body(_, s):
                        out = self._lower_levels(levels, {**s, **cs},
                                                 sharded, body_layouts)
                        return {k: out[k] for k in s}
                    return lax.fori_loop(0, n, body, st)

                if sharded:
                    fn = jax.shard_map(
                        loop, mesh=self.mesh,
                        in_specs=(self._state_specs(state, body_layouts),
                                  self._state_specs(consts, body_layouts),
                                  P()),
                        out_specs=self._state_specs(state, body_layouts),
                        check_vma=False)
                    return fn(state, consts, steps)
                return loop(state, consts, steps)

        call.__name__ = f"{self._exe_name}_fused"
        jfn = jax.jit(call, donate_argnums=(0,) if self.donate else ())
        tile_config = self._tile_config

        def invoke(state, steps):
            args = self._split_state(state, donate_keys, read_only)
            with tile_scope(tile_config):
                return {**args[2],
                        **jfn(*args, jnp.asarray(steps, jnp.int32))}

        invoke.jit_fn = jfn
        invoke.donate_keys = donate_keys
        invoke.read_only = read_only
        invoke.exit_layouts = body_layouts
        return invoke

    def _run_fused(self, state: dict, steps: int) -> dict:
        """The fused path: the steps in jitted fori_loops of one
        executable, cached by plan signature + entry layouts.  A
        device-only graph makes one call.  With leading periodic host
        nodes, each pass where one is due starts a chunk: the due nodes
        get the state as it stands between chunks (submitted to the pool
        with one double-buffer copy under donation, or run inline), then
        one call runs the chunk's steps.  Before a due pass's copies, the
        dispatcher waits for the callbacks of the due passes before the
        previous one (the ``slot`` wait), so the snapshots in device
        memory are those of two due passes at most, however long the
        call, and a callback has a whole chunk's time beyond its own
        before it holds the device back.  The last callbacks drain at the
        end, as on the pass-by-pass path, once the device has run the
        last chunk: what the drain's ``ripple.host_wait`` then holds is
        host work that the device's steps did not hide."""
        hosts = self._periodic_hosts
        due = schedule_lib.due_passes([n.every for _, n in hosts], steps)
        if not due or due[0][0]:
            due.insert(0, (0, []))
        # the epoch waits for the device to finish the state this
        # returns (``state`` as it stands then) before the drain
        with self._epoch(settle=lambda: state) as ctx:
            _fault_trip("executor.region", detail="fused")
            entry = dict(self._state_layouts)
            key = ("fused", self._layout_sig(entry))
            fn = self._fetch(key, lambda: self._build_fused_fn(entry))
            ends = [i for i, _ in due[1:]] + [steps]
            last = 0    # pooled tasks of the previous due pass
            for (start, js), end in zip(due, ends):
                if js and ctx is not None:
                    # two due passes' copies alive at most: the callbacks
                    # before the previous due pass had a chunk's time to end
                    ctx.hold(last)
                    last = len(js)
                for j in js:
                    self._host_node(*hosts[j], state, ctx)
                with tracing.span("ripple.dispatch",
                                  graph=self.graph.name, region="fused"):
                    state = fn(dict(state), end - start)
            self._state_layouts.update(fn.exit_layouts)
            state = self._restore_initial_layouts(dict(state))
            return state


def _read_condition(graph: Graph, state: dict) -> bool:
    """A host loop's condition, read back to the host (the dispatcher
    waits for the device here)."""
    with tracing.span("ripple.host_wait", reason="condition"):
        return bool(jax.device_get(graph.condition(state)))


def execute(graph: Graph, mesh: Optional[Mesh] = None, steps: int = 1,
            **state_overrides) -> dict:
    """One-shot convenience: init state, run, return final state."""
    ex = Executor(graph, mesh)
    state = ex.init_state(**state_overrides)
    return ex.run(state, steps) if steps != 1 else ex(state)
