"""Graph builder (paper §5.3): emplace / then / split / then_split /
reduce / then_reduce / conditional / sync / subgraphs + access modifiers.

A :class:`Graph` records *levels* of :class:`Node` s — the paper's DAG where
a level contains nodes that may execute in parallel and each level depends
on the previous one.  Per-partition node splitting (the paper's ``split``
creating one node per tensor partition) is realized by SPMD: the executor
lowers the level once and every shard runs it, so the paper's parallel
kernel submission is implicit (DESIGN.md §2).

Access modifiers communicate *how a kernel touches halo data*, which is
exactly the information the paper uses to minimize graph connectivity:

* plain tensor arg                      — no halo read (paper's default);
* ``concurrent_padded_access(t)``       — reads halo, writes a different
  buffer: halo exchange may overlap the kernel's interior compute
  (``overlap=True`` on split nodes enables it for any number of
  mesh-partitioned halo axes — 2-D/3-D decompositions included);
* ``exclusive_padded_access(t)``        — reads halo of a buffer the kernel
  itself updates: the pre-update halo must be captured first (ordering edge);
* ``*_in_shared(t)``                    — additionally stage blocks in VMEM
  (TPU's shared memory) via the Pallas path of the kernel.
"""

from __future__ import annotations

import enum
import itertools
from collections import Counter
from dataclasses import dataclass, field as dfield
from typing import Any, Callable, Optional, Sequence, Union

from .layout import Layout
from .tensor import DistTensor, ReductionResult

__all__ = [
    "ExecutionKind",
    "AccessMode",
    "TensorArg",
    "preferred_layout",
    "concurrent_padded_access",
    "exclusive_padded_access",
    "in_shared",
    "concurrent_padded_access_in_shared",
    "exclusive_padded_access_in_shared",
    "Reducer",
    "SumReducer",
    "MaxReducer",
    "MinReducer",
    "MulReducer",
    "AndReducer",
    "OrReducer",
    "XorReducer",
    "MinimumReducer",
    "MaximumReducer",
    "Node",
    "Graph",
]

_node_counter = itertools.count()


class ExecutionKind(enum.Enum):
    Cpu = "cpu"  # host-executed (outside jit) — heterogeneous nodes
    Gpu = "gpu"  # device-executed (jit/shard_map); TPU in production


class AccessMode(enum.Enum):
    DEFAULT = "default"
    CONCURRENT_PADDED = "concurrent_padded"
    EXCLUSIVE_PADDED = "exclusive_padded"
    SHARED = "shared"
    CONCURRENT_PADDED_SHARED = "concurrent_padded_shared"
    EXCLUSIVE_PADDED_SHARED = "exclusive_padded_shared"

    @property
    def padded(self) -> bool:
        return self in (
            AccessMode.CONCURRENT_PADDED,
            AccessMode.EXCLUSIVE_PADDED,
            AccessMode.CONCURRENT_PADDED_SHARED,
            AccessMode.EXCLUSIVE_PADDED_SHARED,
        )

    @property
    def exclusive(self) -> bool:
        return self in (
            AccessMode.EXCLUSIVE_PADDED,
            AccessMode.EXCLUSIVE_PADDED_SHARED,
        )

    @property
    def shared(self) -> bool:
        return self in (
            AccessMode.SHARED,
            AccessMode.CONCURRENT_PADDED_SHARED,
            AccessMode.EXCLUSIVE_PADDED_SHARED,
        )


@dataclass(frozen=True)
class TensorArg:
    tensor: DistTensor
    mode: AccessMode = AccessMode.DEFAULT
    layout: Optional[Layout] = None  # kernel's preferred layout (solver hint)


def preferred_layout(t: DistTensor | TensorArg,
                     layout: Layout) -> TensorArg:
    """Annotate an argument with the kernel's preferred layout.

    A *hint*, not a pin: the executor's layout solver honors it unless a
    stronger constraint (user ``pin_layout`` or a padded-access
    requirement) overrides it."""
    if isinstance(t, TensorArg):
        return TensorArg(t.tensor, t.mode, layout)
    return TensorArg(t, AccessMode.DEFAULT, layout)


def concurrent_padded_access(t: DistTensor) -> TensorArg:
    """Mark ``t`` as read *including its halo*, written elsewhere.

    The defining access mode of a double-buffered stencil: because the
    node writes a different buffer, the halo exchange may overlap the
    kernel's interior compute (``g.split(..., overlap=True)``).

    Example::

        g.split(laplace, concurrent_padded_access(src), dst, overlap=True)
    """
    return TensorArg(t, AccessMode.CONCURRENT_PADDED)


def exclusive_padded_access(t: DistTensor) -> TensorArg:
    """Mark ``t`` as read including its halo by a node that ALSO updates
    ``t`` in place (paper Fig. 9): the pre-update halo must be captured
    before the write, so the executor threads it as an extra data
    dependency instead of overlapping it.

    Example::

        g.split(fim_sweep, exclusive_padded_access(phi), mask, writes=(0,))
    """
    return TensorArg(t, AccessMode.EXCLUSIVE_PADDED)


def in_shared(t: DistTensor) -> TensorArg:
    """Mark ``t`` for staging through shared memory (VMEM on TPU): the
    kernel's Pallas path DMAs each block into the fast on-chip space
    before computing (paper's ``in_shared()``).  Example:
    ``g.split(kern, in_shared(u), out)``."""
    return TensorArg(t, AccessMode.SHARED)


def concurrent_padded_access_in_shared(t: DistTensor) -> TensorArg:
    """:func:`concurrent_padded_access` + :func:`in_shared`: halo read of
    a separately-written buffer, blocks staged in VMEM (the paper's
    combined modifier, e.g. the FORCE stencil's winning config)."""
    return TensorArg(t, AccessMode.CONCURRENT_PADDED_SHARED)


def exclusive_padded_access_in_shared(t: DistTensor) -> TensorArg:
    """:func:`exclusive_padded_access` + :func:`in_shared`: in-place halo
    read with VMEM staging (the eikonal FIM kernel's configuration)."""
    return TensorArg(t, AccessMode.EXCLUSIVE_PADDED_SHARED)


@dataclass(frozen=True)
class Reducer:
    """Local reduction + cross-shard combiner pair."""

    name: str
    local: Callable  # array -> scalar
    combine: str     # 'add'|'mul'|'max'|'min'|'and'|'or'|'xor'|
                     # 'minimum'|'maximum' (executor picks lax.p* or
                     # all_gather+fold)


def SumReducer() -> Reducer:  # noqa: N802 - mirrors paper naming
    """Sum reduction: ``jnp.sum`` per shard + ``lax.psum`` across shards.
    Example: ``g.then_reduce(t, total, SumReducer())``."""
    import jax.numpy as jnp

    return Reducer("sum", jnp.sum, "add")


def _nan_ignoring(reduce_all, reduce_nan):
    """Per the Ripple spec NaN table, ``min``/``max`` return the NUMBER
    when one operand is a quiet NaN — i.e. quiet NaNs are ignored (the
    all-NaN slice still reduces to qNaN)."""
    import jax.numpy as jnp

    def local(x):
        x = jnp.asarray(x)
        if jnp.issubdtype(x.dtype, jnp.floating):
            return reduce_nan(x)
        return reduce_all(x)

    return local


def _nan_propagating(reduce_all):
    """``minimum``/``maximum`` semantics: any quiet NaN operand makes the
    whole reduction qNaN (spec: NUM vs qNaN -> qNaN)."""
    import jax.numpy as jnp

    def local(x):
        x = jnp.asarray(x)
        m = reduce_all(x)
        if jnp.issubdtype(x.dtype, jnp.floating):
            m = jnp.where(jnp.isnan(x).any(),
                          jnp.asarray(jnp.nan, m.dtype), m)
        return m

    return local


def MaxReducer() -> Reducer:  # noqa: N802
    """Max reduction: NaN-ignoring ``max`` per shard (spec: NUM vs qNaN ->
    NUM) + ``lax.pmax`` across shards (e.g. the Euler wavespeed CFL
    bound).  For the NaN-propagating variant use :func:`MaximumReducer`."""
    import jax.numpy as jnp

    return Reducer("max", _nan_ignoring(jnp.max, jnp.nanmax), "max")


def MinReducer() -> Reducer:  # noqa: N802
    """Min reduction: NaN-ignoring ``min`` per shard + ``lax.pmin`` across
    shards.  For the NaN-propagating variant use :func:`MinimumReducer`."""
    import jax.numpy as jnp

    return Reducer("min", _nan_ignoring(jnp.min, jnp.nanmin), "min")


def MulReducer() -> Reducer:  # noqa: N802
    """Product reduction: ``jnp.prod`` per shard; cross-shard combine is an
    all-gather of the per-shard scalars + local fold (no ``lax.pprod``
    exists, and the log-sum trick is wrong for zeros/negatives)."""
    import jax.numpy as jnp

    return Reducer("mul", jnp.prod, "mul")


def AndReducer() -> Reducer:  # noqa: N802
    """Bitwise/logical AND reduction over integer or boolean records
    (e.g. "did every cell converge" flags); all_gather+fold combine."""
    import jax.numpy as jnp

    def local(x):
        x = jnp.asarray(x)
        init = ~jnp.zeros((), x.dtype)  # all-ones identity (True for bool)
        from jax import lax as _lax
        return _lax.reduce(x, init, _lax.bitwise_and, tuple(range(x.ndim)))

    return Reducer("and", local, "and")


def OrReducer() -> Reducer:  # noqa: N802
    """Bitwise/logical OR reduction (e.g. "did any cell hit the boundary"
    flags); all_gather+fold combine."""
    import jax.numpy as jnp

    def local(x):
        x = jnp.asarray(x)
        from jax import lax as _lax
        return _lax.reduce(x, jnp.zeros((), x.dtype), _lax.bitwise_or,
                           tuple(range(x.ndim)))

    return Reducer("or", local, "or")


def XorReducer() -> Reducer:  # noqa: N802
    """Bitwise XOR reduction (parity / checksum-style reductions);
    all_gather+fold combine."""
    import jax.numpy as jnp

    def local(x):
        x = jnp.asarray(x)
        from jax import lax as _lax
        return _lax.reduce(x, jnp.zeros((), x.dtype), _lax.bitwise_xor,
                           tuple(range(x.ndim)))

    return Reducer("xor", local, "xor")


def MinimumReducer() -> Reducer:  # noqa: N802
    """NaN-PROPAGATING min (spec ``minimum``: NUM vs qNaN -> qNaN), the
    float-only companion of :func:`MinReducer`."""
    import jax.numpy as jnp

    return Reducer("minimum", _nan_propagating(jnp.min), "minimum")


def MaximumReducer() -> Reducer:  # noqa: N802
    """NaN-PROPAGATING max (spec ``maximum``: NUM vs qNaN -> qNaN), the
    float-only companion of :func:`MaxReducer`."""
    import jax.numpy as jnp

    return Reducer("maximum", _nan_propagating(jnp.max), "maximum")


NodeArg = Union[DistTensor, TensorArg, ReductionResult, Any]


@dataclass
class Node:
    kind: str                      # 'op' | 'split' | 'reduce' | 'sync' | 'loop'
    fn: Optional[Callable] = None
    args: tuple = ()
    writes: Optional[tuple[int, ...]] = None  # arg indices the fn returns
    exec_kind: ExecutionKind = ExecutionKind.Gpu
    reducer: Optional[Reducer] = None
    result: Optional[ReductionResult] = None
    overlap: bool = False          # interior/boundary comm-compute overlap
    subgraph: Optional["Graph"] = None
    every: Optional[int] = None    # host node's period in passes (None: each)
    name: str = dfield(default_factory=lambda: f"node{next(_node_counter)}")

    def tensor_args(self):
        for i, a in enumerate(self.args):
            if isinstance(a, TensorArg):
                yield i, a.tensor, a.mode
            elif isinstance(a, DistTensor):
                yield i, a, AccessMode.DEFAULT

    def default_writes(self) -> tuple[int, ...]:
        """Paper convention for split nodes: the last tensor argument is the
        output (saxpy: (a, x, y) writes y; double-buffered stencils:
        (in, out) writes out)."""
        if self.writes is not None:
            return self.writes
        tidx = [i for i, _, _ in self.tensor_args()]
        return (tidx[-1],) if tidx else ()


class Graph:
    """Builder for a level-structured DAG (paper Listings 5-12)."""

    def __init__(self, default_exec: ExecutionKind = ExecutionKind.Gpu,
                 name: str = "graph"):
        self.default_exec = default_exec
        self.name = name
        self.levels: list[list[Node]] = []
        self.condition: Optional[Callable] = None  # state -> bool array

    # -- internals ---------------------------------------------------------
    def _current_level(self) -> list[Node]:
        if not self.levels:
            self.levels.append([])
        return self.levels[-1]

    def _new_level(self) -> list[Node]:
        if not self.levels or self.levels[-1]:
            self.levels.append([])
        return self.levels[-1]

    def _exec(self, kind: Optional[ExecutionKind]) -> ExecutionKind:
        return kind if kind is not None else self.default_exec

    @staticmethod
    def _hint_args(args: tuple, layout: Optional[Layout]) -> tuple:
        """Apply a node-level ``layout=`` preference to record tensor args
        that don't already carry their own hint."""
        if layout is None:
            return args
        out = []
        for a in args:
            if isinstance(a, TensorArg) and a.layout is None \
                    and a.tensor.is_record:
                a = TensorArg(a.tensor, a.mode, layout)
            elif isinstance(a, DistTensor) and a.is_record:
                a = TensorArg(a, AccessMode.DEFAULT, layout)
            out.append(a)
        return tuple(out)

    def _period(self, every: Optional[int],
                exec_kind: Optional[ExecutionKind]) -> Optional[int]:
        """Check a node's ``every=``: a whole number of passes, >= 1, on a
        host (Cpu) node only."""
        if every is None:
            return None
        if self._exec(exec_kind) is not ExecutionKind.Cpu:
            raise ValueError("every= is a host node's period: give it "
                             "with exec_kind=ExecutionKind.Cpu")
        if isinstance(every, bool) or not isinstance(every, int) \
                or every < 1:
            raise ValueError(f"every must be a whole number of passes "
                             f">= 1, got {every!r}")
        return every

    def _add(self, level: list[Node], item, exec_kind, every=None,
             **kw) -> None:
        if isinstance(item, Graph):
            if every is not None:
                raise ValueError("every= applies to host nodes, not to "
                                 "subgraphs")
            level.append(Node(kind="loop" if item.condition else "subgraph",
                              subgraph=item,
                              exec_kind=self._exec(exec_kind)))
        else:
            level.append(Node(fn=item, exec_kind=self._exec(exec_kind),
                              every=self._period(every, exec_kind), **kw))

    # -- paper API -----------------------------------------------------------
    def emplace(self, *items, exec_kind: Optional[ExecutionKind] = None,
                layout: Optional[Layout] = None, every: Optional[int] = None,
                **kw) -> "Graph":
        """Add node(s)/subgraph(s) to the *current* level (parallel).

        ``layout=`` marks every record tensor in ``args`` with the node's
        preferred layout (a solver hint, see ``core/executor.py``).
        ``every=k`` gives a host node a period: it runs on pass ``i`` of
        a ``run()`` where ``i % k == 0`` (passes 0, k, 2k, ...) and
        nowhere else.  Example, a snapshot every 100 steps::

            g.emplace(save, exec_kind=ExecutionKind.Cpu, args=(u,),
                      every=100)
        """
        if "args" in kw:
            kw["args"] = self._hint_args(tuple(kw["args"]), layout)
        level = self._current_level()
        for item in items:
            self._add(level, item, exec_kind, every, kind="op", **kw)
        return self

    def then(self, *items, exec_kind: Optional[ExecutionKind] = None,
             layout: Optional[Layout] = None, every: Optional[int] = None,
             **kw) -> "Graph":
        """Add node(s)/subgraph(s) on a *new* level (sequential dep);
        ``every=`` as in :meth:`emplace`."""
        if "args" in kw:
            kw["args"] = self._hint_args(tuple(kw["args"]), layout)
        level = self._new_level()
        for item in items:
            self._add(level, item, exec_kind, every, kind="op", **kw)
        return self

    def split(self, fn: Callable, *args: NodeArg,
              writes: Optional[Sequence[int]] = None,
              exec_kind: Optional[ExecutionKind] = None,
              overlap: bool = False,
              layout: Optional[Layout] = None,
              every: Optional[int] = None) -> "Graph":
        """Tensor op on the current level; becomes one node per partition
        (paper §5.3.3) — here: SPMD over the tensor's mesh axes.
        ``every=`` gives a host split its period, as in :meth:`emplace`.

        ``overlap=True`` asks for the interior/boundary lowering: the
        padded args' halo transfers (all partitioned axes, corners
        included) fly while the interior program runs.  ``fn`` must then
        be a shape-polymorphic stencil (``m + 2w -> m`` cells along every
        haloed dim).  Declined requests are recorded in
        ``Executor.plan.overlap_fallbacks`` (and warn once when real
        transfers were degraded)."""
        self._current_level().append(
            Node(kind="split", fn=fn, args=self._hint_args(args, layout),
                 writes=None if writes is None else tuple(writes),
                 exec_kind=self._exec(exec_kind), overlap=overlap,
                 every=self._period(every, exec_kind)))
        return self

    def then_split(self, fn: Callable, *args: NodeArg,
                   writes: Optional[Sequence[int]] = None,
                   exec_kind: Optional[ExecutionKind] = None,
                   overlap: bool = False,
                   layout: Optional[Layout] = None,
                   every: Optional[int] = None) -> "Graph":
        """:meth:`split` on a *new* level (sequential dependency on the
        current one)."""
        self._new_level()
        return self.split(fn, *args, writes=writes, exec_kind=exec_kind,
                          overlap=overlap, layout=layout, every=every)

    def reduce(self, tensor: DistTensor, result: ReductionResult,
               reducer: Reducer, field: Optional[str] = None) -> "Graph":
        """Reduce ``tensor`` (or one record ``field`` of it) into the
        ``result`` slot on the current level (paper Listing 8):
        ``reducer.local`` per shard, ``lax.p*`` across the mesh.

        Example::

            total = make_reduction_result("total")
            g.then_reduce(t, total, SumReducer())   # state["total"]
        """
        self._current_level().append(
            Node(kind="reduce", args=(tensor, field), reducer=reducer,
                 result=result, exec_kind=ExecutionKind.Gpu))
        return self

    def then_reduce(self, tensor: DistTensor, result: ReductionResult,
                    reducer: Reducer, field: Optional[str] = None) -> "Graph":
        """:meth:`reduce` on a *new* level (sequential dependency)."""
        self._new_level()
        return self.reduce(tensor, result, reducer, field)

    def sync(self, fn: Optional[Callable] = None) -> "Graph":
        """Full barrier: all pending device work completes, then ``fn`` runs
        on the host (paper §5.3.4)."""
        self._new_level().append(Node(kind="sync", fn=fn,
                                      exec_kind=ExecutionKind.Cpu))
        self._new_level()
        return self

    def conditional(self, pred: Callable) -> "Graph":
        """Execute this graph while ``pred(state)`` is true (paper §5.3.6,
        cf. Listing 9's map-reduce loop).  Proper *while* semantics: the
        predicate gates the first iteration too, so a graph entered with a
        false condition runs zero times."""
        self.condition = pred
        return self

    # -- introspection ---------------------------------------------------------
    def nodes(self):
        """Every node in builder (program) order, levels flattened."""
        for level in self.levels:
            yield from level

    def all_tensors(self) -> dict[str, DistTensor]:
        """Every :class:`DistTensor` the graph touches, by name
        (subgraphs included).  Two accesses of one name must agree on
        storage (space/layout/partition); halo may differ per access."""
        out: dict[str, DistTensor] = {}
        for node in self.nodes():
            if node.subgraph is not None:
                out.update(node.subgraph.all_tensors())
                continue
            for _, t, _ in node.tensor_args():
                prev = out.get(t.name)
                if prev is not None and prev.storage_key() != t.storage_key():
                    raise ValueError(
                        f"tensor name {t.name!r} bound to two different "
                        f"storages (halo/boundary may differ per access; "
                        f"space/layout/partition may not)")
                out[t.name] = t
        return out

    def all_results(self) -> dict[str, ReductionResult]:
        """Every reduction-result slot the graph writes, by name."""
        out: dict[str, ReductionResult] = {}
        for node in self.nodes():
            if node.subgraph is not None:
                out.update(node.subgraph.all_results())
            if node.result is not None:
                out[node.result.name] = node.result
        return out

    def is_device_only(self) -> bool:
        """True when no node needs the host (no ``sync()``, no Cpu
        nodes) — the whole graph can trace into one jitted program."""
        for node in self.nodes():
            if node.kind == "sync":
                return False
            if node.subgraph is not None and not node.subgraph.is_device_only():
                return False
            if node.exec_kind is ExecutionKind.Cpu and node.kind != "subgraph":
                return False
        return True

    def node_scopes(self) -> dict[int, str]:
        """``<graph name>/<label>`` for every node, keyed by ``id(node)``:
        the name scope the executor lowers the node under, so a device
        op's metadata names its node (``euler_step/update_x``).  The label
        is the node function's ``__name__``, or ``reduce:<result>`` for a
        reduction, with ``[<level>]`` added where labels tie (XLA reads
        an ``@`` in a scope as a separator); a subgraph's nodes are scoped
        under that subgraph's own name."""
        labels = []
        for i, level in enumerate(self.levels):
            for n in level:
                if n.subgraph is not None:
                    continue
                if n.kind == "reduce":
                    base = f"reduce:{n.result.name}"
                else:
                    fn = getattr(n.fn, "func", n.fn)   # a partial's function
                    base = getattr(fn, "__name__", None) or n.kind
                labels.append((n, base, i))
        ties = Counter(base for _, base, _ in labels)
        out = {id(n): f"{self.name}/{base}"
               + (f"[{i}]" if ties[base] > 1 else "")
               for n, base, i in labels}
        for n in self.nodes():
            if n.subgraph is not None:
                out.update(n.subgraph.node_scopes())
        return out

    def summary(self) -> str:
        """One line per node: level, kind, and the tensors it touches."""
        lines = [f"Graph {self.name!r} ({len(self.levels)} levels)"]
        for i, level in enumerate(self.levels):
            for n in level:
                desc = n.kind
                if n.subgraph is not None:
                    desc += f"[{n.subgraph.name}]"
                ts = ",".join(t.name for _, t, _ in n.tensor_args())
                lines.append(f"  L{i}: {n.name} {desc} ({ts})")
        if self.condition is not None:
            lines.append("  while <condition>")
        return "\n".join(lines)
