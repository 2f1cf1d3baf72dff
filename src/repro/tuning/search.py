"""Measured autotuner — JOINT layout × tile search with HLO cost-model
pruning (HONEI / CrystalGPU applied to Ripple's polymorphic layout).

The layout solver (``core/executor.py``) picks AoS/SoA/AoSoA by static
heuristics and kernels run with fixed default tile shapes — the paper's
"near-optimal bandwidth across targets" claim, asserted but never
measured.  This module measures it: for an ``Executor``'s plan it

1. benchmarks the heuristic baseline with real timed executions of the
   plan's region executables (``timing.time_fn_split`` — the same
   harness every benchmark table uses), while recording which Pallas
   kernels the trace consults (``tiles.record_tile_use``), and compiles
   the baseline's device-region HLO into a traffic model
   (``analysis.hlo.CostRanker``);
2. proposes the JOINT candidate space: the cross product of per-key
   halo-feasible layouts (``core.executor.layout_candidates``) × per
   consulted kernel its ``tile_candidates()`` hook, plus PER-SEGMENT
   layout refinements for keys live in several segments (the in-trace
   relayout machinery makes mixed-segment layouts value-exact);
3. ranks every proposal with the HLO cost model (baseline bytes + an
   analytic relayout-traffic and strided-access penalty) so only the
   cheapest fraction (:class:`TuneBudget`) is ever measured;
4. times the surviving candidates with real executions.  Each
   candidate's timing loop stops early once its running median is
   statistically dominated by the incumbent
   (``timing.time_fn_budget``), and the search itself stops once the
   incumbent survives ``TuneBudget.neighborhoods`` consecutive
   candidates;
5. commits the argmin configuration (a :class:`TuningDecision` —
   including any per-segment layout assignments) and persists it in the
   on-disk cache (``repro.tuning.cache``, schema v3) keyed by heuristic
   plan signature × device assortment × jax version, so a second
   process (the serving pattern) loads it with ZERO timed measurements.
   Entries written by the v2 coordinate-descent tuner are
   migration-read (:func:`legacy_tuning_key`) and re-persisted under
   the v3 key without re-measurement when still feasible.

``Executor(tune="auto", tune_budget=...)`` drives this at construction;
``tune="load"`` only consults the cache (heuristics on a miss);
``plan.describe_tuning()`` renders what was proposed, pruned, measured,
chosen, and why.  ``STATS["measurements"]`` counts timed candidate
executions — tests assert it stays 0 on a cache hit.
"""

from __future__ import annotations

import hashlib
import itertools
import math
from dataclasses import dataclass, field as dfield
from typing import Any, Optional

from . import cache as cache_lib
from . import tiles as tiles_lib
from .timing import time_fn_budget

__all__ = ["Measurement", "TuneBudget", "TuningDecision", "STATS",
           "reset_stats", "tuning_key", "legacy_tuning_key",
           "resolve_tuning", "measure_plan"]

# per-process tuner counters; tests assert measurements == 0 on cache hits
STATS = {"measurements": 0, "cache_hits": 0, "cache_misses": 0, "stores": 0,
         "proposed": 0, "pruned": 0, "migrations": 0}

# how many graph steps one timed call executes (relative comparisons only
# need steady-state per-step cost to dominate fixed dispatch overhead)
TUNE_STEPS = 2
TUNE_ITERS = 5

# makes the baseline probe's plan signature unique per tuning session so
# its trace really runs (and tile-use recording sees every kernel) even
# when an identical heuristic plan was already compiled in-process
_probe_nonce = itertools.count(1)


def reset_stats() -> None:
    """Zero the per-process tuner counters (tests)."""
    for k in STATS:
        STATS[k] = 0


@dataclass(frozen=True)
class TuneBudget:
    """Measurement budget for the joint search (``tune_budget=``).

    ``max_measure_frac`` bounds the fraction of proposed joint
    candidates that survive HLO cost-model pruning into real timed
    measurement (clamped to at least ``min_measure`` and at most
    ``max_measure`` when set).  ``neighborhoods`` stops the search once
    the incumbent survives that many consecutive measured candidates
    without being beaten.  ``dominate_factor`` stops one CANDIDATE's
    timing loop early (after ``min_timing_iters`` timed calls) once its
    running median exceeds ``incumbent × factor`` — it cannot win, so
    the remaining iterations are skipped.  ``measure_all`` disables
    pruning and early stopping entirely (conformance testing).
    ``max_proposals`` caps combinatorial blow-up of the joint space."""

    max_measure_frac: float = 0.3
    min_measure: int = 2
    max_measure: Optional[int] = None
    neighborhoods: int = 3
    dominate_factor: float = 1.15
    min_timing_iters: int = 2
    measure_all: bool = False
    max_proposals: int = 512

    @classmethod
    def coerce(cls, value) -> "TuneBudget":
        """A :class:`TuneBudget` from None (defaults), a dict of fields,
        or an existing instance (returned as-is)."""
        if value is None:
            return cls()
        if isinstance(value, cls):
            return value
        if isinstance(value, dict):
            return cls(**value)
        raise TypeError(f"tune_budget must be None, a dict or a "
                        f"TuneBudget, got {type(value).__name__}")

    def measure_count(self, proposed: int) -> int:
        """How many of ``proposed`` candidates the budget measures."""
        if proposed <= 0:
            return 0
        if self.measure_all:
            return proposed
        k = math.ceil(self.max_measure_frac * proposed)
        k = max(k, min(self.min_measure, proposed))
        if self.max_measure is not None:
            k = min(k, self.max_measure)
        return min(k, proposed)


@dataclass(frozen=True)
class Measurement:
    """One timed candidate configuration.

    ``kind`` is ``'baseline'`` (the untouched heuristic plan) or
    ``'joint'`` (one joint layout×tile candidate; ``candidate`` is its
    compact config label, e.g. ``'p=SOA,saxpy=2048'``).
    ``predicted_bytes`` is the HLO cost model's traffic estimate that
    ranked the candidate (0 when ranking was unavailable), ``iters``
    how many timed calls the steady median used, ``early_stopped``
    whether the timing loop was cut short because the candidate was
    statistically dominated.  ``chosen`` marks the committed row."""

    kind: str
    key: str
    candidate: str
    first_ms: float
    steady_ms: float
    chosen: bool = False
    predicted_bytes: float = 0.0
    iters: int = 0
    early_stopped: bool = False

    def describe(self) -> str:
        what = ("heuristic plan" if self.kind == "baseline"
                else f"{self.kind} {self.key}={self.candidate}")
        mark = "  [chosen]" if self.chosen else ""
        extra = ""
        if self.predicted_bytes:
            extra += f", predicted {self.predicted_bytes / 1e6:.3f} MB"
        if self.early_stopped:
            extra += f", dominated after {self.iters} iters"
        return (f"{what}: steady {self.steady_ms:.4f} ms "
                f"(first {self.first_ms:.1f} ms{extra}){mark}")


@dataclass
class TuningDecision:
    """The tuner's committed configuration for one plan.

    ``layouts`` maps state keys to the measured-best storage layout
    (only keys that beat the heuristic appear), ``tiles`` maps kernel
    names to the measured-best tile config, and ``segment_layouts``
    holds any PER-SEGMENT layout assignments the joint search committed
    (segment index -> key -> Layout; the executor merges these into its
    ``segment_layout_overrides``).  ``proposed`` / ``pruned`` /
    ``measured`` count the joint search space: how many candidates were
    proposed, how many the HLO cost ranking (plus early stopping)
    skipped, and how many were actually timed.  ``source`` says where
    the decision came from: ``'measured'`` (this process timed
    candidates), ``'cache'`` (loaded from the persistent cache — zero
    measurements), ``'migrated'`` (a v2 coordinate-tuner entry re-keyed
    under the v3 schema — also zero measurements) or ``'heuristic'``
    (``tune="load"`` missed the cache; nothing applied).
    :meth:`describe` renders the full measurement log."""

    source: str
    cache_key: str
    layouts: dict[str, Any] = dfield(default_factory=dict)   # key -> Layout
    tiles: dict[str, Any] = dfield(default_factory=dict)     # kernel -> tile
    baseline_ms: Optional[float] = None
    tuned_ms: Optional[float] = None
    measurements: list[Measurement] = dfield(default_factory=list)
    segment_layouts: dict[int, dict[str, Any]] = dfield(default_factory=dict)
    proposed: int = 0
    pruned: int = 0
    measured: int = 0

    @property
    def applied(self) -> bool:
        """True when the decision changes anything vs the heuristics."""
        return bool(self.layouts or self.tiles or self.segment_layouts)

    def describe(self) -> str:
        """Human-readable tuning report (``plan.describe_tuning()``)."""
        lines = [f"tuning ({self.source}, cache key {self.cache_key}):"]
        if self.baseline_ms is not None and self.tuned_ms is not None:
            ratio = self.baseline_ms / max(self.tuned_ms, 1e-9)
            lines[0] += (f" heuristic {self.baseline_ms:.4f} ms -> tuned "
                         f"{self.tuned_ms:.4f} ms ({ratio:.2f}x)")
        if self.proposed:
            lines.append(f"  search space: {self.proposed} proposed / "
                         f"{self.pruned} pruned by HLO cost ranking / "
                         f"{self.measured} measured")
        if not self.applied:
            lines.append("  heuristic configuration kept (no measured "
                         "candidate beat it)" if self.source != "heuristic"
                         else "  heuristic configuration in effect (cache "
                         "miss under tune=\"load\" — nothing measured)")
        for name in sorted(self.layouts):
            lines.append(f"  layout {name} -> "
                         f"{getattr(self.layouts[name], 'name', self.layouts[name])}")
        for si in sorted(self.segment_layouts):
            for name in sorted(self.segment_layouts[si]):
                lay = self.segment_layouts[si][name]
                lines.append(f"  segment {si} layout {name} -> "
                             f"{getattr(lay, 'name', lay)}")
        for name in sorted(self.tiles):
            lines.append(f"  tile {name} -> {self.tiles[name]!r}")
        if self.measurements:
            lines.append("  measured:")
            lines.extend(f"    {m.describe()}" for m in self.measurements)
        return "\n".join(lines)


# -- cache (de)serialization ---------------------------------------------------

def tuning_key(executor) -> str:
    """The persistent-cache key of an executor's plan: heuristic plan
    signature × the full device assortment (kinds × counts × process
    count — ``cache.device_assortment``, NOT just ``devices()[0]``, so
    heterogeneous or multi-host meshes never reuse a measurement taken
    on different hardware) × jax version.  Stable across processes for
    graphs whose node functions the plan signature can key structurally
    (plain functions / closures over provable values)."""
    import jax

    raw = repr(("repro-tune-v3", executor.plan.signature,
                cache_lib.device_assortment(), jax.__version__))
    return hashlib.sha1(raw.encode()).hexdigest()[:16]


def legacy_tuning_key(executor) -> str:
    """The key the v2 coordinate-descent tuner would have used for this
    plan — consulted on a v3 miss to migrate old entries forward.  Note
    the v2 key hashed the v2 plan signature; the plan signature itself
    was bumped alongside the schema, so this reconstructs the legacy
    key from the CURRENT signature with the old prefix (sufficient for
    entries whose plan signature components survived the bump, and a
    harmless miss otherwise)."""
    import jax

    raw = repr(("repro-tune-v2", executor.plan.signature,
                cache_lib.device_assortment(), jax.__version__))
    return hashlib.sha1(raw.encode()).hexdigest()[:16]


def _payload(dec: TuningDecision) -> dict:
    return {
        "layouts": {k: v.name for k, v in dec.layouts.items()},
        "tiles": dict(dec.tiles),
        "segment_layouts": {
            str(si): {k: v.name for k, v in d.items()}
            for si, d in dec.segment_layouts.items()},
        "baseline_ms": dec.baseline_ms,
        "tuned_ms": dec.tuned_ms,
        "proposed": dec.proposed,
        "pruned": dec.pruned,
        "measured": dec.measured,
        "measurements": [
            {"kind": m.kind, "key": m.key, "candidate": m.candidate,
             "first_ms": m.first_ms, "steady_ms": m.steady_ms,
             "chosen": m.chosen, "predicted_bytes": m.predicted_bytes,
             "iters": m.iters, "early_stopped": m.early_stopped}
            for m in dec.measurements],
    }


def _decision_from_payload(key: str, payload: dict,
                           source: str = "cache") -> TuningDecision:
    from ..core.layout import Layout

    layouts = {k: Layout[v] for k, v in payload["layouts"].items()}
    tiles = {k: tiles_lib._norm(v) for k, v in payload["tiles"].items()}
    seg_layouts = {
        int(si): {k: Layout[v] for k, v in d.items()}
        for si, d in payload.get("segment_layouts", {}).items()}
    meas = [Measurement(m["kind"], m["key"], m["candidate"],
                        float(m["first_ms"]), float(m["steady_ms"]),
                        bool(m.get("chosen", False)),
                        float(m.get("predicted_bytes", 0.0)),
                        int(m.get("iters", 0)),
                        bool(m.get("early_stopped", False)))
            for m in payload.get("measurements", [])]
    return TuningDecision(source, key, layouts, tiles,
                          payload.get("baseline_ms"),
                          payload.get("tuned_ms"), meas,
                          segment_layouts=seg_layouts,
                          proposed=int(payload.get("proposed", 0)),
                          pruned=int(payload.get("pruned", 0)),
                          measured=int(payload.get("measured", 0)))


def _migrate_legacy(executor, key: str) -> Optional[TuningDecision]:
    """Migration-read a v2 coordinate-tuner cache entry for this plan.

    On a v3 miss: load the legacy key at the legacy schema, check that
    the old decision is still FEASIBLE (every tuned layout key is still
    searchable with that layout as a candidate, every tuned kernel
    still has a registered tile hook), and re-persist it under the v3
    key with zero re-measurement.  An infeasible entry warns once and
    returns None (fresh tuning)."""
    from ..core import executor as executor_lib
    from ..core.layout import Layout

    lkey = legacy_tuning_key(executor)
    payload = cache_lib.load(lkey, schema=cache_lib.LEGACY_SCHEMA_VERSION)
    if payload is None:
        return None
    try:
        dec = _decision_from_payload(key, payload, source="migrated")
    except (KeyError, TypeError, ValueError):
        cache_lib._warn_once(cache_lib.cache_path(lkey),
                             "undecodable legacy decision")
        return None
    cands = executor_lib.layout_candidates(executor)
    heuristic = dict(executor.plan.initial)
    for name, lay in dec.layouts.items():
        if not isinstance(lay, Layout):
            lay = Layout[str(lay)]
        feasible = (lay is heuristic.get(name)
                    or (name in cands and lay in cands[name]))
        if not feasible:
            cache_lib._warn_once(
                cache_lib.cache_path(lkey),
                f"legacy tuned layout {name}->{lay.name} is no longer "
                f"feasible for this plan — re-tuning")
            return None
    registered = set(tiles_lib.registered_tile_kernels())
    for kernel in dec.tiles:
        if kernel not in registered:
            cache_lib._warn_once(
                cache_lib.cache_path(lkey),
                f"legacy tuned kernel {kernel!r} has no registered tile "
                f"hook — re-tuning")
            return None
    cache_lib.store(key, _payload(dec))
    STATS["stores"] += 1
    STATS["migrations"] += 1
    return dec


# -- driver --------------------------------------------------------------------

def resolve_tuning(executor, mode: str, budget=None) -> TuningDecision:
    """The tuned decision for ``executor``'s (heuristic) plan.

    ``mode='load'`` never measures: a cache hit (or a feasible migrated
    v2 entry) applies, a miss keeps heuristics.  ``mode='auto'``
    measures on a miss — under ``budget`` (a :class:`TuneBudget`, a
    dict of its fields, or None for defaults) — and persists the
    result.  Called by ``Executor.__init__`` before the plan is
    finalized."""
    key = tuning_key(executor)
    payload = cache_lib.load(key)
    if payload is not None:
        try:
            dec = _decision_from_payload(key, payload)
        except (KeyError, TypeError, ValueError):
            cache_lib._warn_once(cache_lib.cache_path(key),
                                 "undecodable decision")
            payload = None
        else:
            STATS["cache_hits"] += 1
            return dec
    STATS["cache_misses"] += 1
    dec = _migrate_legacy(executor, key)
    if dec is not None:
        return dec
    if mode == "load":
        return TuningDecision("heuristic", key)
    # cross-process serialization: the first process to take the key's
    # lock measures and persists; any process that waited re-checks the
    # cache under the lock and loads instead of duplicating the
    # measurement (cache.tuning_lock degrades to unlocked on trouble)
    with cache_lib.tuning_lock(key) as locked:
        if locked:
            # misses are never memoized, so this re-reads the FILE — it
            # sees anything a lock holder persisted while we waited
            payload = cache_lib.load(key)
            if payload is not None:
                try:
                    dec = _decision_from_payload(key, payload)
                except (KeyError, TypeError, ValueError):
                    pass
                else:
                    STATS["cache_hits"] += 1
                    return dec
        dec = measure_plan(executor, key, budget)
        cache_lib.store(key, _payload(dec))
        STATS["stores"] += 1
    return dec


# -- joint search --------------------------------------------------------------

def _storage_bytes(t) -> float:
    """Logical storage footprint of one state tensor in bytes (layout-
    independent: every storage layout is a permutation of the same
    elements)."""
    import numpy as np

    n = 1
    for d in t.space:
        n *= int(d)
    comps = t.spec.num_components if t.is_record else 1
    return float(n * comps * np.dtype(t.dtype).itemsize)


def _joint_label(layouts, tiles, seg_layouts) -> str:
    """Compact, deterministic label of one joint candidate."""
    parts = [f"{n}={lay.name}" for n, lay in sorted(layouts.items())]
    parts += [f"seg{si}:{n}={lay.name}"
              for si, d in sorted(seg_layouts.items())
              for n, lay in sorted(d.items())]
    parts += [f"{k}={t!r}" for k, t in sorted(tiles.items())]
    return ",".join(parts) or "heuristic"


def measure_plan(executor, key: str, budget=None) -> TuningDecision:
    """JOINT search over per-key layouts × per-kernel tiles (plus
    per-segment layout refinements), HLO-cost-ranked so only the
    budgeted top fraction is measured; every measured candidate is a
    real execution of the candidate plan's region executables (fresh
    ``Executor`` per candidate — the executable cache keys tile config
    and layout plan, so measurements never alias)."""
    from ..analysis.hlo import CostRanker, layout_access_penalty
    from ..core import executor as executor_lib

    budget = TuneBudget.coerce(budget)
    Executor = executor_lib.Executor
    graph, mesh = executor.graph, executor.mesh
    nonce = next(_probe_nonce)
    candidate_sigs: list[tuple] = []

    def bench(layouts, tiles, seg_layouts=None, probe=False,
              stop_above_ms=None):
        tile_cfg = dict(executor._tile_config)
        if probe:
            tile_cfg["__tune_probe__"] = nonce
        tile_cfg.update(tiles)
        seg_over = {si: dict(d)
                    for si, d in executor._segment_overrides.items()}
        for si, d in (seg_layouts or {}).items():
            seg_over.setdefault(si, {}).update(d)
        ex = Executor(graph, mesh=mesh, donate=executor.donate,
                      layout_overrides={**executor._layout_overrides,
                                        **layouts},
                      schedule=executor.schedule,
                      async_regions=executor.async_regions,
                      tile_overrides=tile_cfg,
                      segment_layout_overrides=seg_over)
        candidate_sigs.append(ex._plan_sig)
        state = ex.init_state(**executor._tune_inputs)

        if executor.donate:
            # measure under the plan's REAL donation setting: donation
            # consumes input buffers, so copy the initial state (the
            # caller's tune_inputs must survive every candidate) and chain
            # each timed call on the previous output
            import jax
            import jax.numpy as jnp

            carry = {"st": jax.tree_util.tree_map(jnp.array, state)}

            def run_once():
                carry["st"] = ex.run(dict(carry["st"]), TUNE_STEPS)
                return carry["st"]
        else:
            def run_once():
                return ex.run(dict(state), TUNE_STEPS)

        recorder = tiles_lib.record_tile_use() if probe else None
        if recorder is not None:
            with recorder as used:
                first, steady, iters_run, dominated = time_fn_budget(
                    run_once, iters=TUNE_ITERS,
                    min_iters=budget.min_timing_iters,
                    stop_above_ms=stop_above_ms)
        else:
            used = None
            first, steady, iters_run, dominated = time_fn_budget(
                run_once, iters=TUNE_ITERS,
                min_iters=budget.min_timing_iters,
                stop_above_ms=stop_above_ms)
        STATS["measurements"] += 1
        return first, steady, iters_run, dominated, used, ex._plan_sig, \
            ex, state

    measurements: list[Measurement] = []
    best_layouts: dict[str, Any] = {}
    best_tiles: dict[str, Any] = {}
    best_segments: dict[int, dict[str, Any]] = {}
    best_sig = None
    proposed = pruned = measured = 0
    try:
        # -- phase 0: baseline probe (times the heuristic plan, records
        # tile use, and supplies the HLO traffic base for ranking) ------
        first, base_ms, _it, _dom, used, _sig, probe_ex, probe_state = \
            bench({}, {}, probe=True)
        measured += 1
        measurements.append(Measurement("baseline", "plan", "heuristic",
                                        first, base_ms, iters=_it))
        best_ms = base_ms

        ranker = None
        try:
            hlo_texts = [probe_ex.region_hlo(probe_state, i)
                         for i, r in enumerate(probe_ex._regions)
                         if r.kind == "device"]
            if hlo_texts:
                ranker = CostRanker(hlo_texts)
        except Exception:
            ranker = None   # non-region plans etc.: rank by penalty only

        # -- phase 1: search axes --------------------------------------
        heuristic = dict(executor.plan.initial)
        layout_axes: dict[str, list] = {}
        for name, cands in sorted(
                executor_lib.layout_candidates(executor).items()):
            base = heuristic.get(name)
            ordered = ([base] if base in cands else []) \
                + [l for l in cands if l is not base]
            layout_axes[name] = ordered

        tile_axes: dict[str, list] = {}
        tile_defaults: dict[str, Any] = {}
        for kernel in sorted(used or {}):
            uses = used[kernel]
            defaults = {d for _, d in uses}
            cand_sets = [set(tiles_lib.tile_candidates(kernel, shape))
                         for shape, _ in uses]
            cands = set.intersection(*cand_sets) if cand_sets else set()
            cands |= defaults
            default = sorted(defaults, key=repr)[0]
            tile_defaults[kernel] = default
            ordered = sorted(
                cands, key=lambda t: (tiles_lib.tile_distance(t, default),
                                      repr(t)))
            if len(ordered) > 1:
                tile_axes[kernel] = ordered

        # -- phase 2: joint proposals ----------------------------------
        lay_names = sorted(layout_axes)
        tile_names = sorted(tile_axes)
        axes = [[(n, v) for v in layout_axes[n]] for n in lay_names] \
            + [[(k, v) for v in tile_axes[k]] for k in tile_names]
        proposals: list[dict] = []
        for combo in itertools.islice(itertools.product(*axes),
                                      budget.max_proposals):
            lay = {n: v for n, v in combo[:len(lay_names)]
                   if v is not heuristic.get(n)}
            til = {k: v for k, v in combo[len(lay_names):]
                   if v != tile_defaults.get(k)}
            proposals.append({"layouts": lay, "tiles": til,
                              "segments": {}})
        # per-segment refinements: a single-(segment, key) layout flip
        # for keys live in >= 2 segments (the relayout machinery keeps
        # mixed-segment assignments value-exact)
        seg_homes: dict[str, list[int]] = {}
        for si, seg in enumerate(executor.plan.per_segment):
            for name in seg:
                if name in layout_axes:
                    seg_homes.setdefault(name, []).append(si)
        for name, sis in sorted(seg_homes.items()):
            if len(sis) < 2 or len(proposals) >= budget.max_proposals:
                continue
            for si in sis:
                for lay in layout_axes[name]:
                    if lay is heuristic.get(name):
                        continue
                    if len(proposals) >= budget.max_proposals:
                        break
                    proposals.append({"layouts": {}, "tiles": {},
                                      "segments": {si: {name: lay}}})
        proposed = len(proposals)

        # -- phase 3: HLO cost ranking ---------------------------------
        def penalty_of(p) -> float:
            try:
                seg_over = {si: dict(d) for si, d
                            in executor._segment_overrides.items()}
                for si, d in p["segments"].items():
                    seg_over.setdefault(si, {}).update(d)
                plan = executor_lib.solve_layouts(
                    executor._segments, executor.tensors,
                    overrides={**executor._layout_overrides,
                               **p["layouts"]},
                    segment_overrides=seg_over)
            except Exception:
                return float("inf")
            pen = 0.0
            for st in plan.relayouts:
                # a relayout reads + writes the whole storage once
                pen += 2.0 * _storage_bytes(executor.tensors[st.tensor])
            for seg in plan.per_segment:
                for name, lay in seg.items():
                    t = executor.tensors.get(name)
                    if t is None or not t.is_record:
                        continue
                    pen += layout_access_penalty(
                        lay.name, _storage_bytes(t),
                        t.spec.num_components)
            return pen

        def tile_dist(p) -> float:
            return sum(tiles_lib.tile_distance(t, tile_defaults[k])
                       for k, t in p["tiles"].items())

        pens = [penalty_of(p) for p in proposals]
        # stable pre-order near-default-first, so cost ties break toward
        # configurations most likely to behave like the baseline
        order = sorted(range(proposed), key=lambda i: tile_dist(
            proposals[i]))
        order = [i for i in order if pens[i] != float("inf")]
        predicted: dict[int, float] = {}
        if ranker is not None:
            ranked = ranker.rank([(str(i), pens[i]) for i in order])
            order = [int(c.label) for c in ranked]
            predicted = {int(c.label): c.predicted_bytes for c in ranked}
        else:
            order.sort(key=lambda i: pens[i])
            predicted = {i: pens[i] for i in order}

        # -- phase 4/5: prune, then measure the survivors --------------
        k = budget.measure_count(proposed)
        survived = taken = 0
        for idx in order:
            if taken >= k:
                break
            p = proposals[idx]
            if not (p["layouts"] or p["tiles"] or p["segments"]):
                continue   # the all-heuristic combo IS the baseline probe
            if not budget.measure_all and survived >= budget.neighborhoods:
                break      # incumbent survived enough joint neighborhoods
            stop = (None if budget.measure_all
                    else best_ms * budget.dominate_factor)
            f, s, iters_run, dominated, _, sig, _, _ = bench(
                p["layouts"], p["tiles"], p["segments"],
                stop_above_ms=stop)
            measured += 1
            taken += 1
            measurements.append(Measurement(
                "joint", "plan",
                _joint_label(p["layouts"], p["tiles"], p["segments"]),
                f, s, predicted_bytes=predicted.get(idx, 0.0),
                iters=iters_run, early_stopped=dominated))
            if s < best_ms:
                best_ms, best_sig = s, sig
                best_layouts = dict(p["layouts"])
                best_tiles = dict(p["tiles"])
                best_segments = {si: dict(d)
                                 for si, d in p["segments"].items()}
                survived = 0
            else:
                survived += 1
        # ``measured`` counts every configuration with timing data (the
        # baseline probe included); everything proposed but never timed
        # was pruned — by the cost ranking or by neighborhood early stop
        pruned = max(proposed - measured, 0)
        STATS["proposed"] += proposed
        STATS["pruned"] += pruned
    finally:
        # drop the losing candidates' executables; the winner benched under
        # the caller's own donation setting (donation is part of the plan
        # signature), so the caller's executor fetches it straight from the
        # cache with zero new traces
        for sig in candidate_sigs:
            if sig != best_sig:
                executor_lib._EXECUTABLE_CACHE.pop(sig, None)

    chosen_label = _joint_label(best_layouts, best_tiles, best_segments)
    measurements = [
        Measurement(m.kind, m.key, m.candidate, m.first_ms, m.steady_ms,
                    chosen=(m.candidate == chosen_label
                            if chosen_label != "heuristic"
                            else m.kind == "baseline"),
                    predicted_bytes=m.predicted_bytes, iters=m.iters,
                    early_stopped=m.early_stopped)
        for m in measurements]
    return TuningDecision("measured", key, best_layouts, best_tiles,
                          baseline_ms=base_ms, tuned_ms=best_ms,
                          measurements=measurements,
                          segment_layouts=best_segments,
                          proposed=proposed, pruned=max(pruned, 0),
                          measured=measured)
