"""Continuous-batching front end over the graph-native serving executors.

Requests stream into fixed batch slots of ONE decode executor (the
retrace-free ``Executor`` fast path): a free slot triggers a B=1 prefill
graph whose per-layer caches are scattered into the decode state along the
batch *storage* axis — whatever layout the decode plan chose (AoS/AoSoA
keep batch leading; SoA puts it behind the component axis) — while
``tokens``/``pos``/``active`` are per-slot vectors, so every slot sits at
its own sequence depth (the paper's polymorphic-layout argument applied to
the serving state itself).

Retirement is host-side: after each step the harvested token is matched
against ``eos_token`` / ``max_new_tokens`` / the cache capacity, and the
slot's ``active`` flag is dropped (inactive slots keep overwriting one
stale cache row, which is harmless — their logits are discarded and the
slot is re-prefilled at admission).

Fault tolerance reuses the Supervisor's machinery (runtime/supervisor.py):
``StepStats`` Welford straggler detection per decode step, and transient
retries through the shared :class:`repro.runtime.faults.RetryPolicy`
(exponential backoff, deterministic jitter) under
``max_failures``/``max_retries_per_step`` budgets — admission faults
(``batcher.admit``) are retried the same way as decode-step faults
(``batcher.step``).  Recovery needs no checkpoint store: greedy decode is a pure
function of the request log, so ``_recover()`` rebuilds the decode state
by re-prefilling every in-flight request with prompt + generated tokens —
the request log IS the checkpoint.
"""

from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core.executor import Executor
from repro.core.layout import Layout, relayout_data
from repro.launch.steps import (make_decode_graph, make_prefill_graph)
from repro.models import kvcache as kvc
from repro.models.config import ModelConfig

from .faults import RetryPolicy, trip as _fault_trip
from .supervisor import StepStats, TransientError

__all__ = ["Request", "Batcher"]


@dataclass
class Request:
    """One generation request moving queued -> active -> done/evicted."""

    rid: int
    prompt: np.ndarray                    # (S,) int32
    max_new_tokens: int
    generated: list = field(default_factory=list)
    status: str = "queued"
    slot: int = -1
    t_submit: float = 0.0
    t_admit: float = 0.0
    t_done: float = 0.0
    token_times: list = field(default_factory=list)   # wall time per token

    @property
    def text_tokens(self) -> list:
        return list(self.generated)


def _batch_axis(layout: Layout) -> int:
    """Storage axis holding the batch space dim (batch is never the tiled
    AoSoA dim, so only SoA's leading component axis shifts it)."""
    return 1 if layout is Layout.SOA else 0


@partial(jax.jit, static_argnums=(3,), donate_argnums=(0,))
def _scatter_slot(dst, src, slot, axis):
    start = (jnp.int32(0),) * axis + (slot,) + \
        (jnp.int32(0),) * (dst.ndim - axis - 1)
    return lax.dynamic_update_slice(dst, src.astype(dst.dtype), start)


class Batcher:
    """Admit/evict requests into the fixed batch slots of one decode
    executor; every admitted slot advances one greedy token per ``step()``.

    The decode executable is traced at most once per process — a fresh
    ``Batcher`` in a worker that reuses the same ``cfg``/``params`` objects
    serves straight from the process-wide executable cache with zero new
    traces (asserted in CI via ``cache_stats()["trace_events"]``).

    Admission overlaps decode (``prefill_ahead=True``): the decode call
    returns at dispatch (the executor's event-driven runtime), and the
    queue head's prefills are dispatched BEHIND the in-flight step on
    the device stream before the batcher blocks for the step's tokens —
    so a new request's prefill costs wall time only where it exceeds
    the decode step it hid behind.  Token results are unchanged:
    prefill is a pure function of the prompt, and recovery replays
    (prompt + generated) never reuse a prepared prefill.
    ``StepStats`` records completion times (measured after
    ``block_until_ready``), with dispatch-return tracked separately.
    """

    def __init__(self, cfg: ModelConfig, params, *, batch: int,
                 max_seq: int, mesh=None, eos_token: Optional[int] = None,
                 max_failures: int = 10, max_retries_per_step: int = 3,
                 straggler_zscore: float = 3.0,
                 prefill_ahead: bool = True,
                 executor_opts: Optional[dict] = None,
                 step_hook: Optional[Callable[[int], None]] = None,
                 retry: Optional[RetryPolicy] = None,
                 log: Callable[[str], None] = print):
        self.cfg = cfg
        self.params = params
        self.batch = batch
        self.max_seq = max_seq
        self.mesh = mesh
        self.eos_token = eos_token
        self.max_failures = max_failures
        self.max_retries_per_step = max_retries_per_step
        self.straggler_zscore = straggler_zscore
        self.step_hook = step_hook
        # shared transient-retry policy (exponential backoff with
        # deterministic jitter); the recovery ACTION stays the batcher's
        # own request-log replay (_recover)
        self.retry = retry if retry is not None \
            else RetryPolicy(base_delay=0.01, max_delay=0.25)
        self.log = log
        self._exec_opts = dict(executor_opts or {})
        self.dg = make_decode_graph(cfg, params, batch=batch,
                                    max_seq=max_seq, mesh=mesh)
        # the weights ride as read-only executor state
        self._weights = self.dg.weights.state(params)
        self.executor = Executor(self.dg.graph, mesh=mesh,
                                 **{"tune_inputs": self._weights,
                                    **self._exec_opts})
        self.state = self.executor.init_state(**self._weights)
        self.slots: list = [None] * batch
        self.queue: deque = deque()
        self.retired: list = []
        self.stats = StepStats()
        self.steps = 0
        self.failures = 0
        self._next_rid = 0
        self._prefill: dict = {}          # prompt_len -> (PrefillGraph, Executor)
        # admit-while-in-flight: prefills computed behind a dispatched
        # decode step, keyed by request id, consumed at admission
        self.prefill_ahead = bool(prefill_ahead)
        self._prepared: dict = {}         # rid -> (PrefillGraph, Executor, state)

    # -- request lifecycle -------------------------------------------------
    def submit(self, prompt, max_new_tokens: int = 64) -> Request:
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if len(prompt) == 0:
            raise ValueError("empty prompt")
        if len(prompt) >= self.max_seq:
            raise ValueError(
                f"prompt length {len(prompt)} >= max_seq {self.max_seq}")
        req = Request(self._next_rid, prompt, max_new_tokens,
                      t_submit=time.perf_counter())
        self._next_rid += 1
        self.queue.append(req)
        return req

    def evict(self, rid: int) -> bool:
        """Drop a request wherever it is (queue or live slot)."""
        for req in list(self.queue):
            if req.rid == rid:
                self.queue.remove(req)
                self._prepared.pop(rid, None)
                req.status = "evicted"
                self.retired.append(req)
                return True
        for slot, req in enumerate(self.slots):
            if req is not None and req.rid == rid:
                self._retire(slot, status="evicted")
                return True
        return False

    @property
    def active_count(self) -> int:
        return sum(r is not None for r in self.slots)

    def pending(self) -> int:
        return len(self.queue)

    # -- admission ---------------------------------------------------------
    def _prefill_for(self, prompt_len: int):
        if prompt_len not in self._prefill:
            pg = make_prefill_graph(self.cfg, self.params,
                                    prompt_len=prompt_len,
                                    max_seq=self.max_seq, mesh=self.mesh)
            self._prefill[prompt_len] = (pg, Executor(pg.graph,
                                                      mesh=self.mesh))
        return self._prefill[prompt_len]

    def _admit_ready(self) -> None:
        for slot in range(self.batch):
            if not self.queue:
                return
            if self.slots[slot] is None:
                # peek-admit-pop: a failure mid-admission (faults.py's
                # "batcher.admit" site) leaves the request at the queue
                # head, so the retry re-admits instead of losing it
                self._admit(self.queue[0], slot)
                self.queue.popleft()

    def _prefill_state(self, prompt: np.ndarray):
        pg, exp = self._prefill_for(len(prompt))
        pst = exp.init_state(prompt=jnp.asarray(prompt, jnp.int32)[None],
                             **self._weights)
        return pg, exp, exp(pst)

    def _prefill_ahead(self) -> None:
        """Compute prefills for the queue head while a decode step is in
        flight (the decode dispatch already returned; these prefill
        programs queue up behind it on the device stream, so admission
        work overlaps the step instead of serializing after it).
        Results are consumed by :meth:`_admit`; recovery replays
        (``req.generated`` non-empty) never use them — their prefill
        must include the generated tokens."""
        for req in list(self.queue)[:self.batch]:
            if req.generated or req.rid in self._prepared:
                continue
            self._prepared[req.rid] = self._prefill_state(req.prompt)

    def _admit(self, req: Request, slot: int) -> None:
        # trips BEFORE any state mutation: a failed admission is fully
        # retryable (the request is still queued / still in the replay
        # set, and no slot tensor has been scattered yet)
        _fault_trip("batcher.admit", detail=f"rid{req.rid}",
                    step=self.steps)
        prompt = np.concatenate([req.prompt,
                                 np.asarray(req.generated[:-1], np.int32)])
        prepared = self._prepared.pop(req.rid, None)
        if prepared is not None and not req.generated:
            pg, exp, pst = prepared
        else:
            pg, exp, pst = self._prefill_state(prompt)
        if req.generated:
            # recovery replay: the last generated token is the next input
            first = int(req.generated[-1])
        else:
            first = int(np.asarray(pst["first"])[0])
        for cslot in pg.slots:
            if cslot.kind in ("A", "L"):
                name = cslot.tensors[0].name
                src = pst[name]
                src_lay = exp.plan.initial[name]
                dst_lay = self.executor.plan.initial[name]
                if src_lay is not dst_lay:
                    src = relayout_data(src, kvc.kv_spec(self.cfg.head_dim),
                                        src_lay, dst_lay)
                self.state[name] = _scatter_slot(
                    self.state[name], src, jnp.int32(slot),
                    _batch_axis(dst_lay))
            else:
                for t in cslot.tensors:
                    self.state[t.name] = _scatter_slot(
                        self.state[t.name], pst[t.name], jnp.int32(slot), 0)
        pos = len(prompt)
        self.state["tokens"] = self.state["tokens"].at[slot].set(first)
        self.state["pos"] = self.state["pos"].at[slot].set(pos)
        self.state["active"] = self.state["active"].at[slot].set(True)
        req.slot = slot
        req.status = "active"
        now = time.perf_counter()
        if not req.t_admit:
            req.t_admit = now
        self.slots[slot] = req
        if not req.generated:
            req.generated.append(first)
            req.token_times.append(now)
            self._maybe_finish(slot, first, pos)

    def _retire(self, slot: int, status: str = "done") -> None:
        req = self.slots[slot]
        if req is None:
            return
        req.status = status
        req.t_done = time.perf_counter()
        req.slot = -1
        self.slots[slot] = None
        self.retired.append(req)
        self.state["active"] = self.state["active"].at[slot].set(False)

    def _maybe_finish(self, slot: int, token: int, pos: int) -> None:
        req = self.slots[slot]
        if req is None:
            return
        if (self.eos_token is not None and token == self.eos_token) \
                or len(req.generated) >= req.max_new_tokens \
                or pos + 1 >= self.max_seq:
            self._retire(slot)

    # -- decode steps ------------------------------------------------------
    def step(self) -> bool:
        """Admit what fits, advance every active slot one token.  Returns
        False when nothing was active (drained).

        Admission runs INSIDE the retried block, so a failure during the
        admission scatter (faults.py's "batcher.admit" site) recovers
        exactly like a failed decode step: backoff per the shared
        :class:`~repro.runtime.faults.RetryPolicy`, then request-log
        replay (``_recover``) — and since recovery itself re-admits,
        faults during recovery consume the same retry budget instead of
        escaping."""
        retries = 0
        need_recover = False
        while True:
            try:
                if need_recover:
                    need_recover = False
                    self._recover()
                self._admit_ready()
                if self.active_count == 0:
                    return False
                t0 = time.perf_counter()
                if self.step_hook is not None:
                    self.step_hook(self.steps)
                _fault_trip("batcher.step", step=self.steps)
                self.state = self.executor(self.state)
                t_dispatch = time.perf_counter() - t0
                # decode step in flight (async dispatch): admit-ahead —
                # prefill queued requests behind it on the device stream
                if self.prefill_ahead:
                    self._prefill_ahead()
                # StepStats contract: dt is COMPLETION time, measured
                # after block_until_ready (the async executor's call
                # above returned at dispatch)
                jax.block_until_ready(self.state["tokens"])
                dt = time.perf_counter() - t0
                if self.stats.update(dt, self.steps,
                                     self.straggler_zscore,
                                     dispatch=t_dispatch):
                    self.log(f"[batcher] straggler step {self.steps}: "
                             f"{dt * 1e3:.1f}ms "
                             f"(mean {self.stats.mean * 1e3:.1f})")
                break
            except Exception as e:
                if not self.retry.is_transient(e):
                    raise
                self.failures += 1
                retries += 1
                if self.failures > self.max_failures:
                    raise RuntimeError(
                        f"exceeded max_failures={self.max_failures}") from e
                if retries > self.max_retries_per_step:
                    raise RuntimeError(
                        f"decode step failed {retries} times") from e
                self.log(f"[batcher] transient failure ({e}); replaying "
                         f"{self.active_count} in-flight request(s) "
                         f"(retry {retries}, backoff "
                         f"{self.retry.backoff(retries) * 1e3:.0f}ms)")
                self.retry.backoff_sleep(retries)
                need_recover = True
        self.steps += 1
        self._harvest()
        return True

    def _harvest(self) -> None:
        tokens = np.asarray(self.state["tokens"])
        pos = np.asarray(self.state["pos"])
        now = time.perf_counter()
        for slot, req in enumerate(self.slots):
            if req is None:
                continue
            tok = int(tokens[slot])
            req.generated.append(tok)
            req.token_times.append(now)
            self._maybe_finish(slot, tok, int(pos[slot]))

    def _recover(self) -> None:
        """Rebuild the decode state from the request log (greedy decode is
        deterministic, so re-prefilling prompt + generated tokens restores
        the exact cache; the last generated token becomes the next input).

        Requests stay in ``self.slots`` throughout: recovery itself can
        take a fault (an injected or real failure during a replay
        prefill), and the retry calls ``_recover`` again — it must still
        see EVERY live request.  ``init_state()`` resets the device state
        wholesale, so a partially re-admitted previous attempt leaves no
        residue."""
        live = [(slot, req) for slot, req in enumerate(self.slots)
                if req is not None]
        self.state = self.executor.init_state(**self._weights)
        for slot, req in live:
            self._admit(req, slot)

    def run(self, max_steps: Optional[int] = None) -> list:
        """Drain: admit + step until every request retired (or the step
        budget runs out).  Returns the retired request list."""
        while self.queue or self.active_count:
            if max_steps is not None and self.steps >= max_steps:
                break
            if not self.step():
                if not self.queue:
                    break
        return self.retired

    # -- introspection -----------------------------------------------------
    def cache_stats(self) -> dict:
        out = {"decode": self.executor.cache_stats()}
        out["prefill"] = {S: ex.cache_stats()
                          for S, (_, ex) in sorted(self._prefill.items())}
        return out
