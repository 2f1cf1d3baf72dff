"""Host spans and counters at the executor's layer boundaries.

One switch, the JAX profiler: while it records (``jax.profiler.trace``,
``start_trace`` or the profiler server), ``span`` opens a
``TraceAnnotation`` on the trace's host plane, on the device ops' clock,
and keeps a :class:`Span` (``time.perf_counter`` seconds) in a bounded
record that :func:`recorded` returns; ``count`` adds to a counter.
Otherwise a span costs one check.  Parents come from a per-thread stack;
the outermost ``ripple.run`` (``new_run=True``) takes a fresh ``run_id``
that every span under it carries, a nested run (a host loop's iterations)
included, and ``carry`` hands the caller's place in the stack to a pool
thread.  The annotation carries the ``run_id`` too, so a trace viewer
ties a pool thread's spans to their run.
"""

from __future__ import annotations

import contextlib
import itertools
import threading
import time
from collections import deque, namedtuple
from typing import Any

import jax

__all__ = ["Span", "Record", "recording", "span", "count", "carry",
           "recorded", "clear"]

try:
    from jax._src.lib import _profiler
    _is_enabled = _profiler.TraceMe.is_enabled
except (ImportError, AttributeError):   # a JAX without the check
    _is_enabled = None


def recording() -> bool:
    """True while the JAX profiler records (False where JAX cannot say)."""
    return _is_enabled is not None and bool(_is_enabled())


Span = namedtuple("Span", "name run_id parent thread t0 t1 attrs")
Span.__doc__ = """One closed span; ``parent`` is the enclosing span's name,
``run_id`` that of the ``ripple.run`` it ran under (``None`` outside)."""
Record = namedtuple("Record", "spans counters")
Record.__doc__ = """The spans in the order they closed, and each counter's
total."""


_spans: deque = deque(maxlen=1 << 16)    # the oldest fall out first
_counters: dict[str, int] = {}
_lock = threading.Lock()
_run_ids = itertools.count(1)
_OFF = contextlib.nullcontext()


class _Local(threading.local):
    def __init__(self):
        self.stack: list = []    # this thread's open spans, innermost last


_local = _Local()


class _Open:
    __slots__ = ("name", "attrs", "new_run", "run_id", "parent", "t0",
                 "annotation")

    def __init__(self, name: str, new_run: bool, attrs: dict):
        self.name, self.new_run, self.attrs = name, new_run, attrs

    def __enter__(self):
        st = _local.stack
        top = st[-1] if st else None
        self.parent = top.name if top is not None else None
        self.run_id = top.run_id if top is not None else None
        if self.new_run and self.run_id is None:
            self.run_id = next(_run_ids)
        ids = {} if self.run_id is None else {"run_id": self.run_id}
        self.annotation = jax.profiler.TraceAnnotation(self.name, **ids,
                                                       **self.attrs)
        self.annotation.__enter__()
        st.append(self)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        _local.stack.pop()
        self.annotation.__exit__(*exc)
        done = Span(self.name, self.run_id, self.parent,
                    threading.current_thread().name, self.t0, t1, self.attrs)
        with _lock:
            _spans.append(done)
        return False


def span(name: str, new_run: bool = False, **attrs: Any):
    """A context manager that records one span while the profiler
    records; ``new_run`` gives it and the spans under it a fresh
    ``run_id`` unless it opens under a run already."""
    if not recording():
        return _OFF
    return _Open(name, new_run, attrs)


def count(name: str, n: int) -> None:
    """Add ``n`` to counter ``name`` while the profiler records."""
    if recording():
        with _lock:
            _counters[name] = _counters.get(name, 0) + n


def carry(fn):
    """``fn`` wrapped to run, on any thread, under the calling thread's
    innermost open span (its parent and ``run_id``); ``fn`` itself when
    no span is open."""
    if not _local.stack:
        return fn
    top = _local.stack[-1]

    def under_caller(*args, **kwargs):
        _local.stack.append(top)
        try:
            return fn(*args, **kwargs)
        finally:
            _local.stack.pop()

    return under_caller


def recorded() -> Record:
    """The spans closed and the counters added so far."""
    with _lock:
        return Record(list(_spans), dict(_counters))


def clear() -> None:
    """Forget every recorded span and counter."""
    with _lock:
        _spans.clear()
        _counters.clear()
