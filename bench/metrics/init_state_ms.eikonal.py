"""Host time per solve in ``Executor.init_state`` (ms): the program's own
``ripple.init_state`` spans (``repro.tracing``), which it records only
while the profiler records, summed over the traced solves and divided by
their number.  Nothing where the program records no such span."""


def read(run, work):
    try:
        from repro import tracing
    except ImportError:
        return None
    solves = run.traced.get("solves", 0)
    spent = sum(s.t1 - s.t0 for s in tracing.recorded().spans
                if s.name == "ripple.init_state")
    if not solves or not spent:
        return None
    return spent * 1e3 / solves
