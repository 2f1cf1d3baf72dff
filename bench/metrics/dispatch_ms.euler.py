"""Host time per Euler step in the executor's calls of its compiled
programs (ms): the program's own ``ripple.dispatch`` spans
(``repro.tracing``), which it records only while the profiler records,
summed over the traced calls and divided by their steps.  Nothing where
the program records no such span."""


def read(run, work):
    try:
        from repro import tracing
    except ImportError:
        return None
    steps = run.traced.get("steps", 0)
    spent = sum(s.t1 - s.t0 for s in tracing.recorded().spans
                if s.name == "ripple.dispatch")
    if not steps or not spent:
        return None
    return spent * 1e3 / steps
