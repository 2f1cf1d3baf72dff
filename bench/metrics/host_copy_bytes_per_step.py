"""Bytes one chip copies per Euler step so that host nodes can read
their arguments while later steps donate them: the program's own
``host.copy_bytes`` counter (``repro.tracing``), which adds each
double-buffer copy's bytes while the profiler records, over the traced
calls' steps.  One copy of the state per snapshot period reads the
state's bytes over the period; a copy every step would read the state's
bytes.  Nothing where the program keeps no such counter."""


def read(run, work):
    try:
        from repro import tracing
    except ImportError:
        return None
    steps = run.traced.get("steps", 0)
    copied = tracing.recorded().counters.get("host.copy_bytes")
    if not steps or copied is None:
        return None
    return copied / steps
