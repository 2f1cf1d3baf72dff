"""Host time per snapshot (ms): the program's own ``ripple.host`` spans
(``repro.tracing``), each one call of the snapshot node on the host pool
(its read of the state to the host, the ring and the float64
diagnostics), which it records only while the profiler records, summed
over the traced calls and divided by the snapshots they took.  Nothing
where the program records no such span."""


def read(run, work):
    try:
        from repro import tracing
    except ImportError:
        return None
    snaps = run.traced.get("snapshots", 0)
    spent = sum(s.t1 - s.t0 for s in tracing.recorded().spans
                if s.name == "ripple.host")
    if not snaps or not spent:
        return None
    return spent * 1e3 / snaps
