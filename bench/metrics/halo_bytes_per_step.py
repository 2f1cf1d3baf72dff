"""Halo bytes one chip sends through collectives per Euler step, on
average over the mesh's chips: the program's own ``halo.bytes`` counter
(``repro.tracing``), which adds, for every pass it runs while the
profiler records, each scheduled block's payload from each shard that
sends it, over the traced calls' steps.  Nothing where the program keeps
no such counter."""


def read(run, work):
    try:
        from repro import tracing
    except ImportError:
        return None
    steps = run.traced.get("steps", 0)
    sent = tracing.recorded().counters.get("halo.bytes")
    if not steps or sent is None:
        return None
    return sent / steps
