"""Time per Euler step that the executor's dispatcher spent waiting on
host work with the device out of work (ms): the program's own
``ripple.host_wait`` spans (``repro.tracing``), which it records only
while the profiler records, summed over the traced calls and divided by
their steps.  On the fused path with periodic host nodes the call's end
waits for the device first, so the drain's span holds only host work
that the device's steps did not hide.  A ``slot`` wait, before a due
pass's copies, holds the dispatcher while the device still runs the
chunk queued before it, and is left out: host work that outlasts that
chunk outlasts the call's last chunk too, and shows in the drain.
Nothing where the program records no such span."""


def read(run, work):
    try:
        from repro import tracing
    except ImportError:
        return None
    steps = run.traced.get("steps", 0)
    waits = [s.t1 - s.t0 for s in tracing.recorded().spans
             if s.name == "ripple.host_wait"
             and s.attrs.get("reason") != "slot"]
    if not steps or not waits:
        return None
    return sum(waits) * 1e3 / steps
