"""Share of the HBM roofline reached by the double-buffer copy of a
snapshot (%): a read and a write of a chip's state (the bytes of
``work.euler_step_bytes``) at the chip's peak HBM bandwidth, over the
copy's device time per snapshot in the traced piece.

``KERNEL`` matches the copy's stable name, the ``host_snapshot`` DMA
kernel; a program without it reads nothing."""

KERNEL = r"^(ROOT )?%host_snapshot[.\d]* = .*custom_call_target=\"tpu_custom_call\""


def read(run, work):
    t, snaps = run.trace, run.traced.get("snapshots", 0)
    if t is None or not snaps:
        return None
    copy_s = t.op_seconds(KERNEL)
    if copy_s <= 0:
        return None
    bw = work.peaks(run.device_kind)["hbm_bytes_per_s"]
    need_s = work.euler_step_bytes(work.euler_step_cells(run.cfg)) / bw
    return 100.0 * need_s / (copy_s / snaps)
