"""Share of the HBM roofline reached by the split FORCE update kernel
(%): the bytes its two directional passes must move in a step, each a
read and a write of a chip's state (``2 x work.euler_step_bytes``), at
the chip's peak HBM bandwidth, over the kernel's device time per step in
the traced piece (averaged over the cell's devices).

``KERNEL`` matches the Pallas call's stable name, ``euler_force_update``;
a program without the kernel (the XLA-fused update) reads nothing."""

KERNEL = r"^%euler_force_update[.\d]* = .*custom_call_target=\"tpu_custom_call\""


def read(run, work):
    t, steps = run.trace, run.traced.get("steps", 0)
    if t is None or not steps:
        return None
    kernel_s = t.op_seconds(KERNEL)
    if kernel_s <= 0:
        return None
    bw = work.peaks(run.device_kind)["hbm_bytes_per_s"]
    need_s = 2 * work.euler_step_bytes(work.euler_step_cells(run.cfg)) / bw
    return 100.0 * need_s / (kernel_s / steps)
