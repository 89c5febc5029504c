"""Share of the stream-collide kernel's roofline: the least time of the
window's interior cell updates on one chip (``bench/roofline.py``: 156 B per
update at the table's HBM peak, or the operations at the compute peak where
that is longer) over the device time of the kernel's ops in the trace."""

from bench import roofline, trace


def read(rec):
    if rec.trace is None or not rec.coarse_steps:
        return None
    kernel_s = rec.trace.op_seconds(trace.is_kernel)
    if kernel_s <= 0:
        return None
    least, _bound = roofline.least_seconds(rec.updates / rec.chips, rec.lattice_c, rec.peaks)
    return 100.0 * least / kernel_s
