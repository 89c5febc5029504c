"""The whole step's share of the chip's peak: the least time of the window's
interior cell updates on one chip at the peak that binds them
(``bench/roofline.py``: 156 B per update at the HBM peak, or the operations
at the compute peak where that is longer) over the window's wall time. It
bounds every kernel's roofline share from below, whichever kernels the
step runs."""

from bench import roofline


def read(rec):
    if not rec.coarse_steps or not rec.peaks or rec.window_s <= 0:
        return None
    least, _bound = roofline.least_seconds(rec.updates / rec.chips, rec.lattice_c, rec.peaks)
    return 100.0 * least / rec.window_s
