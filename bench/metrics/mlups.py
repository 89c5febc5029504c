"""Million interior cell updates per second: every update of the window (a
level-l block counts 2^l x its cells per coarse step) over the window's
whole wall time."""


def read(rec):
    if not rec.coarse_steps:
        return None
    return rec.updates / rec.window_s / 1e6
