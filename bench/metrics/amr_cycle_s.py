"""Seconds per AMR cycle: the window's whole wall time over the cycles it
completed (the window ends on a whole unit of cycles)."""


def read(rec):
    if not rec.cycles:
        return None
    return rec.window_s / len(rec.cycles)
