"""Seconds from the process's start to the window's first op: imports, the
solver's build, the set-up ops, the seed's state, the warm-up and every
compile among them."""


def read(rec):
    return rec.setup_s
