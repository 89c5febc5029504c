"""Seconds per AMR cycle in the program's ``rebind:ghosts`` span: the host
ghost exchange after the topology changed, its plan rebuild included."""

from bench import spans


def read(rec):
    return spans.per_cycle_s(rec, "rebind:ghosts")
