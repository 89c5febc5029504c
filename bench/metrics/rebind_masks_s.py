"""Seconds per AMR cycle in the program's ``rebind:masks`` span: the
re-derivation of every block's cell types after the topology changed."""

from bench import spans


def read(rec):
    return spans.per_cycle_s(rec, "rebind:masks")
