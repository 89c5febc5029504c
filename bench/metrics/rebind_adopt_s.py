"""Seconds per AMR cycle in the program's ``rebind:adopt`` span: the arena's
repack of the new forest into per-level stacks and the engine's cache
invalidation."""

from bench import spans


def read(rec):
    return spans.per_cycle_s(rec, "rebind:adopt")
