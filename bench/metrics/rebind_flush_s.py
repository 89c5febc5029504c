"""Seconds per AMR cycle in the program's ``residency:flush`` span: the
download of the device-newer pdf stacks into the host arena before the
pipeline reads them."""

from bench import spans


def read(rec):
    return spans.per_cycle_s(rec, "residency:flush")
