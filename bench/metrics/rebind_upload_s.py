"""Seconds per AMR cycle in the program's ``residency:upload`` spans: the
host's part of uploading the new topology's pdf and mask stacks to the
device. A transfer may land after its span ends; the cycle's own fence
waits for it."""

from bench import spans


def read(rec):
    return spans.per_cycle_s(rec, "residency:upload")
