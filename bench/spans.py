"""Seconds per AMR cycle in one of the program's own host spans.

The program mirrors its telemetry spans into the profiler as host
annotations named ``<cat>:<name>`` (``repro.telemetry``). A traced window's
host plane holds them beside the harness's own ``bench:<op>`` annotations,
on the profiler's clock.
"""

from __future__ import annotations


def per_cycle_s(rec, name: str):
    """Summed duration of the traced window's host spans named exactly
    ``name``, over the AMR cycles the window completed; ``None`` without
    cycles, without a trace, or where no such span ran (a program that
    lacks it, or a renamed span)."""
    if rec.trace is None or not rec.cycles:
        return None
    durs = [op.dur_ns for op in rec.trace.host if op.name == name]
    if not durs:
        return None
    return sum(durs) / 1e9 / len(rec.cycles)
