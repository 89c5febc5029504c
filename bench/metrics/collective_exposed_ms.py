"""The part of ``collective_ms`` in which no other op runs on the same
chip, per coarse step and averaged over the chips: the permutes' device
time that nothing hides. Control flow traced around its body's ops (a
``conditional``, a ``while``) is not another op here."""

from __future__ import annotations

from pathlib import Path

from bench import harness, trace

_cm = harness.load_module(Path(__file__).resolve().parents[2], "metrics", "collective_ms")


def exposed_s(ops) -> float:
    """Seconds of the permutes' union that no other op's interval covers:
    the union of both less the other ops' union."""
    other = [o for o in ops if _cm.is_other_work(o)]
    both = other + [o for o in ops if _cm.is_permute(o)]
    return (trace.union_ns(both) - trace.union_ns(other)) / 1e9


def read(rec):
    return _cm.per_step_ms(rec, exposed_s)
