"""Device time of the halo permutes per coarse step, averaged over the
chips: the union of the intervals of the ops whose own instruction is a
collective-permute (the synchronous form, or an asynchronous pair's
``-start`` or ``-done``), from the trace.

An op is judged by its own name (before `` = ``) or its opcode, never by
its operands: a fusion that reads a ``collective-permute-done`` is compute,
not the permute."""

from __future__ import annotations

import re

from bench import trace

PERMUTE_OPS = frozenset({"collective-permute", "collective-permute-start",
                         "collective-permute-done"})
# control flow traced around the ops of its body: not an op that runs
WRAPPER_OPS = frozenset({"while", "conditional", "call"})
_HEAD = re.compile(r"\s*%?([A-Za-z_][\w.\-]*)\s*(=)?")
_OPCODE = re.compile(r"(?:^|\s)([a-z][a-z0-9\-]*)\(")


def name_and_opcode(text: str) -> tuple[str, str | None]:
    """The instruction's own name without ``%`` and numeric suffix, and its
    opcode where the text is a whole instruction (``%name = shape op(...)``)."""
    m = _HEAD.match(text)
    if m is None:
        return "", None
    name = re.sub(r"\.\d+$", "", m.group(1))
    if m.group(2) is None:
        return name, None
    op = _OPCODE.search(text, m.end())
    return name, (op.group(1) if op else None)


def is_permute(op: trace.Op) -> bool:
    name, opcode = name_and_opcode(op.name)
    return name in PERMUTE_OPS or opcode in PERMUTE_OPS


def is_other_work(op: trace.Op) -> bool:
    """An op that runs on the chip and is not a permute."""
    name, opcode = name_and_opcode(op.name)
    return not is_permute(op) and (opcode or name) not in WRAPPER_OPS


def per_step_ms(rec, seconds_of_chip) -> float | None:
    """``seconds_of_chip(ops)`` averaged over the traced chips, in ms per
    coarse step of the window."""
    if rec.trace is None or not rec.coarse_steps or not rec.trace.devices:
        return None
    devices = rec.trace.devices.values()
    return 1e3 * sum(seconds_of_chip(ops) for ops in devices) / len(devices) / rec.coarse_steps


def read(rec):
    return per_step_ms(rec, lambda ops: trace.union_ns([o for o in ops if is_permute(o)]) / 1e9)
