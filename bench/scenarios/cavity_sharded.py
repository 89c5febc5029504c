"""The §5.1 cavity as ``cavity.py`` builds it, for engines that keep the pdfs
on the device between steps, such as ``device_sharded`` over the chips of a
host: a host write of the state reaches the device through the solver's
``pdfs_written`` hook. Everything else is ``cavity.py``'s."""

from __future__ import annotations

import sys
from pathlib import Path

import numpy as np

from bench import harness

_cavity = harness.load_module(Path(__file__).resolve().parents[2], "scenarios", "cavity")
interiors = _cavity.interiors
perturbed = _cavity.perturbed
topology = _cavity.topology


def build(cfg: dict):
    """``cavity.build``, refused at once by a program whose solver cannot be
    told of host pdf writes (its device copy would keep the old state)."""
    from repro.lbm import AMRLBM

    if not hasattr(AMRLBM, "pdfs_written"):
        raise RuntimeError("AMRLBM has no pdfs_written(): host pdf writes cannot reach "
                           f"the {cfg['program']['stepping_mode']} engine's device copy")
    return _cavity.build(cfg)


def write_interiors(sim, values: dict[int, np.ndarray]) -> None:
    """Overwrite the blocks' interior pdfs on the host and tell the solver,
    so the next step uploads them."""
    sim.materialize_host()
    for b in sim.forest.all_blocks():
        sim.spec.interior(b.data["pdf"])[...] = values[b.bid]
    sim.pdfs_written()


def block_updates(sim) -> int:
    """``cavity.block_updates``; also logs the engine's slot layout where it
    pads each level's stack to the same height on every rank."""
    layout = getattr(sim.engine, "slot_layout", None)
    if layout is not None:
        print(describe_layout(layout(), sim.cfg.cells_per_block), file=sys.stderr, flush=True)
    return _cavity.block_updates(sim)


def describe_layout(layout: dict, cells_per_block) -> str:
    """One line: per level the real blocks on each rank, the slots every step
    runs and the pad's share of them; then the pad's share of the slot
    updates of a coarse step (a level-l slot steps 2^l times)."""
    parts, real, stepped = [], 0, 0
    for level, lay in sorted(layout.items()):
        parts.append(f"L{level} blocks per rank {lay['blocks']} in {lay['slots']} slots "
                     f"(pad {100 * lay['pad_share']:.2f}%)")
        real += sum(lay["blocks"]) << level
        stepped += lay["slots"] << level
    n3 = int(np.prod(cells_per_block))
    return (f"slot layout: {'; '.join(parts)}; pad {100 * (1 - real / stepped):.2f}% of "
            f"{stepped * n3} slot-cell updates per coarse step")
