"""One AMR cycle under the source paper's §5.1.1 stress trigger, ended by
the upload of the new topology's stacks that the next coarse step would
make.

The trigger is copied here from the program's ``benchmarks/scenario.py`` so
that no change to the program can move the yardstick. With ``max_level`` 1
it coarsens every fine octet into its parent and splits every coarse block
in eight, so about 70% of the cells change size in each cycle and the
forest alternates between two topologies.
"""

from __future__ import annotations


def stress_marks(_rank, blocks):
    """Mark callback: coarsen the finest level, refine the level below it."""
    finest = max((b.level for b in blocks.values()), default=0)
    out = {}
    for bid, blk in blocks.items():
        if blk.level == finest and finest > 0:
            out[bid] = blk.level - 1
        elif blk.level == finest - 1:
            out[bid] = blk.level + 1
    return out


def run(sim) -> dict:
    """The cycle, then the pdf and mask stacks go to the device as
    ``FusedEngine.advance`` would fetch them, and the pdfs are stored back
    as device-newer, so the next cycle's flush downloads them. Returns once
    the uploads have landed."""
    import jax

    crit = sim.criterion
    sim.criterion = stress_marks
    try:
        report = sim.adapt()
    finally:
        sim.criterion = crit
    if not report.executed:
        raise RuntimeError("the stress trigger did not change the forest")
    res = sim.arena.device()
    stacks = []
    for level in sorted({b.level for b in sim.forest.all_blocks()}):
        stacks.append(res.fetch(level, "mask"))
        pdf = res.fetch(level, "pdf")
        res.store(level, "pdf", pdf)
        stacks.append(pdf)
    jax.block_until_ready(stacks)
    return {"stages": {name: s.seconds for name, s in report.stages.items()}}
