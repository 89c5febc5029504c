"""Check of AMR cycles: the seeded start state of the warm-up is kept, and
after the window the blocks' pdfs are read back. The plain reference
follows the start state through every cycle on a dense finest-level grid
(refinement replicates, coarsening averages) and predicts each cycle's
topology; a different topology makes the gap infinite. Compared: the
largest pdf gap over all blocks."""

from __future__ import annotations

import time

import numpy as np


def collect(run, _check, start: dict, topologies: list) -> dict:
    """``run`` is the harness's :class:`~bench.harness.Run` after the window."""
    return {"final": run.scenario.interiors(run.sim), "start": start,
            "topologies": list(topologies)}


def reduce(data: dict, cfg: dict, *, control: bool = False, log=print) -> dict:
    """The blocks' pdfs after every cycle of the run, against the reference's
    following of the start state through the cycles it predicts; with
    ``control`` the reference in bfloat16 takes the program's place."""
    from bench import reference

    t = time.perf_counter()
    tops = data["topologies"]
    ref = reference.MigrationReference(data["start"], tops[0], cfg)
    low = reference.MigrationReference(data["start"], tops[0], cfg, dtype="bfloat16")
    for got in tops[1:]:
        want = ref.stress_topology()
        if got != want:
            log(f"topology after a cycle differs from the reference's "
                f"({len(got)} vs {len(want)} blocks)")
            return {"max_dpdf": float("inf")}
        ref.cycle(want)
        if control:
            low.cycle(want)
    final = {b: low.block(b) for b in tops[-1]} if control else data["final"]
    gap = 0.0
    for bid, f in final.items():
        if not np.isfinite(f).all():
            return {"max_dpdf": float("inf")}
        gap = max(gap, float(np.abs(f - ref.block(bid)).max()))
    log(f"reference: {len(tops) - 1} AMR cycles in {time.perf_counter() - t:.3f} s")
    return {"max_dpdf": gap}
