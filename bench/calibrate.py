"""Readings that a cell's check limits are set from.

    python bench/calibrate.py --workload <cell> --seeds 101,102,... --units N

One process builds the cell's solver and fixes its topology once; then, for
each seed, it puts the seed's perturbation in, runs the warm-up and ``N``
window units, and collects what the check compares. For every seed it
prints one JSON line with two readings of each compared number: the
program's against the plain reference (``program``: sound runs, the lower
reading) and the reference computed in bfloat16 put in the program's place
(``control``: the upper reading). The last line is the largest program
reading and the smallest control reading of each number. The benchmark's
own runs never run the control.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[1]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--units", type=int, required=True, help="window units per seed")
    args = ap.parse_args()
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CHECKOUT / ".jax_cache")
    sys.path.insert(0, str(CHECKOUT))
    from bench import harness

    cell = harness.load_cell(CHECKOUT, args.workload)
    harness.program_path()
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    dev = jax.devices()[0]
    check = cell.traffic["check"]

    def log(msg: str) -> None:
        print(msg, file=sys.stderr, flush=True)

    run = harness.Run(cell)
    base = run.prepare()
    worst: dict[str, float] = {}
    least: dict[str, float] = {}
    for seed in (int(s) for s in args.seeds.split(",")):
        state, tops = run.start(base, seed)
        rec = harness.RunRecord(chips=cell.chips, lattice_c=cell.config["lattice"]["c"])
        run.window(rec, tops, units=args.units)
        data = run.collect(state, tops)
        prog = run.check.reduce(data, cell.config, log=log)
        ctrl = run.check.reduce(data, cell.config, control=True, log=log)
        for k in check["limits"]:
            worst[k] = max(worst.get(k, 0.0), prog[k])
            least[k] = min(least.get(k, float("inf")), ctrl[k])
        print(json.dumps({"seed": seed, "platform": dev.platform, "units": args.units,
                          "coarse_steps": rec.coarse_steps, "cycles": len(rec.cycles),
                          "program": prog, "control": ctrl}), flush=True)
    print(json.dumps({"workload": cell.name, "platform": dev.platform,
                      "program_max": worst, "control_min": least,
                      "limits": check["limits"]}), flush=True)


if __name__ == "__main__":
    main()
