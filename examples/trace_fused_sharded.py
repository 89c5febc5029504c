"""Generate the committed example telemetry trace: a 4-rank ``fused_sharded``
run whose timeline shows the per-substep emit/interior/route/absorb phases
(the PR 7 overlap structure), the AMR pipeline stages around an AMR event,
halo plan compiles, h2d/d2h residency traffic, and per-pair p2p byte
counters — everything ``tools/trace_report.py`` renders.

The 6x6x6 root grid matters: with 4 ranks, every rank then owns blocks with
no cross-rank face, so the interior/boundary split of the fused_sharded
substep actually engages (on a 4x4x4 grid every block of every rank is a
boundary block and no ``interior`` span ever appears).

    PYTHONPATH=src python examples/trace_fused_sharded.py \
        [--out examples/traces/fused_sharded_4rank.trace.json]
    python tools/trace_report.py examples/traces/fused_sharded_4rank.trace.json
"""

import argparse

from repro import telemetry
from repro.lbm import AMRLBM, LidDrivenCavityConfig


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--out", default="examples/traces/fused_sharded_4rank.trace.json"
    )
    ap.add_argument("--steps", type=int, default=4)
    args = ap.parse_args()

    telemetry.configure(enabled=True, capacity=8192)
    cfg = LidDrivenCavityConfig(
        root_grid=(6, 6, 6),
        cells_per_block=(4, 4, 4),
        nranks=4,
        max_level=1,
        stepping_mode="fused_sharded",
    )
    sim = AMRLBM(cfg)
    sim.advance(args.steps // 2)
    sim.adapt(force_rebalance=True)  # the AMR event the timeline spans
    sim.advance(args.steps - args.steps // 2)

    path = telemetry.export.write_chrome_trace(args.out)
    tr = telemetry.get_tracer()
    phases = sorted({r.name for r in tr.records() if r.cat == "substep"})
    print(f"wrote {path} ({len(tr.records())} records)")
    print(f"substep phases: {phases}")
    print(f"per-rank buffers: {tr.buffer_stats()}")


if __name__ == "__main__":
    main()
