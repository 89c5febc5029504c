"""End-to-end driver: 3D lid-driven cavity with dynamic AMR (paper §5.1.1).

Runs the LBM (D3Q19, TRT) with the velocity-gradient refinement criterion,
diffusion load balancing, and per-level time stepping on persistent
LevelArena buffers (use ``--mode fused`` for the device-resident fused
superstep — one jitted program per coarse step — ``--mode restack`` for the
legacy per-substep restacking path, ``--mode sharded`` for the rank-sharded
data plane with cross-rank halo messaging, ``--mode fused_sharded`` for
the per-rank device-resident composition of the two, and ``--mode
device_sharded`` for one rank per XLA device with in-program ``ppermute``
halo routing — needs ``--nranks`` devices, e.g.
``XLA_FLAGS=--xla_force_host_platform_device_count=4 ... --mode
device_sharded --nranks 4``; see the README's "Choosing a stepping mode").
Prints per-epoch diagnostics including the AMR pipeline stage costs and,
per mode, data-plane halo traffic or host<->device transfer counts.

    PYTHONPATH=src python examples/lbm_cavity_amr.py [--steps 12] [--mode arena]
"""

import argparse

from repro.launch.compile_cache import enable_compile_cache
from repro.lbm import AMRLBM, LidDrivenCavityConfig


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=12)
    ap.add_argument("--amr-interval", type=int, default=3)
    ap.add_argument(
        "--mode",
        choices=(
            "arena", "fused", "sharded", "fused_sharded", "device_sharded",
            "restack",
        ),
        default="arena",
    )
    ap.add_argument("--nranks", type=int, default=8)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = LidDrivenCavityConfig(
        root_grid=(2, 2, 2),
        cells_per_block=(8, 8, 8),
        nranks=args.nranks,
        omega=1.6,
        u_lid=(0.08, 0.0, 0.0),
        collision="trt",
        max_level=2,
        refine_upper=0.04,
        refine_lower=0.006,
        balancer="diffusion-pushpull",
        stepping_mode=args.mode,
    )
    sim = AMRLBM(cfg)
    print(f"initial: {sim.forest.num_blocks()} blocks "
          f"({sim.num_fluid_cells()} fluid cells), mass {sim.total_mass():.2f}, "
          f"stepping={args.mode}")
    for epoch in range(args.steps // args.amr_interval):
        sim.advance(args.amr_interval)
        report = sim.adapt()
        sim.forest.check_all()
        levels = {l: sim.forest.blocks_per_rank(l) for l in sim.forest.levels_in_use()}
        print(
            f"step {sim.coarse_step:3d}: blocks={sim.forest.num_blocks():4d} "
            f"levels={sorted(levels)} vmax={sim.max_velocity():.4f} "
            f"mass={sim.total_mass():.2f} amr={'ran' if report.executed else 'skipped'}"
        )
        for lvl, counts in levels.items():
            print(f"    L{lvl}: max/rank={max(counts)} total={sum(counts)}")
    halo = sim.data_stats["halo"]
    if halo.p2p_bytes:
        print(f"halo traffic: {halo.p2p_bytes} bytes in {halo.p2p_messages} "
              f"p2p messages over {halo.exchange_rounds} rounds")
    if args.mode == "fused":
        res = sim.arena.device()
        fused = sim.data_stats["fused"]
        print(f"fused: {fused.exchange_rounds} in-program exchanges, "
              f"{res.h2d_transfers} h2d / {res.d2h_transfers} d2h transfers "
              f"({res.h2d_bytes + res.d2h_bytes} bytes total)")
    if args.mode == "fused_sharded":
        fused = sim.data_stats["fused"]
        residencies = [a.device() for a in sim.arenas.per_rank if a.levels()]
        h2d = sum(r.h2d_transfers for r in residencies)
        d2h = sum(r.d2h_transfers for r in residencies)
        print(f"fused_sharded: {fused.p2p_bytes} device-message bytes in "
              f"{fused.p2p_messages} p2p messages over {fused.exchange_rounds} "
              f"rounds; {h2d} h2d / {d2h} d2h transfers across "
              f"{len(residencies)} ranks")
    if args.mode == "device_sharded":
        fused = sim.data_stats["fused"]
        print(f"device_sharded: {fused.p2p_bytes} ppermute bytes in "
              f"{fused.p2p_messages} p2p messages over {fused.exchange_rounds} "
              f"in-program exchanges; {sim.comm.ppermute_rounds} ppermute "
              f"rounds, {sim.comm.ppermute_pad_bytes} pad bytes, "
              f"{sim.engine.device_held_bytes_per_rank()} held bytes/device")
    print(f"done: {sim.amr_cycles} AMR cycles executed")


if __name__ == "__main__":
    main()
