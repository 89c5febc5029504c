"""Benchmark harness: one function per paper table/figure.

Prints ``name,metric,value`` CSV rows. Run with:
    PYTHONPATH=src python -m benchmarks.run [--quick]

Mapping to the paper:
  amr_cycle          Tables 4-7 / Figs 8-15: AMR cycle cost per balancer vs N
                     (wall seconds at small N; per-rank bytes/rounds vs N)
  balance_quality    Table 3: avg/max blocks per rank before/after balancing
  diffusion_iters    Figs 10/12: main iterations to perfect balance vs N
  metadata_sync      Table 1: bytes globally replicated per rank (SFC) vs
                     diffusion, weak scaling
  migration_volume   Figs 8/9/11/13 data-migration stage: bytes moved per rank
  lbm_mlups          kernel throughput (MLUPS; the Pallas kernel runs in
                     interpret mode on CPU and natively on a TPU) + the
                     pure-jnp reference path
  stepping           per-substep restacking vs persistent arena vs the fused
                     device superstep vs the rank-sharded data plane (host
                     p2p + device-resident fused_sharded): blocks/s of the
                     full substepping loop, best-of-k timed, swept over
                     --ranks, appended to the BENCH_stepping.json trajectory
  particles          Lagrangian tracer layer: particles/s advected (RK2 +
                     redistribution) per stepping mode + redistribution p2p
                     bytes per step, appended to BENCH_particles.json
  serving            serving layer: batched ensemble vs sequential execution
                     of identical jobs — jobs/s, speedup, and compile-cache
                     hit rate, appended to BENCH_serving.json
  roofline           §Roofline: renders the dry-run artifact table
"""

from __future__ import annotations

import argparse
import math
import time

import numpy as np

from repro.launch.compile_cache import enable_compile_cache


def _csv(name: str, metric: str, value) -> None:
    print(f"{name},{metric},{value}")


def _timed(fn, *args) -> float:
    t0 = time.perf_counter()
    fn(*args)
    return time.perf_counter() - t0


# -----------------------------------------------------------------------------


def amr_cycle(quick: bool = False) -> None:
    """One full AMR stress cycle per balancer; wall time + comm volume."""
    from repro.core import AMRPipeline, BlockDataRegistry, Comm, DiffusionBalancer, SFCBalancer
    from .scenario import build_scenario, stress_marks

    ranks = (8, 32) if quick else (8, 32, 128)
    balancers = {
        "sfc-morton": lambda: SFCBalancer(order="morton"),
        "sfc-hilbert": lambda: SFCBalancer(order="hilbert"),
        "diff-push": lambda: DiffusionBalancer(mode="push", flow_iterations=15, max_main_iterations=30),
        "diff-pushpull": lambda: DiffusionBalancer(mode="pushpull", flow_iterations=5, max_main_iterations=30),
    }
    for nranks in ranks:
        for name, make in balancers.items():
            forest, geom = build_scenario(nranks)
            for b in forest.all_blocks():
                b.data["payload"] = np.zeros(512, np.float32)  # 2 KiB stand-in
            comm = Comm(nranks)
            pipe = AMRPipeline(balancer=make(), registry=BlockDataRegistry.trivial("payload"))
            t0 = time.perf_counter()
            forest, rep = pipe.run_cycle(forest, comm, stress_marks(geom))
            dt = time.perf_counter() - t0
            _csv(f"amr_cycle/{name}", f"n{nranks}_wall_s", round(dt, 4))
            _csv(f"amr_cycle/{name}", f"n{nranks}_coll_bytes_per_rank", comm.stats.collective_bytes_per_rank)
            _csv(f"amr_cycle/{name}", f"n{nranks}_p2p_bytes_per_rank_max", comm.stats.max_sent_bytes_per_rank)
            _csv(f"amr_cycle/{name}", f"n{nranks}_balance_iters", rep.main_iterations)


def balance_quality(quick: bool = False) -> None:
    """Table 3: avg/max blocks per rank, before and after load balancing."""
    from repro.core import Comm, DiffusionBalancer
    from repro.core.proxy import build_proxy, migrate_proxy_blocks
    from repro.core.refine import mark_and_balance_targets
    from .scenario import build_scenario, stress_marks

    nranks = 32
    forest, geom = build_scenario(nranks)
    comm = Comm(nranks)
    changed, ghost = mark_and_balance_targets(forest, comm, stress_marks(geom))
    proxy = build_proxy(forest, comm, ghost)
    levels = proxy.levels_in_use()
    for lvl in levels:
        counts = proxy.blocks_per_rank(lvl)
        _csv("balance_quality", f"L{lvl}_before_avg", round(sum(counts) / nranks, 3))
        _csv("balance_quality", f"L{lvl}_before_max", max(counts))
    balancer = DiffusionBalancer(mode="pushpull", flow_iterations=5, max_main_iterations=30)
    it = 0
    while True:
        assignments, again = balancer(proxy, comm, it)
        migrate_proxy_blocks(proxy, forest, comm, assignments)
        it += 1
        if not again:
            break
    for lvl in levels:
        counts = proxy.blocks_per_rank(lvl)
        ceil = math.ceil(sum(counts) / nranks)
        _csv("balance_quality", f"L{lvl}_after_avg", round(sum(counts) / nranks, 3))
        _csv("balance_quality", f"L{lvl}_after_max", max(counts))
        _csv("balance_quality", f"L{lvl}_perfect_max", ceil)


def diffusion_iters(quick: bool = False) -> None:
    """Figs 10/12: main iterations to perfect balance vs rank count."""
    from repro.core import AMRPipeline, BlockDataRegistry, Comm, DiffusionBalancer
    from .scenario import build_scenario, stress_marks

    ranks = (8, 32) if quick else (8, 16, 32, 64, 128)
    for mode, flows in (("push", 15), ("pushpull", 5)):
        for nranks in ranks:
            forest, geom = build_scenario(nranks)
            comm = Comm(nranks)
            bal = DiffusionBalancer(mode=mode, flow_iterations=flows, max_main_iterations=40)
            pipe = AMRPipeline(balancer=bal, registry=BlockDataRegistry.trivial())
            forest, rep = pipe.run_cycle(forest, comm, stress_marks(geom))
            _csv(f"diffusion_iters/{mode}", f"n{nranks}", rep.main_iterations)


def metadata_sync(quick: bool = False) -> None:
    """Table 1: per-rank bytes held after the balancing synchronization."""
    from repro.core import AMRPipeline, BlockDataRegistry, Comm, DiffusionBalancer, SFCBalancer
    from .scenario import build_scenario, stress_marks

    ranks = (8, 32) if quick else (8, 32, 128)
    cases = {
        "sfc_per_level_ids": lambda: SFCBalancer(per_level=True, weighted=False),
        "sfc_per_level_weighted": lambda: SFCBalancer(per_level=True, weighted=True),
        "sfc_flat_counts": lambda: SFCBalancer(per_level=False, weighted=False),
        "diffusion": lambda: DiffusionBalancer(mode="pushpull", flow_iterations=5, max_main_iterations=20),
    }
    for nranks in ranks:
        for name, make in cases.items():
            forest, geom = build_scenario(nranks)
            comm = Comm(nranks)
            pipe = AMRPipeline(balancer=make(), registry=BlockDataRegistry.trivial())
            pipe.run_cycle(forest, comm, stress_marks(geom))
            _csv(f"metadata_sync/{name}", f"n{nranks}_bytes_per_rank", comm.stats.collective_bytes_per_rank)


def migration_volume(quick: bool = False) -> None:
    """Data-migration stage volume per balancer (Figs 8/9/11/13 breakdown)."""
    from repro.core import AMRPipeline, BlockDataRegistry, Comm, DiffusionBalancer, SFCBalancer
    from .scenario import build_scenario, stress_marks

    nranks = 32
    for name, make in (
        ("sfc-morton", lambda: SFCBalancer(order="morton")),
        ("diff-pushpull", lambda: DiffusionBalancer(mode="pushpull", flow_iterations=5, max_main_iterations=30)),
    ):
        forest, geom = build_scenario(nranks)
        for b in forest.all_blocks():
            b.data["payload"] = np.zeros(16384, np.float32)  # 64 KiB per block
        comm = Comm(nranks)
        pipe = AMRPipeline(balancer=make(), registry=BlockDataRegistry.trivial("payload"))
        forest, rep = pipe.run_cycle(forest, comm, stress_marks(geom))
        mig = rep.stages.get("migrate")
        bal = rep.stages.get("balance")
        _csv(f"migration_volume/{name}", "migrate_bytes_total", mig.p2p_bytes)
        _csv(f"migration_volume/{name}", "balance_bytes_total", bal.p2p_bytes)
        _csv(f"migration_volume/{name}", "proxy_blocks_moved", rep.proxy_blocks_moved)


def lbm_mlups(quick: bool = False) -> None:
    """Fused stream-collide throughput. On CPU the Pallas kernel runs in
    interpret mode, whose wall time says nothing about a TPU."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.lbm_collide.ops import make_stream_collide
    from repro.lbm.lattice import D3Q19

    B, n = (2, 16) if quick else (4, 32)
    rng = np.random.default_rng(0)
    f = jnp.asarray(
        np.asarray(D3Q19.w, np.float32)[None, :, None, None, None]
        * (1 + 0.01 * rng.standard_normal((B, 19, n, n, n)).astype(np.float32))
    )
    mask = jnp.zeros((B, n, n, n), jnp.int32)
    for backend in ("ref", "pallas"):
        step = make_stream_collide(omega=1.6, backend=backend)
        out = step(f, mask)
        out.block_until_ready()
        t0 = time.perf_counter()
        reps = 3 if backend == "pallas" else 10
        for _ in range(reps):
            out = step(out, mask)
        out.block_until_ready()
        dt = (time.perf_counter() - t0) / reps
        mlups = B * n**3 / dt / 1e6
        _csv(f"lbm_mlups/{backend}", f"cells{B * n**3}", round(mlups, 3))


def stepping(
    quick: bool = False,
    *,
    best_of: int | None = None,
    ranks: tuple[int, ...] = (4,),
    steps: int | None = None,
    trace: str | None = None,
) -> None:
    """Per-substep restacking (seed) vs persistent arena vs the device-
    resident fused superstep vs the rank-sharded data plane (host p2p and
    device-resident fused_sharded) on the lid-driven-cavity config: blocks/s
    throughput of the full substepping loop (halo exchange + fused kernel),
    swept over simulated rank counts, appended to the BENCH_stepping.json
    trajectory (entry schema + append protocol: see README "Benchmark
    trajectories", guarded by benchmarks/check_stepping.py in CI).

    Single runs on a shared host are noise-bound (observed ~1.6x swings), so
    every timing is best-of-``best_of`` (default 2 quick / 3 full).

    With ``trace`` (a directory), telemetry is enabled for the timed region
    and one Chrome-trace artifact per (mode, nranks) is written there —
    render with ``tools/trace_report.py``. Tracing adds span overhead to the
    timed loops, so traced timings are not comparable with untraced entries.
    """
    from pathlib import Path

    from repro import telemetry
    from repro.lbm import AMRLBM

    from .scenario import cavity_config

    if trace:
        telemetry.configure(enabled=True)
    coarse = steps if steps is not None else (2 if quick else 4)
    k = best_of if best_of is not None else (2 if quick else 3)
    k = max(1, k)
    cells = (8, 8, 8) if quick else (16, 16, 16)
    # per-coarse-step stage attribution of the timed region (halo / step /
    # fused seconds from data_stats — exactly the spans, see telemetry docs)
    data_stages = ("halo", "step", "fused")
    traj_entries = []
    # restack/arena/fused never consult Block.owner, so their timings are
    # rank-independent: measure them once and reuse across the sweep
    baseline: dict[str, tuple[float, float, int, float, dict]] = {}
    rank_dependent = ("sharded", "fused_sharded", "device_sharded")
    for nranks in ranks:
        results: dict[str, float] = {}
        halo_bytes: dict[str, int] = {}
        wall: dict[str, float] = {}
        compile_s: dict[str, float] = {}
        stage_s: dict[str, dict[str, float]] = {}
        for mode in (
            "restack", "arena", "fused", "sharded", "fused_sharded",
            "device_sharded",
        ):
            if mode == "device_sharded":
                import jax

                if jax.device_count() < nranks:
                    print(
                        f"stepping: skipping device_sharded at n{nranks} "
                        f"(only {jax.device_count()} XLA device(s); set "
                        "XLA_FLAGS=--xla_force_host_platform_device_count"
                        f"={nranks})"
                    )
                    continue
            if mode not in rank_dependent and mode in baseline:
                (
                    results[mode], wall[mode], halo_bytes[mode],
                    compile_s[mode], stage_s[mode],
                ) = baseline[mode]
            else:
                cfg = cavity_config(
                    nranks=nranks, stepping_mode=mode, cells_per_block=cells
                )
                sim = AMRLBM(cfg)
                sim.advance(1)  # warm up the L0 stepper jit
                sim.adapt()  # develop the two-level structure
                # first post-adapt advance pays the program rebuild + jit for
                # the two-level topology: report it as compile_s, never fold
                # it into the throughput timing below
                compile_s[mode] = _timed(sim.advance, 1)
                sim.advance(1)  # explicit untimed steady-state warmup
                # block-steps per coarse step: level-l blocks substep 2^l times
                work = sum(
                    (2**l) * sum(1 for b in sim.forest.all_blocks() if b.level == l)
                    for l in sim.forest.levels_in_use()
                )
                # fused_sharded/device_sharded route their in-program device
                # messages through Comm but attribute them to the "fused"
                # stage (halo and step are indistinguishable inside the
                # per-rank / shard_map programs)
                stage = (
                    "fused"
                    if mode in ("fused_sharded", "device_sharded")
                    else "halo"
                )
                h0 = sim.data_stats[stage].p2p_bytes
                sec0 = {st: sim.data_stats[st].seconds for st in data_stages}
                if trace:
                    telemetry.get_tracer().reset()  # one artifact per mode
                dt = min(_timed(sim.advance, coarse) for _ in range(k))
                if trace:
                    telemetry.export.write_chrome_trace(
                        Path(trace) / f"stepping_{mode}_n{nranks}.trace.json"
                    )
                results[mode] = coarse * work / dt
                wall[mode] = dt
                # normalized to one coarse step of the timed region, so
                # entries are comparable across --best-of / --steps choices
                halo_bytes[mode] = (
                    sim.data_stats[stage].p2p_bytes - h0
                ) // (k * coarse)
                stage_s[mode] = {
                    st: round(
                        (sim.data_stats[st].seconds - sec0[st]) / (k * coarse), 6
                    )
                    for st in data_stages
                    if sim.data_stats[st].seconds > sec0[st]
                }
                if mode not in rank_dependent:
                    baseline[mode] = (
                        results[mode], wall[mode], halo_bytes[mode],
                        compile_s[mode], stage_s[mode],
                    )
            _csv(f"stepping/{mode}", f"n{nranks}_blocks_per_s", round(results[mode], 1))
            _csv(f"stepping/{mode}", f"n{nranks}_wall_s", round(wall[mode], 4))
            _csv(f"stepping/{mode}", f"n{nranks}_compile_s", round(compile_s[mode], 4))
        speedup = results["arena"] / results["restack"]
        fused_rel = results["fused"] / results["restack"]
        sharded_rel = results["sharded"] / results["restack"]
        fsh_rel = results["fused_sharded"] / results["restack"]
        _csv("stepping", f"n{nranks}_arena_speedup", round(speedup, 3))
        _csv("stepping", f"n{nranks}_fused_speedup", round(fused_rel, 3))
        _csv("stepping", f"n{nranks}_sharded_speedup", round(sharded_rel, 3))
        _csv("stepping", f"n{nranks}_fused_sharded_speedup", round(fsh_rel, 3))
        _csv("stepping", f"n{nranks}_sharded_halo_bytes_per_step", halo_bytes["sharded"])
        # device_sharded is present only when the process has >= nranks XLA
        # devices (see the skip above), so its keys are optional in the
        # trajectory schema (validated when present by check_stepping.py)
        dev_extra: dict[str, float | int] = {}
        if "device_sharded" in results:
            dev_rel = results["device_sharded"] / results["restack"]
            _csv("stepping", f"n{nranks}_device_sharded_speedup", round(dev_rel, 3))
            dev_extra = {
                "device_sharded_speedup": round(dev_rel, 3),
                "device_sharded_halo_p2p_bytes_per_step": halo_bytes[
                    "device_sharded"
                ],
            }
        traj_entries.append(
            {
                "scenario": "lid-driven-cavity",
                "cells_per_block": list(cells),  # quick/full differ ~8x in blocks/s
                "quick": quick,
                "coarse_steps": coarse,
                "best_of": k,
                "nranks": nranks,
                "blocks_per_s": {m: round(v, 1) for m, v in results.items()},
                "compile_s": {m: round(v, 4) for m, v in compile_s.items()},
                # mode -> {halo/step/fused: seconds per coarse step of the
                # timed region}; sums to ~wall/(best_of*coarse) per mode
                "stage_seconds_per_step": dict(stage_s),
                "arena_speedup": round(speedup, 3),
                "fused_speedup": round(fused_rel, 3),
                "sharded_speedup": round(sharded_rel, 3),
                "fused_sharded_speedup": round(fsh_rel, 3),
                "sharded_halo_p2p_bytes_per_step": halo_bytes["sharded"],
                "fused_sharded_halo_p2p_bytes_per_step": halo_bytes["fused_sharded"],
                **dev_extra,
            }
        )
    _append_trajectory("stepping", "BENCH_stepping.json", traj_entries)


def _append_trajectory(bench: str, filename: str, entries: list[dict]) -> None:
    """Append entries to a committed JSON trajectory (atomic, corruption-safe
    — same protocol as the stepping trajectory). Warnings are reported under
    ``bench`` in the name column, like every other row the bench emits."""
    import json
    from pathlib import Path

    traj_path = Path(__file__).resolve().parents[1] / filename
    try:
        traj = json.loads(traj_path.read_text())
        if not isinstance(traj, list):
            raise ValueError("trajectory is not a list")
    except OSError:  # no trajectory yet
        traj = []
    except ValueError:  # corrupt/partial/wrong shape: preserve aside, don't wipe
        bad = traj_path.with_suffix(".json.corrupt")
        traj_path.replace(bad)
        _csv(bench, "trajectory_warning", f"unreadable, moved to {bad.name}")
        traj = []
    traj.extend(entries)
    tmp = traj_path.with_suffix(".json.tmp")
    tmp.write_text(json.dumps(traj, indent=2) + "\n")
    tmp.replace(traj_path)  # atomic: a killed run can't truncate the trajectory


def particles(quick: bool = False) -> None:
    """Lagrangian tracer throughput: particles advected per second (trilinear
    RK2 + redistribution, the whole data_stats["particles"] stage) per
    stepping mode, plus redistribution p2p bytes and block moves per coarse
    step. Tracers are clustered under the lid so the run exercises the
    heterogeneous cells + alpha*N load model and real redistribution."""
    from repro.lbm import AMRLBM
    from repro.particles import ParticlesConfig

    from .scenario import cavity_config

    per_block = 64 if quick else 256
    coarse = 2 if quick else 4
    nranks = 4
    traj_entries = []
    for mode in ("arena", "sharded"):
        cfg = cavity_config(
            nranks=nranks,
            stepping_mode=mode,
            particles=ParticlesConfig(
                per_block=per_block,
                seed=1,
                alpha=0.05,
                region=((0.0, 0.0, 1.5), (2.0, 2.0, 2.0)),
            ),
        )
        sim = AMRLBM(cfg)
        sim.advance(1)  # warm up steppers + the advection kernel jit
        sim.adapt()  # develop the two-level structure
        sim.advance(1)
        n = sim.total_particles()
        st = sim.data_stats["particles"]
        t0, b0, m0 = st.seconds, st.p2p_bytes, sim.particles_moved
        sim.advance(coarse)
        dt = st.seconds - t0
        pps = n * coarse / max(dt, 1e-9)
        redist_bytes = (st.p2p_bytes - b0) // coarse
        moved = (sim.particles_moved - m0) / coarse
        _csv(f"particles/{mode}", "num_particles", n)
        _csv(f"particles/{mode}", "particles_per_s", round(pps, 1))
        _csv(f"particles/{mode}", "redist_p2p_bytes_per_step", redist_bytes)
        _csv(f"particles/{mode}", "moved_per_step", round(moved, 2))
        traj_entries.append(
            {
                "scenario": "lid-driven-cavity-tracers",
                "quick": quick,
                "mode": mode,
                "nranks": nranks,
                "coarse_steps": coarse,
                "num_particles": n,
                "particles_per_s": round(pps, 1),
                "redist_p2p_bytes_per_step": int(redist_bytes),
                "moved_per_step": round(moved, 2),
            }
        )
    _append_trajectory("particles", "BENCH_particles.json", traj_entries)


def serving(quick: bool = False) -> None:
    """Serving-layer amortization: the same 4 identical jobs executed as one
    batched ensemble (shared compiled superstep, per-member coefficients as
    batched operands) vs sequentially as independent fused runs (each paying
    its own program compiles). Emits jobs/s for both paths, the speedup, and
    the batched path's compile-cache hit rate; appends to the
    BENCH_serving.json trajectory (guarded by benchmarks/check_serving.py)."""
    from repro.serving import JobSpec, SimulationService

    from .scenario import cavity_config

    njobs = 4
    steps = 8 if quick else 12
    interval = 4

    def run_jobs(batching: bool) -> tuple[float, SimulationService]:
        svc = SimulationService(batching=batching)
        # sequential baseline = today's best solo path (device-resident fused)
        mode = "arena" if batching else "fused"
        for _ in range(njobs):
            svc.submit(
                JobSpec(
                    config=cavity_config(stepping_mode=mode),
                    coarse_steps=steps,
                    amr_interval=interval,
                    collect_diagnostics=False,
                )
            )
        t0 = time.perf_counter()
        svc.run()
        return time.perf_counter() - t0, svc

    seq_dt, _seq = run_jobs(batching=False)
    bat_dt, bat = run_jobs(batching=True)
    seq_jps = njobs / seq_dt
    bat_jps = njobs / bat_dt
    speedup = bat_jps / seq_jps
    s = bat.summary()
    _csv("serving/sequential", "jobs_per_s", round(seq_jps, 3))
    _csv("serving/batched", "jobs_per_s", round(bat_jps, 3))
    _csv("serving", "batched_speedup", round(speedup, 3))
    _csv("serving", "compile_cache_hit_rate", round(s["compile_cache_hit_rate"], 3))
    _csv("serving", "compile_misses", s["compile_misses"])
    _csv("serving", "divergence_splits", s["divergence_splits"])
    _append_trajectory(
        "serving",
        "BENCH_serving.json",
        [
            {
                "scenario": "lid-driven-cavity",
                "quick": quick,
                "njobs": njobs,
                "coarse_steps": steps,
                "amr_interval": interval,
                "sequential_jobs_per_s": round(seq_jps, 3),
                "batched_jobs_per_s": round(bat_jps, 3),
                "batched_speedup": round(speedup, 3),
                "compile_hits": s["compile_hits"],
                "compile_misses": s["compile_misses"],
                "compile_cache_hit_rate": round(s["compile_cache_hit_rate"], 3),
                "divergence_splits": s["divergence_splits"],
            }
        ],
    )


def roofline(quick: bool = False) -> None:
    """Render the §Roofline table from the dry-run artifacts."""
    import json
    from pathlib import Path

    art_dir = Path(__file__).resolve().parents[1] / "experiments" / "dryrun"
    rows = sorted(art_dir.glob("*.json"))
    if not rows:
        _csv("roofline", "status", "no dry-run artifacts (run repro.launch.dryrun)")
        return
    for path in rows:
        d = json.loads(path.read_text())
        r = d["roofline"]
        name = f"{d['arch']}/{d['shape']}/{d['mesh']}"
        _csv(f"roofline/{name}", "dominant", r["dominant"])
        _csv(f"roofline/{name}", "compute_s", f"{r['compute_s']:.4g}")
        _csv(f"roofline/{name}", "memory_s", f"{r['memory_s']:.4g}")
        _csv(f"roofline/{name}", "collective_s", f"{r['collective_s']:.4g}")
        _csv(f"roofline/{name}", "roofline_fraction", f"{r.get('roofline_fraction', 0):.3f}")
        _csv(f"roofline/{name}", "useful_ratio", d["flops"]["useful_ratio"])


ALL = {
    "amr_cycle": amr_cycle,
    "balance_quality": balance_quality,
    "diffusion_iters": diffusion_iters,
    "metadata_sync": metadata_sync,
    "migration_volume": migration_volume,
    "lbm_mlups": lbm_mlups,
    "stepping": stepping,
    "particles": particles,
    "serving": serving,
    "roofline": roofline,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", action="append", choices=sorted(ALL), default=None)
    ap.add_argument(
        "--best-of", type=int, default=None,
        help="stepping: timings are best-of-K (default 2 quick / 3 full)",
    )
    ap.add_argument(
        "--ranks", type=str, default="4",
        help="stepping: comma-separated simulated rank counts to sweep",
    )
    ap.add_argument(
        "--steps", type=int, default=None,
        help="stepping: coarse steps per timed run (default 2 quick / 4 full)",
    )
    ap.add_argument(
        "--trace", type=str, default=None,
        help="stepping: enable telemetry and write one Chrome-trace artifact "
             "per (mode, nranks) into this directory",
    )
    args = ap.parse_args()
    enable_compile_cache()
    names = args.only or list(ALL)
    ranks = tuple(int(r) for r in args.ranks.split(",") if r)
    print("name,metric,value")
    for name in names:
        t0 = time.perf_counter()
        if name == "stepping":
            stepping(quick=args.quick, best_of=args.best_of, ranks=ranks,
                     steps=args.steps, trace=args.trace)
        else:
            ALL[name](quick=args.quick)
        _csv(name, "bench_wall_s", round(time.perf_counter() - t0, 2))


if __name__ == "__main__":
    main()
