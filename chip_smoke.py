"""Smoke run of the AMR+LBM main path on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four chips of one host

One chip: the lid-driven cavity of ``benchmarks/scenario.py`` at 16^3 cells
per block on an 8x8x8 root grid (512 root blocks, one refinement level, D3Q19,
TRT) is stepped through ``AMRLBM`` -> ``FusedEngine`` -> the Pallas kernel,
compiled natively, across an AMR event. The same configuration is then
stepped with the jnp reference stencil in the same process, and the two runs
must agree.

Four chips: ``device_sharded`` with one rank per chip is stepped against
``fused`` on one chip of the same process, same configuration and schedule.

Every check is an assert; a failed check ends the process with a traceback
and a non-zero exit. The last line of stdout is a JSON object naming the
device, printed only when every check passed. Without a TPU the script fails
before it prints anything. The timings it prints are smoke timings of single
steps, not a benchmark.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))  # benchmarks.scenario

ROOT_GRID = (8, 8, 8)
CELLS = (16, 16, 16)
# coarse steps before the AMR event and after it; the second step of each
# topology is the steady one (the first includes its build and compile)
SCHEDULE = ("step", "step", "adapt", "step", "step")

# Agreement between two runs, on interior fluid cells. Both runs store f32
# and differ only in rounding order (Pallas kernel vs jnp stencil, or one
# device vs four): each of the <= 2 * 4 stencil applications of the schedule
# adds a few ulps, and the flow carries them along without amplifying them.
# 2e-5 is ~170 f32 ulps at unit density; a wrong streaming, bounce-back or
# collision term moves density or velocity by 1e-3 or more.
RHO_ATOL = 2e-5
U_ATOL = 2e-5
# Walls and the moving lid conserve mass exactly, and AMR's restriction and
# prolongation conserve it too; what remains is f32 round-off of ~2.5e6 cell
# updates per step and of the per-block sums, well under 1e-6 relative.
MASS_RTOL = 1e-5

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def base_config(stepping_mode: str, kernel_backend: str, nranks: int):
    from benchmarks.scenario import cavity_config

    return dataclasses.replace(
        cavity_config(
            nranks=nranks,
            stepping_mode=stepping_mode,
            cells_per_block=CELLS,
            kernel_backend=kernel_backend,
        ),
        root_grid=ROOT_GRID,
    )


def _blocks_per_level(sim) -> dict[int, int]:
    out: dict[int, int] = {}
    for b in sim.forest.all_blocks():
        out[b.level] = out.get(b.level, 0) + 1
    return dict(sorted(out.items()))


def _topology(sim) -> list[tuple[int, int]]:
    return sorted((b.bid, b.level) for b in sim.forest.all_blocks())


def _fields(sim) -> dict[int, tuple[np.ndarray, np.ndarray, np.ndarray]]:
    """bid -> (rho, u, fluid) on the block interior, from host pdfs."""
    from repro.lbm.criteria import macroscopic
    from repro.lbm.grid import CellType

    sim.materialize_host()
    out = {}
    for b in sim.forest.all_blocks():
        rho, u = macroscopic(b.data["pdf"], sim.spec.lattice)
        fluid = sim.spec.interior(b.data["mask"]) == CellType.FLUID
        out[b.bid] = (sim.spec.interior(rho), sim.spec.interior(u), fluid)
    return out


def run_case(label: str, cfg, *, after_steps=None) -> dict:
    """Build ``AMRLBM(cfg)`` and run :data:`SCHEDULE`, printing each phase.

    ``after_steps(sim)`` runs once after the last step, before the results
    are read back. Returns the topologies after each AMR event, the final
    interior fields, masses and timings."""
    import jax

    from repro.lbm import AMRLBM

    compile_s = [0.0]

    def on_event(event: str, duration_secs: float, **_kw) -> None:
        if event == _COMPILE_EVENT:
            compile_s[0] += duration_secs

    jax.monitoring.register_event_duration_secs_listener(on_event)
    t0 = time.perf_counter()
    sim = AMRLBM(cfg)
    nblocks = sim.forest.num_blocks()
    ncells = nblocks * int(np.prod(cfg.cells_per_block))
    print(f"[{label}] build: {nblocks} blocks {_blocks_per_level(sim)}, "
          f"{ncells} cells, {time.perf_counter() - t0:.3f} s", flush=True)
    mass0 = sim.total_mass()
    topologies = []
    steady = None
    fresh = True  # next step is the first of its topology
    for phase in SCHEDULE:
        t = time.perf_counter()
        if phase == "step":
            sim.advance(1)  # the engine fences on block_until_ready
            dt = time.perf_counter() - t
            what = "first of topology: build + compile + run" if fresh else "steady"
            print(f"[{label}] coarse step {sim.coarse_step}: {dt:.4f} s ({what})",
                  flush=True)
            if not fresh:
                steady = (dt, sim.forest.num_blocks())
            fresh = False
        else:
            report = sim.adapt()
            assert report.executed, f"[{label}] the AMR event did not execute"
            sim.forest.check_all()
            topologies.append(_topology(sim))
            fresh = True
            nblocks = sim.forest.num_blocks()
            print(f"[{label}] adapt: executed, {nblocks} blocks "
                  f"{_blocks_per_level(sim)}, "
                  f"{nblocks * int(np.prod(cfg.cells_per_block))} cells, "
                  f"{time.perf_counter() - t:.3f} s", flush=True)
    if after_steps is not None:
        after_steps(sim)
    jax.monitoring.unregister_event_duration_listener(on_event)
    print(f"[{label}] backend compile: {compile_s[0]:.3f} s", flush=True)
    print(f"[{label}] steady coarse step (smoke timing, not a benchmark): "
          f"{steady[0]:.4f} s at {steady[1]} blocks", flush=True)
    return dict(
        topologies=topologies,
        fields=_fields(sim),
        mass0=mass0,
        mass=sim.total_mass(),
    )


def check_mass(label: str, run: dict) -> None:
    drift = abs(run["mass"] - run["mass0"]) / run["mass0"]
    print(f"[{label}] mass: initial {run['mass0']!r}, final {run['mass']!r}, "
          f"relative drift {drift:.3e} (bound {MASS_RTOL:g})", flush=True)
    assert drift <= MASS_RTOL, (label, drift)


def compare(a_label: str, a: dict, b_label: str, b: dict) -> None:
    """Assert identical AMR topologies and agreeing interior fields."""
    assert len(a["topologies"]) == len(b["topologies"])
    for i, (ta, tb) in enumerate(zip(a["topologies"], b["topologies"]), 1):
        assert ta == tb, f"topology after AMR event {i} differs"
        print(f"topology after AMR event {i}: identical in {a_label} and "
              f"{b_label} ({len(ta)} blocks)", flush=True)
    assert a["fields"].keys() == b["fields"].keys()
    drho = du = 0.0
    for bid, (rho_a, u_a, fluid) in a["fields"].items():
        rho_b, u_b, fluid_b = b["fields"][bid]
        assert np.array_equal(fluid, fluid_b), bid
        assert np.isfinite(rho_a[fluid]).all() and np.isfinite(u_a[:, fluid]).all()
        drho = max(drho, float(np.abs(rho_a - rho_b)[fluid].max(initial=0.0)))
        du = max(du, float(np.abs(u_a - u_b)[:, fluid].max(initial=0.0)))
    print(f"{a_label} vs {b_label}: max |drho| {drho:.3e} (atol {RHO_ATOL:g}), "
          f"max |du| {du:.3e} (atol {U_ATOL:g})", flush=True)
    assert drho <= RHO_ATOL and du <= U_ATOL, (drho, du)


def one_chip() -> None:
    import jax

    from repro.kernels.lbm_collide import resolve_interpret

    interpret = resolve_interpret(None)
    print(f"pallas interpret resolved: {interpret}", flush=True)
    assert interpret is False

    def lowered_has_kernel(sim) -> None:
        fn, levels = sim.engine._fused_program()
        res = sim.arena.device()
        text = fn.lower(tuple(res.fetch(l, "pdf") for l in levels)).as_text()
        found = "tpu_custom_call" in text
        print(f"[pallas] lowered fused superstep contains tpu_custom_call: {found}",
              flush=True)
        assert found

    cfg = base_config("fused", "pallas", nranks=1)
    print(f"config: fused + pallas, D3Q19 {cfg.collision.upper()}, "
          f"{CELLS[0]}^3 cells/block, root grid {cfg.root_grid}, "
          f"max_level {cfg.max_level}, nranks {cfg.nranks}", flush=True)
    pallas = run_case("pallas", cfg, after_steps=lowered_has_kernel)
    peak = jax.devices()[0].memory_stats()["peak_bytes_in_use"]
    print(f"[pallas] peak device bytes in use: {peak}", flush=True)
    check_mass("pallas", pallas)
    gc.collect()  # release the pallas run's device buffers

    ref = run_case("ref", dataclasses.replace(cfg, kernel_backend="ref"))
    check_mass("ref", ref)
    compare("pallas", pallas, "ref", ref)


def four_chips() -> None:
    import jax

    assert len(jax.devices()) >= 4, jax.devices()
    fused = run_case("fused@1", base_config("fused", "pallas", nranks=1))
    check_mass("fused@1", fused)
    gc.collect()

    def placement(sim) -> None:
        shards = [s for a in sim.engine._dev_pdfs for s in a.addressable_shards]
        per_dev: dict[int, int] = {}
        for s in shards:
            per_dev[s.device.id] = per_dev.get(s.device.id, 0) + s.data.nbytes
        print(f"[device_sharded@4] pdf bytes per device: {per_dev}", flush=True)
        assert sorted(per_dev) == sorted(d.id for d in jax.devices()[:4])
        assert all(v > 0 for v in per_dev.values())

    sharded = run_case(
        "device_sharded@4",
        base_config("device_sharded", "pallas", nranks=4),
        after_steps=placement,
    )
    check_mass("device_sharded@4", sharded)
    compare("device_sharded@4", sharded, "fused@1", fused)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    import jax

    dev = jax.devices()[0]
    assert dev.platform == "tpu", f"no TPU: JAX found {dev.platform}"

    import repro.lbm

    assert Path(repro.lbm.__file__).resolve().is_relative_to(ROOT / "src"), (
        "repro must come from this checkout"
    )
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    print(f"device: {dev.platform} {dev.device_kind}, count {len(jax.devices())}; "
          f"compile cache {cache}", flush=True)
    if args.chips == 4:
        four_chips()
    else:
        one_chip()
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": len(jax.devices()),
        },
    }))


if __name__ == "__main__":
    main()
