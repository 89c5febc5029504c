"""Check of steady stepping: after the window, the state, the check's coarse
steps through the window's own call at the timed size, and the state after
them are read back; the plain reference takes the same steps from the same
state. Compared: the largest density and velocity gaps over all interior
cells (every interior cell of the cavity is fluid)."""

from __future__ import annotations

import time

import numpy as np


def _rho_u(f: np.ndarray, c: np.ndarray):
    rho = f.sum(axis=0, dtype=np.float64)
    u = np.einsum("qxyz,qd->dxyz", f.astype(np.float64), c) / rho[None]
    return rho, u


def compare_fields(got: dict, want: dict, cfg: dict) -> dict:
    """Largest density and velocity gaps over all interior cells; a block
    missing on either side, or a value that is not finite, makes both gaps
    infinite."""
    if set(got) != set(want):
        return {"max_drho": float("inf"), "max_du": float("inf")}
    c = np.array(cfg["lattice"]["c"], dtype=np.float64)
    drho = du = 0.0
    for bid, g in got.items():
        rg, ug = _rho_u(g, c)
        rw, uw = _rho_u(want[bid], c)
        if not (np.isfinite(rg).all() and np.isfinite(ug).all()):
            return {"max_drho": float("inf"), "max_du": float("inf")}
        drho = max(drho, float(np.abs(rg - rw).max()))
        du = max(du, float(np.abs(ug - uw).max()))
    return {"max_drho": drho, "max_du": du}


def collect(run, check: dict, _start, _topologies) -> dict:
    """``run`` is the harness's :class:`~bench.harness.Run` after the window."""
    scenario, sim = run.scenario, run.sim
    steps = int(check["coarse_steps"])
    before = scenario.interiors(sim)
    for _ in range(steps):
        run.ops["advance"](sim)
    return {"before": before, "after": scenario.interiors(sim),
            "bids": scenario.topology(sim), "steps": steps}


def reduce(data: dict, cfg: dict, *, control: bool = False, log=print) -> dict:
    """The program's steps against the reference's from the same state; with
    ``control`` the reference in bfloat16 takes the program's place."""
    from bench import reference

    t = time.perf_counter()
    want = reference.step_blocks(data["before"], data["bids"], cfg, data["steps"])
    got = data["after"]
    if control:
        got = reference.step_blocks(data["before"], data["bids"], cfg, data["steps"],
                                    dtype="bfloat16")
    log(f"reference: {data['steps']} coarse steps at {len(data['bids'])} blocks in "
        f"{time.perf_counter() - t:.3f} s")
    return compare_fields(got, want, cfg)
