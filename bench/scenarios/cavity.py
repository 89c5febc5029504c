"""The source paper's §5.1 lid-driven cavity, built with the program's own
solver (``repro.lbm.AMRLBM``) as a configuration file states it, and the
reads and writes of its state that set-up and the checks need."""

from __future__ import annotations

import numpy as np


def build(cfg: dict):
    """The program's solver, built as the configuration file states."""
    from repro.lbm import AMRLBM, LidDrivenCavityConfig

    p = cfg["program"]
    sim = AMRLBM(LidDrivenCavityConfig(
        root_grid=tuple(cfg["root_grid"]),
        cells_per_block=tuple(cfg["cells_per_block"]),
        ghost=1,
        nranks=int(p["nranks"]),
        omega=float(cfg["omega"]),
        u_lid=tuple(float(v) for v in cfg["u_lid"]),
        collision=cfg["collision"],
        max_level=int(cfg["max_level"]),
        refine_upper=float(cfg["refine_upper"]),
        refine_lower=float(cfg["refine_lower"]),
        balancer=p["balancer"],
        kernel_backend=p["kernel_backend"],
        stepping_mode=p["stepping_mode"],
    ))
    lat = sim.spec.lattice
    if not (np.array_equal(lat.c, np.array(cfg["lattice"]["c"]))
            and np.allclose(lat.w, cfg["lattice"]["w"], rtol=0, atol=1e-15)
            and np.dtype(sim.spec.dtype) == np.dtype(cfg["dtype"])):
        raise RuntimeError("the program's lattice or pdf dtype is not the configuration's")
    return sim


def interiors(sim) -> dict[int, np.ndarray]:
    """bid -> copy of the block's (Q, n, n, n) interior pdfs."""
    sim.materialize_host()
    return {b.bid: np.array(sim.spec.interior(b.data["pdf"])) for b in sim.forest.all_blocks()}


def write_interiors(sim, values: dict[int, np.ndarray]) -> None:
    """Overwrite the blocks' interior pdfs on the host; the device copy is
    dropped so the next step uploads them."""
    if sim.cfg.stepping_mode != "fused":
        raise NotImplementedError("host writes are wired for the fused engine only")
    sim.materialize_host()
    for b in sim.forest.all_blocks():
        sim.spec.interior(b.data["pdf"])[...] = values[b.bid]
    sim.arena.device().drop(name="pdf")


def perturbed(base: dict[int, np.ndarray], cfg: dict, seed: int, amp: float) -> dict:
    """``base`` plus a seeded density and velocity perturbation of the
    equilibrium, ``w_q (drho + 3 c_q . du)`` with normal ``drho``, ``du`` of
    standard deviation ``amp`` per interior cell."""
    rng = np.random.default_rng(abs(int(seed)))
    c = np.array(cfg["lattice"]["c"], dtype=np.float64)
    w = np.array(cfg["lattice"]["w"], dtype=np.float64)
    out = {}
    for bid in sorted(base):
        f = base[bid]
        shape = f.shape[1:]
        drho = rng.standard_normal(shape) * amp
        du = rng.standard_normal((3, *shape)) * amp
        df = w[:, None, None, None] * (drho[None] + 3.0 * np.einsum("qd,dxyz->qxyz", c, du))
        out[bid] = (f + df).astype(f.dtype)
    return out


def block_updates(sim) -> int:
    """Interior cell updates per coarse step: a level-l block steps 2^l times."""
    n3 = int(np.prod(sim.cfg.cells_per_block))
    return sum(n3 << b.level for b in sim.forest.all_blocks())


def topology(sim) -> list[int]:
    return sorted(b.bid for b in sim.forest.all_blocks())
