import os
import random
from pathlib import Path

import numpy as np
import pytest

from repro.core import Comm, ForestGeometry, make_uniform_forest
from repro.lbm.halo import SLAB_DIRECTIONS, slab_slices


@pytest.fixture
def geom():
    return ForestGeometry(root_grid=(2, 2, 1), max_level=8)


@pytest.fixture
def geom3d():
    return ForestGeometry(root_grid=(2, 2, 2), max_level=8)


def make_random_marks(seed: int, p_refine: float = 0.3, p_coarsen: float = 0.3):
    rng = random.Random(seed)

    def mark(rank, blocks):
        out = {}
        for bid, blk in blocks.items():
            x = rng.random()
            if x < p_refine:
                out[bid] = blk.level + 1
            elif x < p_refine + p_coarsen:
                out[bid] = blk.level - 1
        return out

    return mark


# -- float32 conformance tolerance -------------------------------------------
#
# Two different programs that step the same float32 state (stepping modes,
# block layouts, rank counts, batch shapes, donated or not) do the same
# arithmetic, but the backend's compiler may round it differently: XLA
# vectorizes and contracts a stencil differently at different stack sizes,
# so results agree to round-off, not bit for bit. Every such comparison is
# held to the one tolerance below. Comparisons whose exactness follows from
# construction (ids, masks, topology, serialization, pure data movement, two
# runs of one program) stay bitwise and say why where they assert.

F32_EPS = float(np.finfo(np.float32).eps)  # 1.19e-7: float32 unit round-off x 2
CONFORMANCE_SUBSTEPS = 16  # the longest schedule: 8 coarse steps x 2 substeps
F32_ATOL = 1e-6
"""Absolute tolerance on stepped pdfs, density, velocity and tracer positions.

Each substep may round a value of magnitude ``M`` by about ``F32_EPS * M``,
and the conformance schedules run at most ``CONFORMANCE_SUBSTEPS`` substeps
at the finest level: ``16 * 1.19e-7 * M``. The largest pdf is ``M = 0.36``
(the rest population at density 1.08), which gives 6.9e-7, rounded up to
1e-6. Density and velocity are taken from the float32 pdfs in float64
(:func:`macroscopic64`), so they inherit the pdfs' round-off and add none of
their own. Measured on XLA:CPU: the largest drift over all conformance
comparisons is 1.8e-7 on a pdf and 4.4e-7 on a density."""

MASS_RTOL = CONFORMANCE_SUBSTEPS * F32_EPS
"""Relative tolerance on total mass, 1.9e-6.

Collision conserves mass in every cell, so two programs' totals differ only
by round-off: each substep may round a cell's density by ``F32_EPS``
relative, and a total of such cells keeps that relative bound. The check is
relative because the total is a sum over every fluid cell: its absolute
round-off grows with the cell count, and an absolute limit would tighten
with every grid size. Measured on XLA:CPU: at most 8.4e-9."""


def f32_mismatch(got, ref) -> float:
    """How far ``got`` is from ``ref`` in units of :data:`F32_ATOL` (the
    largest absolute difference over it; 0.0 when equal)."""
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    assert got.shape == ref.shape, (got.shape, ref.shape)
    return float(np.abs(got - ref).max(initial=0.0)) / F32_ATOL


def assert_f32_match(got, ref, *, what: str) -> None:
    """Assert two stepped float32 results agree to :data:`F32_ATOL`."""
    err = f32_mismatch(got, ref)
    assert err <= 1.0, (
        f"{what}: max |diff| {err * F32_ATOL:.3g} is {err:.3g}x the float32 "
        f"conformance tolerance {F32_ATOL:g}"
    )


def assert_mass_match(got: float, ref: float, *, what: str) -> None:
    """Assert two total masses agree to :data:`MASS_RTOL` relative."""
    rel = abs(got - ref) / abs(ref)
    assert rel <= MASS_RTOL, (
        f"{what}: total mass {got!r} vs {ref!r}, relative {rel:.3g} over "
        f"{MASS_RTOL:.3g}"
    )


def macroscopic64(pdf, lattice):
    """(rho, u) of a (Q, X, Y, Z) float32 pdf array, summed in float64 so the
    diagnostic adds no round-off to the state it reads."""
    from repro.lbm.criteria import macroscopic

    return macroscopic(np.asarray(pdf, np.float64), lattice)


def four_cpu_devices_env() -> dict:
    """Environment for a child process whose JAX sees four CPU devices (the
    count is fixed before JAX starts, so a test that needs four devices runs
    its body in such a child)."""
    return dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS=(os.environ.get("XLA_FLAGS", "")
                   + " --xla_force_host_platform_device_count=4").strip(),
        PYTHONPATH=os.pathsep.join(
            [str(Path(__file__).resolve().parents[1] / "src"), os.environ.get("PYTHONPATH", "")]
        ),
    )


def slab_cells(slabs) -> set[tuple[int, int]]:
    """(slot, flat ghosted cell) of every ghost cell a slab part fills."""
    dims = tuple(n + 2 * slabs.ghost for n in slabs.cells)
    out = set()
    for k, d in enumerate(SLAB_DIRECTIONS):
        tgt, _ = slab_slices(d, slabs.cells, slabs.ghost)
        cells = np.ravel_multi_index(
            np.meshgrid(*(np.arange(t.start, t.stop) for t in tgt), indexing="ij"),
            dims,
        ).ravel()
        for b in np.nonzero(slabs.src_slot[k] >= 0)[0]:
            out.update((int(b), int(c)) for c in cells)
    return out
