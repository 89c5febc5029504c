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
