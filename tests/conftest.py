import os
import random
from pathlib import Path

import pytest

from repro.core import Comm, ForestGeometry, make_uniform_forest


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
