"""Host pdf writes reach every device-resident engine through
``AMRLBM.pdfs_written()``.

Each case steps a cavity (two levels after one criterion event; one level
for ``fused_sharded``, whose per-rank two-level programs take most of a
minute to compile on the CPU) until the engine's device copy of the pdfs is
current, writes a seeded perturbation of the state into the host
``Block.data["pdf"]`` views (after ``materialize_host()``), announces the
write or not, steps two coarse steps, and compares every interior pdf with
the ``restack`` engine stepped from the same written state:

* announced, the engine steps the written state: the gap is float32
  round-off. Two coarse steps round each value of order 0.01–0.4 a few
  times (three substeps at level 1), so 1e-6 absolute (about 8 ulp at 1.0);
* not announced, the device copy wins and the run misses the perturbation
  (s.d. 1e-3 per value): the gap must exceed 1e-4, so the comparison can
  see a lost write.

``device_sharded`` at four ranks needs four XLA devices, provisioned before
JAX starts: that case runs this file as a script in a child process with
``XLA_FLAGS=--xla_force_host_platform_device_count=4``.
"""

from __future__ import annotations

import json
import subprocess
import sys

import numpy as np
import pytest

from conftest import four_cpu_devices_env
from repro.lbm import AMRLBM, LidDrivenCavityConfig

BASE = dict(
    root_grid=(2, 2, 2),
    cells_per_block=(8, 8, 8),
    omega=1.5,
    u_lid=(0.08, 0.0, 0.0),
    max_level=1,
    refine_upper=0.03,
    refine_lower=0.004,
    kernel_backend="ref",  # the hook is storage; interpret-mode Pallas only slows it
)
ROUND_OFF = 1e-6
PERTURBATION = 1e-3
SEED = 2**31 + 5


def _sim(mode: str, nranks: int, levels: int) -> AMRLBM:
    sim = AMRLBM(LidDrivenCavityConfig(nranks=nranks, stepping_mode=mode, **BASE))
    sim.advance(2)
    if levels > 1:
        sim.adapt()
    assert len(sim.forest.levels_in_use()) == levels
    return sim


def _interiors(sim: AMRLBM) -> dict[int, np.ndarray]:
    sim.materialize_host()
    return {b.bid: np.array(sim.spec.interior(b.data["pdf"])) for b in sim.forest.all_blocks()}


def _write(sim: AMRLBM, state: dict[int, np.ndarray]) -> None:
    sim.materialize_host()
    for b in sim.forest.all_blocks():
        sim.spec.interior(b.data["pdf"])[...] = state[b.bid]


def write_gap(mode: str, nranks: int, levels: int, announce: bool) -> float:
    """Largest interior pdf gap to ``restack`` after two coarse steps from a
    host-written state, announced through ``pdfs_written()`` or not."""
    sim = _sim(mode, nranks, levels)
    sim.advance(1)  # the device copy of the pdfs is now current
    ref = _sim("restack", 1, levels)
    assert sorted(b.bid for b in ref.forest.all_blocks()) == sorted(
        b.bid for b in sim.forest.all_blocks()
    )
    rng = np.random.default_rng(SEED)
    state = {
        bid: (f + rng.standard_normal(f.shape) * PERTURBATION).astype(f.dtype)
        for bid, f in sorted(_interiors(sim).items())
    }
    _write(sim, state)
    _write(ref, state)
    if announce:
        sim.pdfs_written()
    sim.advance(2)
    ref.advance(2)
    got, want = _interiors(sim), _interiors(ref)
    return max(float(np.abs(got[bid] - want[bid]).max()) for bid in want)


@pytest.mark.parametrize("announce", [True, False], ids=["announced", "silent"])
@pytest.mark.parametrize(
    "mode, nranks, levels",
    [("fused", 1, 2), ("device_sharded", 1, 2), ("fused_sharded", 2, 1)],
)
def test_host_write_reaches_the_device_copy_when_announced(mode, nranks, levels, announce):
    gap = write_gap(mode, nranks, levels, announce)
    if announce:
        assert gap < ROUND_OFF, gap
    else:
        assert gap > 0.1 * PERTURBATION, gap


@pytest.fixture(scope="module")
def four_device_gaps() -> dict:
    proc = subprocess.run([sys.executable, __file__], env=four_cpu_devices_env(),
                          capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("announce", [True, False], ids=["announced", "silent"])
def test_device_sharded_on_four_devices_uploads_announced_writes(four_device_gaps, announce):
    gap = four_device_gaps["announced" if announce else "silent"]
    if announce:
        assert gap < ROUND_OFF, gap
    else:
        assert gap > 0.1 * PERTURBATION, gap


if __name__ == "__main__":
    import jax

    assert jax.device_count() == 4, jax.devices()
    print(json.dumps({
        "announced": write_gap("device_sharded", 4, 2, True),
        "silent": write_gap("device_sharded", 4, 2, False),
    }))
