"""Regression: data-plane stage attribution is consistent across all modes.

``AMRLBM.data_stats`` attributes data-plane cost to four stages — ``halo``
(ghost exchange), ``step`` (kernel calls), ``fused`` (device-resident
coarse-step programs, where halo and step are indistinguishable), and
``particles`` (tracer advection + redistribution). This suite pins the
attribution *contract* for the same 8-coarse-step run (particles enabled,
4 simulated ranks, spanning AMR events) in every stepping mode:

* each mode fills exactly its designated stages and leaves the others empty
  (a host mode must never report "fused" work, a device mode must never
  report per-substep "step" work);
* physics-side counters (tracer advection/move counts, particle
  redistribution bytes) are identical across modes, and mass agrees to the
  float32 conformance tolerance — attribution must not change what is
  measured, only where it is filed;
* the p2p bytes the two sharded modes put on the fabric agree exactly:
  host-sharded files everything under "halo", fused_sharded splits the same
  traffic between "fused" (in-program device messages) and "halo" (host-side
  refreshes around AMR events and particle advection).
"""

import pytest

from conftest import assert_mass_match
from repro.lbm import AMRLBM, LidDrivenCavityConfig
from repro.particles import ParticlesConfig

MODES = ("restack", "arena", "fused", "sharded", "fused_sharded")
HOST_MODES = ("restack", "arena", "sharded")
DEVICE_MODES = ("fused", "fused_sharded")
COARSE_STEPS = 8

BASE = dict(
    root_grid=(2, 2, 2),
    cells_per_block=(8, 8, 8),
    nranks=4,
    omega=1.5,
    u_lid=(0.08, 0.0, 0.0),
    max_level=1,
    refine_upper=0.03,
    refine_lower=0.004,
    kernel_backend="ref",
    particles=ParticlesConfig(
        per_block=8,
        seed=1,
        alpha=0.05,
        region=((0.0, 0.0, 1.5), (2.0, 2.0, 2.0)),
    ),
)


@pytest.fixture(scope="module")
def runs() -> dict[str, AMRLBM]:
    out = {}
    for mode in MODES:
        sim = AMRLBM(LidDrivenCavityConfig(stepping_mode=mode, **BASE))
        sim.run(COARSE_STEPS, amr_interval=4)
        out[mode] = sim
    return out


def test_all_modes_ran_the_same_simulation(runs):
    ref = runs["restack"]
    assert ref.amr_cycles >= 1 and len(ref.forest.levels_in_use()) > 1
    for mode, sim in runs.items():
        assert sim.coarse_step == COARSE_STEPS, mode
        assert sim.amr_cycles == ref.amr_cycles, mode
        assert_mass_match(sim.total_mass(), ref.total_mass(), what=mode)
        # attribution must not perturb the physics-side counters
        assert sim.particles_advected == ref.particles_advected, mode
        assert sim.particles_moved == ref.particles_moved, mode
        assert sim.total_particles() == ref.total_particles(), mode


def test_host_modes_fill_halo_and_step_and_never_fused(runs):
    for mode in HOST_MODES:
        st = runs[mode].data_stats
        assert st["halo"].seconds > 0.0, mode
        assert st["step"].seconds > 0.0, mode
        fused = st["fused"]
        assert fused.seconds == 0.0 and fused.p2p_bytes == 0, mode
        assert fused.p2p_messages == 0 and fused.exchange_rounds == 0, mode


def test_device_modes_fill_fused_and_never_step(runs):
    for mode in DEVICE_MODES:
        st = runs[mode].data_stats
        assert st["fused"].seconds > 0.0, mode
        assert st["fused"].exchange_rounds > 0, mode
        step = st["step"]
        assert step.seconds == 0.0 and step.p2p_bytes == 0, mode
        # halo still carries the host-side refreshes around AMR events and
        # the pre-advection ghost refresh — but no per-substep exchange
        assert st["halo"].seconds > 0.0, mode


def test_particles_stage_is_mode_invariant(runs):
    ref = runs["restack"].data_stats["particles"]
    assert ref.seconds > 0.0
    for mode, sim in runs.items():
        st = sim.data_stats["particles"]
        assert st.seconds > 0.0, mode
        # identical redistribution traffic in every mode (same physics, same
        # rank count, same Comm fabric)
        assert st.p2p_bytes == ref.p2p_bytes, mode
        assert st.p2p_messages == ref.p2p_messages, mode
        assert st.collective_bytes_per_rank == 0, mode


def test_only_comm_routed_stages_report_fabric_traffic(runs):
    # non-sharded data planes never touch the Comm fabric for halo traffic
    for mode in ("restack", "arena", "fused"):
        assert runs[mode].data_stats["halo"].p2p_bytes == 0, mode
    assert runs["sharded"].data_stats["halo"].p2p_bytes > 0
    assert runs["fused_sharded"].data_stats["fused"].p2p_bytes > 0


def test_sharded_modes_account_identical_halo_traffic(runs):
    """Host-sharded files all halo traffic under "halo"; fused_sharded files
    the in-program device messages under "fused" and only the host-side
    refreshes under "halo". The totals must agree byte for byte — the
    compiled message buffers are exactly the host patches."""
    sh = runs["sharded"].data_stats
    fs = runs["fused_sharded"].data_stats
    assert sh["halo"].p2p_bytes == fs["fused"].p2p_bytes + fs["halo"].p2p_bytes
    assert (
        sh["halo"].p2p_messages
        == fs["fused"].p2p_messages + fs["halo"].p2p_messages
    )
