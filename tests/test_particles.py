"""Conformance suite for the Lagrangian particle subsystem.

Pins the invariants that make :mod:`repro.particles` a faithful meshless
layer on the block forest:

* **storage** — refinement routes every particle to the child octant owning
  its position, coarsening concatenates the octet; particle count and id set
  are conserved through any AMR cycle;
* **distributed conformance** — sharded advection at 1/4/13 simulated ranks
  reproduces the single-rank restack reference ids exactly and positions to
  the float32 conformance tolerance (``conftest.F32_ATOL``: the fixed-order
  interpolation is batch-independent, but the velocity field it reads is
  stepped by differently shaped programs) across an AMR event *and* a forced
  load-balancing cycle, with the population exactly conserved;
* **persistence** — checkpoint/restart (including onto a different rank
  count) and buddy resilience round-trip particle state bitwise, via the
  registry codec the §2.5 callbacks derive;
* **accounting** — cross-rank particle traffic is pure batched p2p with
  exact (ragged-honest) byte counts.
"""

import numpy as np
import pytest

from conftest import assert_f32_match
from repro.core import BlockDataRegistry, Comm, ForestGeometry, make_uniform_forest
from repro.lbm import AMRLBM, LidDrivenCavityConfig
from repro.particles import (
    ParticlesConfig,
    all_particles,
    apply_domain_boundary,
    block_box,
    empty_particles,
    find_leaf,
    num_particles,
    particles_nbytes,
    register_particles,
    seed_particles,
    total_particles,
)
from repro.core.migration import payload_nbytes

COARSE_STEPS = 8
AMR_INTERVAL = 4

# tracers clustered under the lid, where the flow is fastest — exercises both
# the heterogeneous load model and genuine cross-block redistribution
BASE = dict(
    root_grid=(2, 2, 2),
    cells_per_block=(8, 8, 8),
    omega=1.5,
    u_lid=(0.08, 0.0, 0.0),
    max_level=1,
    refine_upper=0.03,
    refine_lower=0.004,
    kernel_backend="ref",
    particles=ParticlesConfig(
        per_block=24,
        seed=1,
        alpha=0.05,
        region=((0.0, 0.0, 1.7), (2.0, 2.0, 2.0)),
    ),
)


def _run(mode: str, nranks: int) -> AMRLBM:
    """AMR events at steps 4/8, then a forced load-balancing cycle and one
    more coarse step — the acceptance scenario."""
    sim = AMRLBM(LidDrivenCavityConfig(nranks=nranks, stepping_mode=mode, **BASE))
    n0 = sim.total_particles()
    assert n0 > 0
    sim.run(COARSE_STEPS, amr_interval=AMR_INTERVAL)
    sim.adapt(force_rebalance=True)
    sim.advance(1)
    assert sim.total_particles() == n0, "particle count must be exactly conserved"
    return sim


@pytest.fixture(scope="module")
def reference() -> AMRLBM:
    return _run("restack", 1)


# -- distributed conformance -------------------------------------------------------


@pytest.mark.parametrize(
    "nranks", [1, 4, pytest.param(13, marks=pytest.mark.slow)]
)
def test_sharded_particles_match_single_rank_reference(reference, nranks):
    sim = _run("sharded", nranks)
    assert sim.amr_cycles >= 1, "the run must span at least one AMR event"
    ref = all_particles(reference.forest)
    got = all_particles(sim.forest)
    np.testing.assert_array_equal(got["id"], ref["id"])  # ids: exact by construction
    assert_f32_match(got["pos"], ref["pos"], what="tracer positions")
    assert_f32_match(got["vel"], ref["vel"], what="tracer velocities")


@pytest.mark.parametrize(
    "mode, nranks",
    [("arena", 1), ("fused", 1), ("fused_sharded", 1), ("fused_sharded", 4)],
)
def test_host_and_device_modes_match_reference(reference, mode, nranks):
    sim = _run(mode, nranks)
    ref = all_particles(reference.forest)
    got = all_particles(sim.forest)
    np.testing.assert_array_equal(got["id"], ref["id"])  # ids: exact by construction
    assert_f32_match(got["pos"], ref["pos"], what=f"{mode}@{nranks} tracer positions")


def test_redistribution_is_exercised_and_batched_p2p(reference):
    """The reference run actually moves tracers across blocks; at 13 ranks
    some of those moves cross rank boundaries as batched p2p messages with
    collective-free accounting."""
    assert reference.particles_moved > 0
    sim = _run("sharded", 13)
    assert sim.particles_moved == reference.particles_moved
    st = sim.data_stats["particles"]
    assert st.p2p_bytes > 0 and st.p2p_messages > 0
    assert st.collective_bytes_per_rank == 0
    # every particle sits inside its block after redistribution
    for b in sim.forest.all_blocks():
        lo, hi = block_box(sim.geom, b.bid)
        p = b.data["particles"]
        assert np.all((p["pos"] >= lo) & (p["pos"] < hi)), hex(b.bid)


# -- storage: split/merge routing ---------------------------------------------------


def _make_particle_forest(geom, nranks, per_block=6, seed=3):
    forest = make_uniform_forest(geom, nranks, level=1)
    reg = BlockDataRegistry()
    register_particles(reg, geom)
    seed_particles(forest, geom, per_block=per_block, seed=seed)
    return forest, reg


def test_refine_routes_particles_to_owning_child_octant(geom3d):
    from repro.core import AMRPipeline, SFCBalancer

    forest, reg = _make_particle_forest(geom3d, 2)
    before = all_particles(forest)
    pipe = AMRPipeline(balancer=SFCBalancer(order="morton"), registry=reg)
    comm = Comm(2)
    forest, _ = pipe.run_cycle(
        forest, comm, lambda r, blocks: {bid: b.level + 1 for bid, b in blocks.items()}
    )
    forest.check_all()
    after = all_particles(forest)
    # exact by construction: refinement moves particles, it computes nothing
    np.testing.assert_array_equal(before["id"], after["id"])
    np.testing.assert_array_equal(before["pos"], after["pos"])
    # routing is exact: every particle's position is inside its (finer) block
    for b in forest.all_blocks():
        lo, hi = block_box(geom3d, b.bid)
        p = b.data["particles"]
        assert np.all((p["pos"] >= lo) & (p["pos"] < hi)), hex(b.bid)


def test_coarsen_concatenates_octet_sorted_by_id(geom3d):
    from repro.core import AMRPipeline, SFCBalancer

    forest, reg = _make_particle_forest(geom3d, 3)
    before = all_particles(forest)
    pipe = AMRPipeline(balancer=SFCBalancer(order="morton"), registry=reg)
    comm = Comm(3)
    forest, _ = pipe.run_cycle(
        forest, comm, lambda r, blocks: {bid: b.level - 1 for bid, b in blocks.items()}
    )
    forest.check_all()
    assert forest.levels_in_use() == [0]
    after = all_particles(forest)
    # exact by construction: coarsening concatenates, it computes nothing
    np.testing.assert_array_equal(before["id"], after["id"])
    np.testing.assert_array_equal(before["pos"], after["pos"])
    for b in forest.all_blocks():
        p = b.data["particles"]
        assert np.all(np.diff(p["id"]) > 0), "per-block sets must be id-sorted"


def test_seeding_is_rank_count_independent(geom3d):
    a = _make_particle_forest(geom3d, 1)[0]
    b = _make_particle_forest(geom3d, 7)[0]
    pa, pb = all_particles(a), all_particles(b)
    # exact by construction: one seeded generator per block, whoever owns it
    np.testing.assert_array_equal(pa["id"], pb["id"])
    np.testing.assert_array_equal(pa["pos"], pb["pos"])


# -- domain boundaries --------------------------------------------------------------


def test_reflecting_boundary_mirrors_and_flips_velocity():
    hi = np.array([2.0, 2.0, 2.0])
    pos = np.array([[-0.1, 1.0, 2.3], [0.5, 0.5, 0.5]])
    vel = np.array([[-1.0, 0.0, 2.0], [1.0, 1.0, 1.0]])
    p, v = apply_domain_boundary(pos, vel, hi, "reflect")
    np.testing.assert_allclose(p[0], [0.1, 1.0, 1.7])
    np.testing.assert_allclose(v[0], [1.0, 0.0, -2.0])
    np.testing.assert_allclose(p[1], pos[1])
    assert np.all(p >= 0.0) and np.all(p < hi)


def test_periodic_boundary_wraps_and_routes_across_the_domain(geom3d):
    forest, _reg = _make_particle_forest(geom3d, 4, per_block=2)
    # push one block's particles just past the domain's upper x face
    blk = max(forest.all_blocks(), key=lambda b: block_box(geom3d, b.bid)[1][0])
    p = blk.data["particles"]
    p["pos"][:, 0] = 2.0 + 1e-3  # outside; wraps to ~0.001
    from repro.particles import redistribute_particles

    comm = Comm(4)
    n0 = total_particles(forest)
    moved, _ = redistribute_particles(forest, geom3d, comm, boundary="periodic")
    assert moved >= 1
    assert total_particles(forest) == n0
    for b in forest.all_blocks():
        lo, hi = block_box(geom3d, b.bid)
        q = b.data["particles"]
        assert np.all((q["pos"] >= lo) & (q["pos"] < hi))


def test_find_leaf_is_the_containment_oracle(geom3d):
    forest = make_uniform_forest(geom3d, 2, level=1)
    leaves = {b.bid: b.owner for b in forest.all_blocks()}
    rng = np.random.default_rng(0)
    for pos in rng.random((32, 3)) * np.array(geom3d.root_grid):
        bid = find_leaf(geom3d, leaves, pos)
        lo, hi = block_box(geom3d, bid)
        assert np.all((pos >= lo) & (pos < hi))
    assert find_leaf(geom3d, leaves, (-0.1, 0.5, 0.5)) is None


# -- persistence --------------------------------------------------------------------


def test_checkpoint_roundtrips_particle_state_bitwise(tmp_path):
    from repro.core.checkpoint import load_checkpoint, save_checkpoint

    sim = AMRLBM(LidDrivenCavityConfig(nranks=4, stepping_mode="arena", **BASE))
    sim.run(4, amr_interval=2)
    sim.materialize_host()
    save_checkpoint(sim.forest, sim.registry, tmp_path / "ckpt")
    for nranks in (None, 3):  # same and different rank counts
        restored = load_checkpoint(tmp_path / "ckpt", sim.registry, nranks=nranks)
        ref = {b.bid: b.data["particles"] for b in sim.forest.all_blocks()}
        got = {b.bid: b.data["particles"] for b in restored.all_blocks()}
        assert set(ref) == set(got)
        for bid in ref:
            for k in ("pos", "vel", "id"):  # serialization: exact by construction
                np.testing.assert_array_equal(got[bid][k], ref[bid][k]), (bid, k)


def test_resilience_snapshot_restores_particles():
    from repro.core.resilience import ResilienceManager

    sim = AMRLBM(LidDrivenCavityConfig(nranks=4, stepping_mode="arena", **BASE))
    sim.advance(2)
    before = all_particles(sim.forest)
    mgr = ResilienceManager(sim.registry)
    mgr.snapshot(sim.forest, sim.comm)
    restored, _comm = mgr.fail_and_restore(sim.forest, {1}, sim.pipeline)
    after = all_particles(restored)
    # snapshot and restore copy bytes: exact by construction
    np.testing.assert_array_equal(before["id"], after["id"])
    np.testing.assert_array_equal(before["pos"], after["pos"])


# -- accounting ---------------------------------------------------------------------


def test_particle_payload_bytes_are_exact():
    """Ragged SoA payloads size to the exact sum of their array bytes plus
    wire keys — the Table-1 honesty requirement for particle migration."""
    p = {
        "pos": np.zeros((7, 3), np.float64),
        "vel": np.zeros((7, 3), np.float64),
        "id": np.zeros(7, np.int64),
    }
    keys = sum(len(k) for k in p)
    assert particles_nbytes(p) == 7 * (24 + 24 + 8)
    assert payload_nbytes(p) == particles_nbytes(p) + keys
    assert payload_nbytes(empty_particles()) == keys


def test_weight_hook_tracks_particle_counts():
    sim = AMRLBM(LidDrivenCavityConfig(nranks=4, stepping_mode="sharded", **BASE))
    ncells = 8 * 8 * 8
    alpha = BASE["particles"].alpha
    for b in sim.forest.all_blocks():
        assert b.weight == ncells + alpha * num_particles(b.data["particles"])
    sim.advance(2)
    sim.adapt(force_rebalance=True)
    # weights re-derived from actual post-cycle data, never the 1.0 default
    for b in sim.forest.all_blocks():
        assert b.weight == ncells + alpha * num_particles(b.data["particles"])
