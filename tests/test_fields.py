"""Typed field API (FieldSpec/FieldRegistry) and LevelArena data plane."""

import math

import numpy as np
import pytest

from repro.core import (
    AMRPipeline,
    Comm,
    DiffusionBalancer,
    FieldRegistry,
    FieldSpec,
    ForestGeometry,
    LevelArena,
    SFCBalancer,
    make_uniform_forest,
)
from repro.core.checkpoint import load_checkpoint, save_checkpoint
from repro.core.resilience import ResilienceManager
from repro.lbm import AMRLBM, LidDrivenCavityConfig
from repro.lbm.grid import LBMBlockSpec, make_lbm_fields


CELLS = (4, 4, 4)


def _density_registry() -> FieldRegistry:
    return FieldRegistry(
        cells=CELLS,
        fields=(FieldSpec("rho", dtype=np.float64, refine="interpolate", coarsen="restrict"),),
    )


def _total_mass(forest, reg: FieldRegistry) -> float:
    """Cell-volume-weighted integral: level-l cells have volume 8^-l."""
    return sum(
        float(reg.interior("rho", b.data["rho"]).sum()) * (8.0 ** -b.level)
        for b in forest.all_blocks()
    )


def test_field_registry_derives_seed_equivalent_callbacks():
    """Split->merge through the derived callbacks is the identity on cell
    averages (the seed's volumetric-copy invariant)."""
    spec = LBMBlockSpec(cells=(8, 8, 8))
    reg = make_lbm_fields(spec)
    item = reg.items["pdf"]
    rng = np.random.default_rng(0)
    pdf = rng.standard_normal(spec.pdf_shape).astype(np.float32)
    parts = {o: item.serialize_split(pdf, None, o) for o in range(8)}
    children = {o: item.deserialize_split(p, None) for o, p in parts.items()}
    coarse = {o: item.serialize_merge(children[o], None) for o in range(8)}
    merged = item.deserialize_merge(coarse, None)
    g = spec.ghost
    np.testing.assert_allclose(
        merged[:, g:-g, g:-g, g:-g], pdf[:, g:-g, g:-g, g:-g], rtol=1e-6
    )
    # mask: inject/max must round-trip categorical data exactly
    mi = reg.items["mask"]
    mask = rng.integers(0, 3, spec.mask_shape).astype(np.int32)
    child = mi.deserialize_split(mi.serialize_split(mask, None, 3), None)
    assert child.dtype == np.int32
    back = mi.deserialize_merge(
        {o: mi.serialize_merge(mi.deserialize_split(mi.serialize_split(mask, None, o), None), None)
         for o in range(8)},
        None,
    )
    np.testing.assert_array_equal(back[1:-1, 1:-1, 1:-1], mask[1:-1, 1:-1, 1:-1])


@pytest.mark.parametrize(
    "balancer",
    [SFCBalancer(order="hilbert"), DiffusionBalancer(mode="pushpull", flow_iterations=5)],
)
def test_migrate_data_conserves_mass_for_interpolate_restrict_pair(balancer):
    """Split->merge roundtrip through migrate_data conserves total mass."""
    geom = ForestGeometry(root_grid=(2, 2, 2), max_level=8)
    reg = _density_registry()
    nranks = 4
    forest = make_uniform_forest(geom, nranks, level=1)
    rng = np.random.default_rng(7)
    for b in forest.all_blocks():
        arr = reg.alloc("rho")
        arr[...] = rng.random(arr.shape)
        b.data["rho"] = arr
    mass0 = _total_mass(forest, reg)
    comm = Comm(nranks)
    pipe = AMRPipeline(balancer=balancer, registry=reg)
    # refine everything (split), then coarsen everything (merge): the full
    # interpolate -> restrict roundtrip across the migration machinery
    forest, _ = pipe.run_cycle(
        forest, comm, lambda r, blocks: {bid: blk.level + 1 for bid, blk in blocks.items()}
    )
    forest.check_all()
    assert abs(_total_mass(forest, reg) - mass0) < 1e-9 * abs(mass0)
    forest, _ = pipe.run_cycle(
        forest, comm, lambda r, blocks: {bid: blk.level - 1 for bid, blk in blocks.items()}
    )
    forest.check_all()
    assert abs(_total_mass(forest, reg) - mass0) < 1e-9 * abs(mass0)


def test_arena_views_and_slots_follow_topology():
    geom = ForestGeometry(root_grid=(2, 2, 1), max_level=8)
    reg = _density_registry()
    forest = make_uniform_forest(geom, 3, level=1)
    for b in forest.all_blocks():
        b.data["rho"] = np.full(reg.block_shape("rho"), float(b.bid % 97))
    arena = LevelArena(reg)
    arena.adopt(forest)
    arena.check_consistent(forest)
    # views alias the SoA buffer: writing through a block mutates the buffer
    blk = next(forest.all_blocks())
    slot = arena.slot_of(blk.level, blk.bid)
    blk.data["rho"][...] = -5.0
    assert float(arena.buffer(blk.level, "rho")[slot].max()) == -5.0
    # per-block values survived the packing
    for b in forest.all_blocks():
        if b is not blk:
            assert float(b.data["rho"][0, 0, 0]) == float(b.bid % 97)
    # re-adopt with unchanged topology reuses the same buffers
    buf_before = arena.buffer(blk.level, "rho")
    arena.adopt(forest)
    assert arena.buffer(blk.level, "rho") is buf_before
    arena.check_consistent(forest)


def test_arena_slots_consistent_after_amr_cycle():
    """check_all + per-field slot audit after a full AMR/LBM cycle."""
    cfg = LidDrivenCavityConfig(
        root_grid=(2, 2, 2),
        cells_per_block=(8, 8, 8),
        nranks=4,
        omega=1.5,
        u_lid=(0.08, 0.0, 0.0),
        max_level=1,
        refine_upper=0.03,
        refine_lower=0.004,
    )
    sim = AMRLBM(cfg)
    sim.arena.check_consistent(sim.forest)
    sim.advance(2)
    report = sim.adapt()
    assert report.executed
    sim.forest.check_all()
    sim.arena.check_consistent(sim.forest)
    assert set(sim.arena.levels()) == set(sim.forest.levels_in_use())


def test_arena_stepping_matches_restack_baseline():
    """Both stepping modes must produce identical physics."""
    sims = {}
    for mode in ("arena", "restack"):
        cfg = LidDrivenCavityConfig(
            root_grid=(2, 1, 1),
            cells_per_block=(8, 8, 8),
            nranks=2,
            omega=1.5,
            u_lid=(0.06, 0.0, 0.0),
            max_level=1,
            stepping_mode=mode,
            kernel_backend="ref",
        )
        sim = AMRLBM(cfg)
        sim.advance(2)
        sim.adapt()
        sim.advance(1)
        sims[mode] = {b.bid: np.array(b.data["pdf"]) for b in sim.forest.all_blocks()}
    assert sims["arena"].keys() == sims["restack"].keys()
    for bid, pdf in sims["arena"].items():
        np.testing.assert_allclose(pdf, sims["restack"][bid], rtol=1e-6, atol=1e-7)


def test_checkpoint_and_resilience_through_field_registry(tmp_path):
    """Typed registry drives checkpoint encode/decode and buddy restore."""
    geom = ForestGeometry(root_grid=(2, 2, 2), max_level=8)
    reg = _density_registry()
    forest = make_uniform_forest(geom, 8, level=1)
    for b in forest.all_blocks():
        arr = reg.alloc("rho")
        arr[...] = float(b.bid % 1000)
        b.data["rho"] = arr
    # disk checkpoint onto a different rank count
    save_checkpoint(forest, reg, tmp_path)
    restored = load_checkpoint(tmp_path, reg, nranks=3)
    restored.check_all()
    for b in restored.all_blocks():
        assert b.data["rho"].dtype == np.float64
        assert float(b.data["rho"][1, 1, 1]) == float(b.bid % 1000)
    # buddy resilience restore
    pipe = AMRPipeline(
        balancer=DiffusionBalancer(mode="pushpull", flow_iterations=5, max_main_iterations=20),
        registry=reg,
    )
    mgr = ResilienceManager(reg)
    mgr.snapshot(forest, Comm(8))
    restored2, _comm = mgr.fail_and_restore(forest, failed={1, 6}, pipeline=pipe)
    restored2.check_all()
    assert restored2.num_blocks() == forest.num_blocks()
    for b in restored2.all_blocks():
        assert float(b.data["rho"][1, 1, 1]) == float(b.bid % 1000)


def test_buddy_snapshot_survives_in_place_arena_stepping():
    """Snapshots must not alias arena buffers: in-place stepping after a
    snapshot must not change what fail_and_restore brings back."""
    cfg = LidDrivenCavityConfig(
        root_grid=(2, 1, 1),
        cells_per_block=(8, 8, 8),
        nranks=2,
        omega=1.5,
        u_lid=(0.06, 0.0, 0.0),
        max_level=1,
        kernel_backend="ref",
    )
    sim = AMRLBM(cfg)
    sim.advance(1)
    mgr = ResilienceManager(sim.registry)
    mgr.snapshot(sim.forest, sim.comm)
    at_snapshot = {b.bid: np.array(b.data["pdf"]) for b in sim.forest.all_blocks()}
    sim.advance(2)  # mutates the arena buffers in place
    drifted = {b.bid: np.array(b.data["pdf"]) for b in sim.forest.all_blocks()}
    assert any(not np.array_equal(at_snapshot[bid], drifted[bid]) for bid in at_snapshot)
    restored, _comm = mgr.fail_and_restore(sim.forest, failed={1}, pipeline=sim.pipeline)
    got = {b.bid: b.data["pdf"] for b in restored.all_blocks()}
    assert got.keys() == at_snapshot.keys()
    for bid, pdf in got.items():
        np.testing.assert_array_equal(pdf, at_snapshot[bid])  # restore copies bytes
        # restored state owns its memory: stepping it must not touch the
        # snapshot (so a second restore from the same snapshot stays valid)
        for snap in mgr.snapshots:
            for _meta, payload in list(snap.own.values()) + list(snap.buddy.values()):
                assert not np.shares_memory(pdf, payload["pdf"])


def test_copy_policy_passes_payload_opaque():
    reg = FieldRegistry(
        cells=CELLS,
        fields=(FieldSpec("meta", dtype=np.float32, shape=(2,), refine="copy", coarsen="copy"),),
    )
    item = reg.items["meta"]
    d = np.arange(2 * 6 * 6 * 6, dtype=np.float32).reshape(reg.block_shape("meta"))
    child = item.deserialize_split(item.serialize_split(d, None, 5), None)
    np.testing.assert_array_equal(child, d)  # "copy" policy: exact by construction
    assert child is not d  # children must not alias the parent
    merged = item.deserialize_merge({o: item.serialize_merge(d, None) for o in range(8)}, None)
    np.testing.assert_array_equal(merged, d)


def test_ghost_zero_field_splits_and_merges():
    """A field without halo (ghost=0) must go through the derived callbacks."""
    reg = FieldRegistry(
        cells=CELLS,
        fields=(FieldSpec("t", dtype=np.float64, ghost=0, refine="interpolate", coarsen="restrict"),),
    )
    item = reg.items["t"]
    rng = np.random.default_rng(2)
    d = rng.random(reg.block_shape("t"))
    assert d.shape == CELLS  # no ghost padding
    np.testing.assert_array_equal(reg.interior("t", d), d)  # a slice: exact
    children = {
        o: item.deserialize_split(item.serialize_split(d, None, o), None) for o in range(8)
    }
    merged = item.deserialize_merge(
        {o: item.serialize_merge(c, None) for o, c in children.items()}, None
    )
    np.testing.assert_allclose(merged, d, rtol=1e-12)


def test_field_registry_rejects_duplicate_and_validates_decode():
    reg = _density_registry()
    with pytest.raises(AssertionError):
        reg.add(FieldSpec("rho"))
    bad = {"rho": np.zeros((2, 2, 2))}
    with pytest.raises(ValueError, match="payload shape"):
        reg.decode_block(bad, None)
