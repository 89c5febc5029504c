"""Fused-superstep building blocks + stepping data-plane bugfix regressions.

* the compiled ghost plan (flat gather/scatter index arrays executed as jnp
  ops) reproduces the host exchange bit for bit, including the fine->coarse
  coalescence and coarse->fine explosion paths across a level transition;
* ghost-width-0 fields: interior diagnostics must not silently evaluate over
  empty ``arr[0:-0]`` slices;
* even-but-non-power-of-two cells per block are valid (the real halo
  alignment invariant), end to end through an AMR event;
* a caller-owned ``plan_cache`` can never replay a plan built for an older
  forest topology or storage binding;
* the slab-plus-residual halo fill (same-level ghost slabs as block-granular
  copies, cross-level cells written cell by cell) fills the stack bit for
  bit as the merged scatter does, and a built superstep records how many
  cells each part fills.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from conftest import slab_cells
from repro import telemetry
from repro.core import (
    AMRPipeline,
    Comm,
    ForestGeometry,
    LevelArena,
    SFCBalancer,
    make_uniform_forest,
)
from repro.kernels.lbm_collide import ops
from repro.kernels.lbm_collide.ops import apply_compiled_ghost_plan
from repro.lbm import AMRLBM, LidDrivenCavityConfig
from repro.lbm.grid import LBMBlockSpec, make_lbm_fields
from repro.lbm.halo import (
    build_ghost_plan,
    compile_ghost_plan,
    fill_ghost_layers,
    lower_halo_fill,
    lower_slab_placement,
    split_halo_fill,
)


def _seed_fields(forest, reg, rng=None):
    for b in forest.all_blocks():
        if rng is None:
            b.data["pdf"] = np.full(
                reg.block_shape("pdf"), float(b.bid % 97), np.float32
            )
        else:
            b.data["pdf"] = rng.standard_normal(reg.block_shape("pdf")).astype(
                np.float32
            )
        b.data["mask"] = np.zeros(reg.block_shape("mask"), np.int32)


def _two_level_arena(cells=(4, 4, 4), root_grid=(2, 1, 1), refine=(0,)):
    """A 2-level forest (the roots at positions ``refine`` of the sorted root
    ids refined) with arena-backed random pdfs."""
    spec = LBMBlockSpec(cells=cells)
    reg = make_lbm_fields(spec)
    geom = ForestGeometry(root_grid=root_grid, max_level=6)
    forest = make_uniform_forest(geom, 1, level=0)
    _seed_fields(forest, reg)  # migration serializes fields during the cycle
    pipe = AMRPipeline(balancer=SFCBalancer(), registry=reg)
    roots = sorted(b.bid for b in forest.all_blocks())
    forest, _ = pipe.run_cycle(
        forest, Comm(1), lambda r, blocks: {roots[i]: 1 for i in refine}
    )
    assert forest.levels_in_use() == [0, 1]
    _seed_fields(forest, reg, rng=np.random.default_rng(7))
    arena = LevelArena(reg)
    arena.adopt(forest)
    return forest, reg, arena


def test_compiled_plan_matches_host_exchange_bitwise():
    forest, reg, arena = _two_level_arena()
    plan = compile_ghost_plan(
        forest,
        reg,
        {l: arena.slots(l) for l in arena.levels()},
        fields=("pdf",),
    )
    # the forest has a level transition, so all three resampling kinds and
    # both level directions must be present in the lowered ops
    assert {op.kind for op in plan.ops} == {"same", "fine", "coarse"}
    assert plan.num_cells > 0
    bufs = {l: np.array(arena.buffer(l, "pdf")) for l in arena.levels()}
    out = apply_compiled_ghost_plan(plan, {l: b for l, b in bufs.items()})

    fill_ghost_layers(forest, reg, fields=("pdf",))  # host reference, in place
    # exact by construction: gathers, a fixed-order octet sum and a
    # power-of-two scale are the same correctly rounded operations on both sides
    for l in arena.levels():
        np.testing.assert_array_equal(
            np.asarray(out[l]), arena.buffer(l, "pdf"), err_msg=f"level {l}"
        )


def test_compiled_plan_handles_integer_fields():
    """Regression: the fine-coalescence path multiplied by ``dtype(0.125)``,
    which is 0 for integer dtypes — int ghost cells came back zeroed (and
    with FLUID == 0 that silently turns walls into fluid)."""
    forest, reg, arena = _two_level_arena()
    rng = np.random.default_rng(11)
    for b in forest.all_blocks():  # in place: blocks hold arena views
        b.data["mask"][...] = rng.integers(0, 3, b.data["mask"].shape)
    plan = compile_ghost_plan(
        forest, reg, {l: arena.slots(l) for l in arena.levels()}, fields=("mask",)
    )
    bufs = {l: np.array(arena.buffer(l, "mask")) for l in arena.levels()}
    out = apply_compiled_ghost_plan(plan, bufs)
    fill_ghost_layers(forest, reg, fields=("mask",))
    for l in arena.levels():
        assert np.asarray(out[l]).any(), "int ghost fill must not be all-zero"
        np.testing.assert_array_equal(
            np.asarray(out[l]), arena.buffer(l, "mask"), err_msg=f"level {l}"
        )


def test_compiled_plan_levels_filter_restricts_targets_not_sources():
    forest, reg, arena = _two_level_arena()
    plan = compile_ghost_plan(
        forest,
        reg,
        {l: arena.slots(l) for l in arena.levels()},
        fields=("pdf",),
        levels={1},
    )
    assert all(op.dst_level == 1 for op in plan.ops)
    assert {op.src_level for op in plan.ops} == {0, 1}


# -- slab-plus-residual halo fill -----------------------------------------------


@pytest.mark.parametrize(
    "pattern, level", [(0, 1), (1, 0), (1, 1)], ids=["p0-L1", "p1-L0", "p1-L1"]
)
def test_slab_fill_matches_merged_scatter_bitwise(pattern, level):
    """On a two-level forest with domain boundaries and L0<->L1 interfaces,
    in every activity pattern: the slab copies plus the residual's per-cell
    placement leave the stack bitwise equal to the merged per-cell scatter, ghost
    cells included; slab and residual cells partition the merged fill's
    cells; the residual holds only cross-level segments."""
    forest, reg, arena = _two_level_arena(root_grid=(3, 2, 2), refine=(0, 5))
    levels = arena.levels()
    slots = {l: arena.slots(l) for l in levels}
    active = {l for l in levels if l >= max(levels) - pattern}
    plan = compile_ghost_plan(forest, reg, slots, fields=("pdf",), levels=active)
    fill = lower_halo_fill(plan)[level]
    index = {l: i for i, l in enumerate(levels)}
    bufs = [jnp.asarray(arena.buffer(l, "pdf")) for l in levels]
    f = bufs[index[level]]

    vals = ops._concat_vals(bufs, ops._lower_fill_gathers(fill, index), level)
    merged = ops._halo_fill(
        f, vals, level, (jnp.asarray(fill.dst_slot), jnp.asarray(fill.dst_cell)),
        None, None,
    )

    slabs, residual = split_halo_fill(fill, LBMBlockSpec(cells=(4, 4, 4)), f.shape[0])
    assert slabs is not None and residual is not None
    rvals = ops._concat_vals(bufs, ops._lower_fill_gathers(residual, index), level)
    placement = lower_slab_placement(
        residual.dst_slot, residual.dst_cell, (4, 4, 4), 1, f.shape[0]
    )
    out = ops._halo_fill(f, rvals, level, None, slabs, placement)
    # exact by construction: both forms only move values, they compute nothing
    np.testing.assert_array_equal(np.asarray(out), np.asarray(merged))

    slab = slab_cells(slabs)
    res_cells = set(zip(residual.dst_slot.tolist(), residual.dst_cell.tolist()))
    all_cells = set(zip(fill.dst_slot.tolist(), fill.dst_cell.tolist()))
    assert len(slab) == slabs.num_cells
    assert len(res_cells) == residual.num_cells
    assert not slab & res_cells
    assert slab | res_cells == all_cells
    assert {seg.kind for seg in residual.segments} <= {"coarse", "fine"}
    assert all(seg.src_level != level for seg in residual.segments)


def test_fused_superstep_counts_slab_and_scatter_cells():
    """Building a fused superstep records ``halo.slab_cells`` and
    ``halo.scatter_cells`` per level; per level they add up to the cells of
    the merged fills the superstep's activity patterns build."""
    sim = AMRLBM(LidDrivenCavityConfig(
        root_grid=(2, 2, 2), cells_per_block=(4, 4, 4), nranks=1, omega=1.5,
        u_lid=(0.08, 0.0, 0.0), max_level=1, refine_upper=0.03,
        refine_lower=0.004, kernel_backend="ref", stepping_mode="fused",
    ))
    sim.advance(2)
    sim.adapt()
    levels = sim.forest.levels_in_use()
    assert levels == [0, 1]
    tr = telemetry.get_tracer()
    was = tr.enabled
    telemetry.configure(enabled=True)
    tr.reset()
    try:
        sim.advance(1)  # builds the superstep for the new topology
        slab = tr.metrics.counter("halo.slab_cells").series()
        scatter = tr.metrics.counter("halo.scatter_cells").series()
    finally:
        telemetry.configure(enabled=was)
        tr.reset()
    slots = {l: sim.arena.slots(l) for l in levels}
    want = dict.fromkeys(levels, 0)
    for p in range(len(levels)):
        plan = compile_ghost_plan(
            sim.forest, sim.fields, slots, fields=("pdf",),
            levels={l for l in levels if l >= max(levels) - p},
        )
        for l, fill in lower_halo_fill(plan).items():
            want[l] += fill.num_cells
    for l in levels:
        key = f"level={l}"
        assert slab[key] > 0
        assert slab[key] + scatter[key] == want[l]


# -- satellite: ghost-width-0 slicing ------------------------------------------


def test_zero_ghost_diagnostics_see_full_interior():
    """Regression: ``arr[g:-g]`` with ``g == 0`` is ``arr[0:0]`` — diagnostics
    silently summed empty arrays for zero-ghost fields."""
    cfg = LidDrivenCavityConfig(
        root_grid=(1, 1, 1),
        cells_per_block=(4, 4, 4),
        ghost=0,
        nranks=1,
        max_level=0,
        kernel_backend="ref",
        stepping_mode="restack",
    )
    sim = AMRLBM(cfg)
    ncells = 4**3 * sim.forest.num_blocks()
    assert sim.num_fluid_cells() == ncells
    # equilibrium at rho=1: total mass == fluid cell count (level 0 volume)
    assert abs(sim.total_mass() - ncells) < 1e-3
    assert sim.max_velocity() == 0.0


def test_zero_ghost_spec_interior_is_identity():
    spec = LBMBlockSpec(cells=(4, 4, 4), ghost=0)
    a = np.arange(4**3, dtype=np.float32).reshape(4, 4, 4)
    assert spec.interior(a).shape == (4, 4, 4)
    g1 = LBMBlockSpec(cells=(4, 4, 4), ghost=1)
    assert g1.interior(np.zeros((6, 6, 6))).shape == (4, 4, 4)


# -- satellite: even-but-non-pow2 cells per block ------------------------------


def test_even_non_pow2_cells_run_end_to_end():
    """The real invariant is *even* cells per block (octant split + halo
    alignment), not powers of two: a 6^3-cell config must survive stepping
    and an AMR event with mass conserved."""
    cfg = LidDrivenCavityConfig(
        root_grid=(2, 2, 2),
        cells_per_block=(6, 6, 6),
        nranks=2,
        omega=1.5,
        u_lid=(0.08, 0.0, 0.0),
        max_level=1,
        refine_upper=0.03,
        refine_lower=0.004,
        kernel_backend="ref",
        stepping_mode="arena",
    )
    sim = AMRLBM(cfg)
    m0 = sim.total_mass()
    sim.run(4, amr_interval=2)
    sim.forest.check_all()
    assert len(sim.forest.levels_in_use()) > 1  # exercised level transitions
    assert abs(sim.total_mass() - m0) / m0 < 1e-3
    assert np.isfinite(sim.max_velocity())


def test_odd_cells_rejected_with_aligned_message():
    with pytest.raises(AssertionError, match="even"):
        AMRLBM(LidDrivenCavityConfig(cells_per_block=(5, 5, 5)))


# -- satellite: stale plan_cache guard -----------------------------------------


def _uniform_arena(level=0):
    spec = LBMBlockSpec(cells=(4, 4, 4))
    reg = make_lbm_fields(spec)
    geom = ForestGeometry(root_grid=(2, 1, 1), max_level=6)
    forest = make_uniform_forest(geom, 1, level=level)
    _seed_fields(forest, reg)
    return forest, reg


def test_plan_cache_rebuilds_on_storage_rebind():
    """A cached plan holds views into the old arrays; replaying it after a
    storage rebind would fill the *old* arrays and leave the new ones
    untouched. The binding token must force a rebuild."""
    forest, reg = _uniform_arena()
    cache: dict = {}
    fill_ghost_layers(forest, reg, fields=("pdf",), plan_cache=cache)
    blocks = sorted(forest.all_blocks(), key=lambda b: b.bid)
    # rebind every block's storage (what LevelArena.adopt does on repack)
    for b in blocks:
        b.data["pdf"] = np.array(b.data["pdf"]) * 0 + float(b.bid % 97)
    fill_ghost_layers(forest, reg, fields=("pdf",), plan_cache=cache)
    a, b = blocks
    # a's low-x ghost plane must now hold b's value and vice versa
    assert np.all(a.data["pdf"][:, -1, 1:-1, 1:-1] == float(b.bid % 97))
    assert np.all(b.data["pdf"][:, 0, 1:-1, 1:-1] == float(a.bid % 97))


def test_plan_cache_version_token_guards_in_o1():
    """Callers that version their storage pass ``cache_token``: same token
    replays the cached plan (no O(blocks) scan), a bumped token rebuilds."""
    forest, reg = _uniform_arena()
    cache: dict = {}
    fill_ghost_layers(forest, reg, fields=("pdf",), plan_cache=cache, cache_token=1)
    (plan0, tok0) = next(iter(cache.values()))
    assert tok0 == ("version", 1)
    fill_ghost_layers(forest, reg, fields=("pdf",), plan_cache=cache, cache_token=1)
    assert next(iter(cache.values()))[0] is plan0  # replayed
    blocks = sorted(forest.all_blocks(), key=lambda b: b.bid)
    for b in blocks:  # storage rebind + version bump, as an arena adopt does
        b.data["pdf"] = np.array(b.data["pdf"]) * 0 + float(b.bid % 97)
    fill_ghost_layers(forest, reg, fields=("pdf",), plan_cache=cache, cache_token=2)
    assert next(iter(cache.values()))[0] is not plan0  # rebuilt
    a, b = blocks
    assert np.all(a.data["pdf"][:, -1, 1:-1, 1:-1] == float(b.bid % 97))


def test_plan_cache_rebuilds_on_topology_change():
    forest, reg = _uniform_arena()
    cache: dict = {}
    fill_ghost_layers(forest, reg, fields=("pdf",), plan_cache=cache)
    assert len(cache) == 1
    (plan0, _tok0) = next(iter(cache.values()))

    # refine one root: new leaves, new arrays — the old plan is meaningless
    pipe = AMRPipeline(balancer=SFCBalancer(), registry=reg)
    root0 = min(b.bid for b in forest.all_blocks())
    forest, _ = pipe.run_cycle(forest, Comm(1), lambda r, blocks: {root0: 1})
    fill_ghost_layers(forest, reg, fields=("pdf",), plan_cache=cache)
    (plan1, _tok1) = next(iter(cache.values()))
    assert plan1 is not plan0, "stale plan replayed for a mutated forest"
    # and the rebuilt plan actually produced cross-level ghost fills
    ref = {b.bid: np.array(b.data["pdf"]) for b in forest.all_blocks()}
    fill_ghost_layers(forest, reg, fields=("pdf",))  # cacheless reference
    for b in forest.all_blocks():  # one plan's operations replayed: exact
        np.testing.assert_array_equal(b.data["pdf"], ref[b.bid])
