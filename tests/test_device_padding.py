"""Equal-blocks-per-rank padding: the device fabric's SPMD layout, proven.

The ``device_sharded`` mode pads every rank's per-level block stack to the
max per-rank block count so all devices run one program. This is only sound
if the padding is *invisible*: no compiled halo plan may ever read or write
a padded slot, padded slots must be exactly inert under the kernel (masked-
slot writes provably dead), and the physics mass of the real slots must be
untouched. A hand-rolled hypothesis twin in the ``test_balancing.py`` style
pins these properties over seeded-random forest partitions (refine/coarsen/
balance driven by ``make_random_marks``), not just the cavity trajectory the
conformance suite walks:

* **layout** — padded counts are the per-level max over ranks, and every
  rank's dense slot ids stay valid in the padded layout unchanged;
* **no padded reads** — ``verify_padded_plan`` returns no findings for any
  activity pattern's compiled plan on any partition;
* **schedule** — the ppermute rounds are partial permutations covering
  every message exactly once, for any partition;
* **dead writes** — stepping a padded stack leaves real slots equal to
  stepping the unpadded stack (to the float32 conformance tolerance: the
  compiler may round a larger stack differently) and padded slots (all-WALL
  mask, weight PDFs) bitwise unchanged: the pad value is an exact fixed
  point of the stream+collide kernel, so padding adds no mass to real slots.
"""

import numpy as np
import pytest

from conftest import assert_f32_match, assert_mass_match, make_random_marks
from repro.core import (
    AMRPipeline,
    BlockDataRegistry,
    Comm,
    DiffusionBalancer,
    ForestGeometry,
    make_uniform_forest,
)
from repro.kernels.lbm_collide.ops import make_stream_collide
from repro.lbm.grid import CellType, LBMBlockSpec
from repro.lbm.halo import (
    compile_rank_halo_plan,
    padded_block_counts,
    schedule_ppermute_rounds,
    verify_padded_plan,
)
from repro.lbm.lattice import D3Q19

NRANKS = 4
SEEDS = range(6)
SPEC = LBMBlockSpec(cells=(8, 8, 8), ghost=1, lattice=D3Q19)


def _random_partition(seed: int):
    """A seeded-random forest: refine/coarsen marks + diffusion balancing."""
    geom = ForestGeometry(root_grid=(2, 2, 2), max_level=3)
    forest = make_uniform_forest(geom, NRANKS, level=1)
    pipe = AMRPipeline(
        balancer=DiffusionBalancer(mode="pushpull", flow_iterations=5),
        registry=BlockDataRegistry.trivial(),
    )
    forest, _report = pipe.run_cycle(
        forest, Comm(NRANKS), make_random_marks(seed)
    )
    forest.check_all()
    return forest


def _rank_slots(forest):
    """Dense per-rank slot maps, exactly as ``RankArenas.adopt`` assigns."""
    slots: dict[int, dict[int, dict[int, int]]] = {}
    for r in range(NRANKS):
        per_level: dict[int, dict[int, int]] = {}
        for b in forest.local_blocks(r).values():
            per_level.setdefault(b.level, {})[b.bid] = len(
                per_level.get(b.level, {})
            )
        slots[r] = per_level
    return slots


@pytest.mark.parametrize("seed", SEEDS)
def test_padded_layout_and_plans_never_touch_a_padded_slot(seed):
    forest = _random_partition(seed)
    rank_slots = _rank_slots(forest)
    counts = padded_block_counts(rank_slots, NRANKS)

    # layout: per-level max over ranks; dense rank-local ids stay valid
    for lvl in forest.levels_in_use():
        per_rank = [len(rank_slots[r].get(lvl, {})) for r in range(NRANKS)]
        assert counts[lvl] == max(per_rank)
        for r in range(NRANKS):
            ids = sorted(rank_slots[r].get(lvl, {}).values())
            assert ids == list(range(len(ids)))  # dense from zero
            assert all(i < counts[lvl] for i in ids)

    # no activity pattern's compiled plan reads or writes a padded slot, and
    # every pattern's ppermute schedule is a partial-permutation exact cover
    levels = sorted(forest.levels_in_use())
    lmax = levels[-1]
    for p in range(lmax + 1):
        active = {l for l in levels if l >= lmax - p}
        plan = compile_rank_halo_plan(
            forest, SPEC, rank_slots, fields=("pdf",), levels=active
        )
        assert verify_padded_plan(plan, rank_slots) == []
        rounds = schedule_ppermute_rounds(plan.messages)
        covered = sorted(m.key for rnd in rounds for m in rnd.messages)
        assert covered == sorted(m.key for m in plan.messages)
        for rnd in rounds:
            srcs = [s for s, _ in rnd.perm]
            dsts = [d for _, d in rnd.perm]
            assert len(set(srcs)) == len(srcs), rnd.perm
            assert len(set(dsts)) == len(dsts), rnd.perm


def test_verify_padded_plan_detects_an_out_of_range_slot():
    """Sanity: the verifier is not vacuous — a slot map clipped below a used
    dst slot is reported as a violation."""
    forest = _random_partition(0)
    rank_slots = _rank_slots(forest)
    plan = compile_rank_halo_plan(forest, SPEC, rank_slots, fields=("pdf",))
    if not plan.messages:  # pragma: no cover - partition-dependent guard
        pytest.skip("partition produced no cross-rank messages")
    # shrink the receiver's claimed block count below a used dst slot
    m = plan.messages[0]
    dst_level = m.scatter[0][0]
    clipped = {
        r: {l: dict(s) for l, s in levels.items()}
        for r, levels in rank_slots.items()
    }
    used = int(max(int(s[1].max()) for s in m.scatter if s[0] == dst_level))
    kept = {
        bid: slot
        for bid, slot in clipped[m.dst_rank][dst_level].items()
        if slot <= used - 1
    } if used > 0 else {}
    clipped[m.dst_rank][dst_level] = kept
    assert verify_padded_plan(plan, clipped) != []


@pytest.mark.parametrize("seed", SEEDS)
def test_padding_is_inert_under_the_kernel_and_preserves_mass(seed):
    """Stepping the padded stack == stepping the real stack to float32
    round-off, and the padded slots are an exact fixed point (weight PDFs
    under all-WALL masks): masked-slot writes are provably dead and total
    mass over real slots is the unpadded mass."""
    rng = np.random.default_rng(seed)
    Q = SPEC.lattice.Q
    shape = tuple(c + 2 * SPEC.ghost for c in SPEC.cells)
    B, Bmax = 3, 5  # a rank owning 3 of a 5-slot padded stack

    pdf = (0.1 + 0.9 * rng.random((B, Q) + shape)).astype(np.float32)
    # random masks with a WALL shell and a sprinkle of LID cells: the kernel
    # must be inert on pads regardless of what real blocks look like
    mask = np.full((B,) + shape, CellType.WALL, np.int32)
    inner = (slice(None), slice(1, -1), slice(1, -1), slice(1, -1))
    mask[inner] = rng.choice(
        [CellType.FLUID, CellType.WALL, CellType.LID],
        size=mask[inner].shape,
        p=[0.8, 0.15, 0.05],
    ).astype(np.int32)

    w = np.asarray(SPEC.lattice.w, dtype=np.float32)
    pad_pdf = np.broadcast_to(
        w.reshape((Q, 1, 1, 1)), (Bmax - B, Q) + shape
    ).copy()
    padded_pdf = np.concatenate([pdf, pad_pdf])
    padded_mask = np.concatenate(
        [mask, np.full((Bmax - B,) + shape, CellType.WALL, np.int32)]
    )

    # padding preserves total mass: exactly the real mass plus the known
    # inert pad contribution (weights sum to 1 per cell); concatenation only
    # copies, so the real slots are bitwise the input
    assert np.asarray(padded_pdf[:B]).tobytes() == pdf.tobytes()

    step = make_stream_collide(
        omega=1.5, lattice=SPEC.lattice, u_wall=(0.08, 0.0, 0.0), backend="ref"
    )
    out_real = np.asarray(step(pdf, mask))
    out_padded = np.asarray(step(padded_pdf, padded_mask))

    # real slots: the vmapped kernel is per-block, so padding cannot perturb
    # real physics beyond how the compiler rounds a larger stack
    assert_f32_match(out_padded[:B], out_real, what="real slots of the padded step")
    # padded slots: bitwise unchanged — exact by construction, the kernel
    # writes f_out * 0 + f * 1 on non-fluid cells, which is f in IEEE arithmetic
    assert out_padded[B:].tobytes() == pad_pdf.tobytes()
    # and therefore mass over real slots is preserved by padding
    assert_mass_match(
        float(out_padded[:B].sum(dtype=np.float64)),
        float(out_real.sum(dtype=np.float64)),
        what="real slots of the padded step",
    )


def _device_sharded_build(*names: str):
    """A one-rank ``device_sharded`` cavity after one criterion event (two
    levels), and the series of the telemetry counters and gauges ``names``
    that building its program for that topology records."""
    from repro import telemetry
    from repro.lbm import AMRLBM, LidDrivenCavityConfig

    sim = AMRLBM(LidDrivenCavityConfig(
        root_grid=(2, 2, 2), cells_per_block=(4, 4, 4), nranks=1, omega=1.5,
        u_lid=(0.08, 0.0, 0.0), max_level=1, refine_upper=0.03,
        refine_lower=0.004, kernel_backend="ref", stepping_mode="device_sharded",
    ))
    sim.advance(2)
    sim.adapt()
    tr = telemetry.get_tracer()
    was = tr.enabled
    telemetry.configure(enabled=True)
    tr.reset()
    try:
        sim.advance(1)  # builds the program for the new topology
        series = {n: tr.metrics.snapshot()[n]["series"] for n in names}
    finally:
        telemetry.configure(enabled=was)
        tr.reset()
    return sim, series


def test_device_sharded_build_records_its_slot_layout():
    """Building the ``device_sharded`` program records the layout it pads
    to (``DeviceShardedEngine.slot_layout``) as telemetry: real blocks per
    level and rank, stepped slots and the pad share per level."""
    sim, series = _device_sharded_build(
        "device.real_blocks", "device.stepped_slots", "device.pad_share"
    )
    real, stepped, pad = series.values()
    layout = sim.engine.slot_layout()
    assert sorted(layout) == [0, 1]
    for level, lay in layout.items():
        assert lay["blocks"] == [sum(b.level == level for b in sim.forest.all_blocks())]
        assert real[f"level={level},rank=0"] == lay["blocks"][0]
        assert stepped[f"level={level}"] == lay["slots"] == lay["blocks"][0]
        assert pad[f"level={level}"] == lay["pad_share"] == 0.0


def test_device_sharded_build_counts_slab_and_placed_cells_per_rank():
    """Building the ``device_sharded`` program records ``halo.slab_cells``
    and ``halo.scatter_cells`` per level and rank; per (level, rank) they
    add up to the ghost cells the rank's local plans and inbound messages
    target over the activity patterns, and the slab copies take some."""
    sim, series = _device_sharded_build("halo.slab_cells", "halo.scatter_cells")
    slab, scatter = series.values()
    levels = sorted(sim.forest.levels_in_use())
    arenas = sim.engine.arenas.per_rank
    rank_slots = {r: {l: a.slots(l) for l in a.levels()} for r, a in enumerate(arenas)}
    want: dict[str, int] = {}
    for p in range(len(levels)):
        plan = compile_rank_halo_plan(
            sim.forest, sim.fields, rank_slots, fields=("pdf",),
            levels={l for l in levels if l >= max(levels) - p},
        )
        for r, local in plan.local.items():
            for op in local.ops:
                key = f"level={op.dst_level},rank={r}"
                want[key] = want.get(key, 0) + op.num_cells
        for m in plan.messages:
            for dl, _db, _dc, n in m.scatter:
                key = f"level={dl},rank={m.dst_rank}"
                want[key] = want.get(key, 0) + n
    assert sorted(want) == [f"level={l},rank=0" for l in levels]
    for key, n in want.items():
        assert slab[key] > 0
        assert slab[key] + scatter[key] == n, key
