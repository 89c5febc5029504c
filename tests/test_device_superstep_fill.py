"""The ``device_sharded`` superstep's ghost fill, bit for bit.

A two-level forest split over four ranks (one rank holds fewer L0 blocks, so
the L0 stack has a pad slot; ranks share same-level faces and exchange
cross-level values; one rank's L0 fill is all inbound, so it has no slab
part) is filled by :func:`make_device_superstep` with identity steppers, so a
coarse step is the fill alone. Its result must equal, bit for bit, the
per-cell fill applied on the host to the same padded stacks: each rank's
local plan through :func:`apply_compiled_ghost_plan`, then each inbound
message's gather and scatter segments in NumPy.

Both pattern slots of the superstep get the plan of one activity pattern,
so each coarse step runs that pattern's fill twice; the fill reads only
interior cells and writes only ghost cells, so the second pass writes what
the first did, and each pattern's fill is compared on its own.

Four XLA devices must exist before JAX starts: the device side runs this
file as a script in a child process with four CPU devices.
"""

from __future__ import annotations

import json
import subprocess
import sys

import numpy as np
import pytest

from conftest import four_cpu_devices_env, slab_cells
from repro.core import (
    AMRPipeline,
    BlockDataRegistry,
    Comm,
    DiffusionBalancer,
    ForestGeometry,
    make_uniform_forest,
)
from repro.kernels.lbm_collide import ops
from repro.lbm.grid import LBMBlockSpec
from repro.lbm.halo import (
    compile_rank_halo_plan,
    padded_block_counts,
    schedule_ppermute_rounds,
)
from repro.lbm.lattice import D3Q19

NRANKS = 4
LEVELS = (0, 1)
SPEC = LBMBlockSpec(cells=(4, 4, 4))
INDEX = {l: i for i, l in enumerate(LEVELS)}
SEED = 2**31 + 17


def _topology():
    """The forest's per-rank slot maps, padded counts and one plan per
    activity pattern."""
    forest = make_uniform_forest(ForestGeometry(root_grid=(2, 2, 2), max_level=1), NRANKS)
    first = min(b.bid for b in forest.all_blocks())
    forest, _ = AMRPipeline(
        balancer=DiffusionBalancer(mode="pushpull", flow_iterations=5),
        registry=BlockDataRegistry.trivial(),
    ).run_cycle(forest, Comm(NRANKS), lambda _r, blocks: {b: 1 for b in blocks if b == first})
    rank_slots: dict = {r: {} for r in range(NRANKS)}
    for r in range(NRANKS):
        for b in sorted(forest.local_blocks(r).values(), key=lambda b: b.bid):
            level = rank_slots[r].setdefault(b.level, {})
            level[b.bid] = len(level)
    plans = {
        p: compile_rank_halo_plan(forest, SPEC, rank_slots, levels={l for l in LEVELS if l >= 1 - p})
        for p in (0, 1)
    }
    return rank_slots, padded_block_counts(rank_slots, NRANKS), plans


def _stacks(counts) -> list[np.ndarray]:
    """Seeded padded ``(nranks, Bmax, Q, X, Y, Z)`` stacks; pad slots hold
    random values too, so a write to one shows."""
    rng = np.random.default_rng(SEED)
    dims = tuple(n + 2 * SPEC.ghost for n in SPEC.cells)
    return [
        rng.standard_normal((NRANKS, counts[l], D3Q19.Q, *dims)).astype(np.float32)
        for l in LEVELS
    ]


def _gather(stack: np.ndarray, kind: str, slot, cell) -> np.ndarray:
    """NumPy twin of a message segment's sender-side gather, (N, Q)."""
    v = stack.reshape(stack.shape[0], stack.shape[1], -1)[slot, :, cell]
    if kind != "fine":
        return v
    acc = v[:, 0]
    for k in range(1, 8):  # the canonical fixed-order octet sum
        acc = acc + v[:, k]
    return acc * np.float32(0.125)


def host_fill(plan, stacks: list[np.ndarray]) -> list[np.ndarray]:
    """The per-cell fill of one pattern's plan on the host, every value read
    from the stacks as given."""
    out = [s.copy() for s in stacks]
    for r, local in plan.local.items():
        filled = ops.apply_compiled_ghost_plan(local, {l: stacks[INDEX[l]][r] for l in LEVELS})
        for l in LEVELS:
            out[INDEX[l]][r] = np.asarray(filled[l])
    for m in plan.messages:
        payload = np.concatenate([
            _gather(stacks[INDEX[sl]][m.src_rank], kind, sb, sc) for sl, kind, sb, sc in m.gather
        ])
        off = 0
        for dl, db, dc, n in m.scatter:
            dst = out[INDEX[dl]][m.dst_rank]
            flat = dst.reshape(dst.shape[0], dst.shape[1], -1)
            flat[db, :, dc] = payload[off : off + n]
            off += n
    return out


def _inbound(plan, rank: int) -> list:
    """The rank's inbound messages in the order of the round schedule."""
    return [m for rnd in schedule_ppermute_rounds(plan.messages) for m in rnd.messages
            if m.dst_rank == rank]


def _targets(plan, rank: int, level: int) -> list[tuple[int, int]]:
    """(slot, cell) of every ghost cell the rank's local plan and inbound
    messages fill on ``level``."""
    out = []
    local = plan.local.get(rank)
    for op in () if local is None else local.ops:
        if op.dst_level == level:
            out += zip(op.dst_slot.tolist(), op.dst_cell.tolist())
    for m in _inbound(plan, rank):
        for dl, db, dc, _n in m.scatter:
            if dl == level:
                out += zip(db.tolist(), dc.tolist())
    return out


def device_fills() -> dict:
    """Per pattern: whether the device fill equals the host fill bit for bit
    per level, and the build's ``halo.slab_cells`` / ``halo.scatter_cells``
    series. Needs four XLA devices."""
    import jax

    from repro import telemetry
    from repro.kernels.lbm_collide.ops import make_device_superstep

    rank_slots, counts, plans = _topology()
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:NRANKS]), ("ranks",))
    sharding = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("ranks"))
    stacks = _stacks(counts)
    masks = tuple(
        jax.device_put(np.zeros(s.shape[:2] + s.shape[3:], np.int32), sharding) for s in stacks
    )
    tr = telemetry.get_tracer()
    telemetry.configure(enabled=True)
    out = {}
    for p, plan in plans.items():
        tr.reset()
        sched = schedule_ppermute_rounds(plan.messages)
        fn = make_device_superstep(
            mesh=mesh, levels=LEVELS, plans={0: plan, 1: plan}, schedules={0: sched, 1: sched},
            steppers={l: (lambda f, m: f) for l in LEVELS}, spec=SPEC, counts=counts,
        )
        # the program consumes its pdf operands; the host fill reads `stacks`
        got = fn(tuple(jax.device_put(s.copy(), sharding) for s in stacks), masks)
        want = host_fill(plan, stacks)
        out[p] = {
            # exact by construction: identity steppers, so the program only
            # moves values (and sums octets in the host's fixed order)
            "bitwise": {l: np.asarray(got[INDEX[l]]).tobytes() == want[INDEX[l]].tobytes()
                        for l in LEVELS},
            "filled": {l: bool((want[INDEX[l]] != stacks[INDEX[l]]).any()) for l in LEVELS},
            "slab": tr.metrics.counter("halo.slab_cells").series(),
            "scatter": tr.metrics.counter("halo.scatter_cells").series(),
        }
    return out


@pytest.fixture(scope="module")
def device_result() -> dict:
    proc = subprocess.run([sys.executable, __file__], env=four_cpu_devices_env(),
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return {int(p): r for p, r in json.loads(proc.stdout.strip().splitlines()[-1]).items()}


def test_the_forest_has_what_the_fill_must_handle():
    """A pad slot, cross-rank same-level faces, cross-level messages, and a
    rank level whose fill has no slab part."""
    rank_slots, counts, plans = _topology()
    assert any(len(rank_slots[r].get(l, {})) < counts[l] for r in range(NRANKS) for l in LEVELS)
    kinds = {(sl, kind) for m in plans[1].messages for sl, kind, _, _ in m.gather}
    assert {(0, "same"), (1, "same")} <= kinds
    assert {"fine", "coarse"} <= {kind for _, kind in kinds}
    no_slab = [
        (r, l)
        for r in range(NRANKS)
        for l, e in ops._merge_rank_fills(
            plans[1].local.get(r), _inbound(plans[1], r), SPEC, counts, INDEX
        ).items()
        if e["slabs"] is None
    ]
    assert no_slab


@pytest.mark.parametrize("pattern", [0, 1])
def test_slab_and_placed_cells_partition_each_ranks_targets(pattern):
    """For each rank and level, the cells the slab copies fill and the cells
    placed one by one are disjoint and together are exactly the targets of
    the rank's local plan and inbound messages."""
    _, counts, plans = _topology()
    plan = plans[pattern]
    seen_slab = False
    for r in range(NRANKS):
        per = ops._merge_rank_fills(plan.local.get(r), _inbound(plan, r), SPEC, counts, INDEX)
        for l in LEVELS:
            targets = _targets(plan, r, l)
            assert len(set(targets)) == len(targets)
            if l not in per:
                assert not targets
                continue
            e = per[l]
            slab = set() if e["slabs"] is None else slab_cells(e["slabs"])
            seen_slab |= bool(slab)
            slot, cell = ops._fill_targets(e["dst"])
            placed = set(zip(slot.tolist(), cell.tolist()))
            assert len(placed) == slot.size
            assert not slab & placed
            assert slab | placed == set(targets)
    assert seen_slab


@pytest.mark.parametrize("pattern", [0, 1])
def test_device_fill_matches_the_per_cell_host_fill_bitwise(device_result, pattern):
    """The slab copies plus placement on four devices leave every padded
    stack, ghosts and pad slots included, bit for bit as the per-cell host
    fill does."""
    res = device_result[pattern]
    active = {l for l in LEVELS if l >= 1 - pattern}
    assert {int(l) for l, f in res["filled"].items() if f} == active
    assert all(res["bitwise"].values()), res["bitwise"]


@pytest.mark.parametrize("pattern", [0, 1])
def test_device_fill_counts_slab_and_placed_cells_per_rank(device_result, pattern):
    """The build records ``halo.slab_cells`` and ``halo.scatter_cells`` per
    level and rank; each of the two pattern slots holds the plan, so per
    (level, rank) they add up to twice the rank's targets."""
    _, _, plans = _topology()
    res = device_result[pattern]
    for r in range(NRANKS):
        for l in LEVELS:
            key = f"level={l},rank={r}"
            n = len(_targets(plans[pattern], r, l))
            assert res["slab"].get(key, 0) + res["scatter"].get(key, 0) == 2 * n, key
    assert any(v > 0 for v in res["slab"].values())


if __name__ == "__main__":
    import jax

    assert jax.device_count() == NRANKS, jax.devices()
    print(json.dumps(device_fills()))
