"""Compile the main path's Pallas programs for a described TPU v5e.

Nothing runs: the TPU compiler installed with JAX compiles for a chip that is
described, not attached, and refuses what the chip would refuse (unaligned
block shapes, zero-size slices, too much scoped VMEM). Interpret-mode tests
cannot see any of that. Sizes are the real ones: 16^3 cells per block (18^3
tiles with the ghost layer), D3Q19, TRT, 64 blocks per call.

The topology is described inside a module fixture, never at import: only one
process may load the TPU library, and test workers must all collect the same
tests.
"""

from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import (
    AMRPipeline,
    BlockDataRegistry,
    Comm,
    DiffusionBalancer,
    ForestGeometry,
    make_uniform_forest,
)
from repro.kernels.lbm_collide.lbm_collide import lbm_stream_collide_pallas
from repro.kernels.lbm_collide.ops import (
    make_device_superstep,
    make_halo_stream_collide,
    make_stream_collide,
)
from repro.kernels.lbm_collide.ref import CT_LID, CT_WALL
from repro.lbm.grid import LBMBlockSpec
from repro.lbm.halo import (
    SLAB_DIRECTIONS,
    LevelSlabFill,
    compile_ghost_plan,
    compile_rank_halo_plan,
    lower_halo_fill,
    padded_block_counts,
    schedule_ppermute_rounds,
    split_halo_fill,
)
from repro.lbm.lattice import D3Q19

B, N = 64, 18  # blocks per call, tile edge (16 cells + 2 ghost)
KW = dict(omega=1.5, lattice=D3Q19, u_wall=(0.08, 0.0, 0.0), collision="trt")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _mask() -> np.ndarray:
    mask = np.zeros((B, N, N, N), np.int32)
    mask[:, 0] = CT_WALL
    mask[:, :, 0] = CT_WALL
    mask[:, :, :, -1] = CT_LID
    return mask


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_plain_stencil_compiles_natively(one_chip):
    step = jax.jit(
        lambda f, m: lbm_stream_collide_pallas(f, m, interpret=False, **KW)
    )
    compiled = step.lower(
        _sds((B, D3Q19.Q, N, N, N), jnp.float32, one_chip),
        _sds((B, N, N, N), jnp.int32, one_chip),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_halo_in_tile_step_compiles_natively(one_chip):
    # ghost targets: the whole x = 0 face of every block
    cells = np.arange(N * N, dtype=np.int32)
    dst_slot = np.repeat(np.arange(B, dtype=np.int32), cells.size)
    dst_cell = np.tile(cells, B)
    step = make_halo_stream_collide(
        dst_slot, dst_cell, level=0, mask=_mask(), backend="pallas", interpret=False, **KW
    )
    compiled = (
        jax.jit(step)
        .lower(
            _sds((B, D3Q19.Q, N, N, N), jnp.float32, one_chip),
            _sds((dst_cell.size, D3Q19.Q), jnp.float32, one_chip),
        )
        .compile()
    )
    assert "tpu_custom_call" in compiled.as_text()


def test_halo_in_tile_step_names_its_fill_and_kernel(one_chip):
    """The compiled step's ops carry the ghost fill's scope in their
    ``op_name`` metadata, and the kernel's custom call has a stable name,
    so a profile read against the HLO can tell the fill from the kernel."""
    cells = np.arange(N * N, dtype=np.int32)
    step = make_halo_stream_collide(
        np.repeat(np.arange(B, dtype=np.int32), cells.size), np.tile(cells, B),
        level=1, mask=_mask(), backend="pallas", interpret=False, **KW
    )
    text = (
        jax.jit(step)
        .lower(
            _sds((B, D3Q19.Q, N, N, N), jnp.float32, one_chip),
            _sds((B * cells.size, D3Q19.Q), jnp.float32, one_chip),
        )
        .compile()
        .as_text()
    )
    assert 'op_name="jit(step)/ghost_fill/scatter/L1/' in text
    kernel = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert kernel and all(ln.lstrip().startswith("%lbm_collide") for ln in kernel)


def test_slab_fill_step_compiles_natively_and_names_its_parts(one_chip):
    """A fill with a slab part and per-cell values besides compiles for the
    chip; the slab copies carry ``ghost_fill/slab/L<l>`` and the per-cell
    values ``ghost_fill/scatter/L<l>`` in their ``op_name`` metadata."""
    src = np.full((len(SLAB_DIRECTIONS), B), -1, np.int32)
    src[SLAB_DIRECTIONS.index((1, 0, 0))] = (np.arange(B) + 1) % B
    src[SLAB_DIRECTIONS.index((0, -1, 1)), ::2] = np.arange(1, B, 2)
    slabs = LevelSlabFill((N - 2,) * 3, 1, src)
    cells = np.arange(N * N, dtype=np.int32)  # the x = 0 face: per-cell values
    step = make_halo_stream_collide(
        np.repeat(np.arange(B, dtype=np.int32), cells.size), np.tile(cells, B),
        level=1, mask=_mask(), slabs=slabs, backend="pallas", interpret=False, **KW
    )
    text = (
        jax.jit(step)
        .lower(
            _sds((B, D3Q19.Q, N, N, N), jnp.float32, one_chip),
            _sds((B * cells.size, D3Q19.Q), jnp.float32, one_chip),
        )
        .compile()
        .as_text()
    )
    assert 'op_name="jit(step)/ghost_fill/slab/L1/' in text
    assert 'op_name="jit(step)/ghost_fill/scatter/L1/' in text
    assert "tpu_custom_call" in text


def test_one_level_slab_fill_compiles_without_scatter(one_chip):
    """On a one-level grid every ghost slab has a same-level source or none:
    the whole fill is slab copies, and the compiled step holds no scatter
    over the level stack."""
    forest = make_uniform_forest(ForestGeometry(root_grid=(4, 4, 4), max_level=1), 1)
    blocks = sorted(forest.all_blocks(), key=lambda b: b.bid)
    assert len(blocks) == B
    spec = LBMBlockSpec(cells=(N - 2,) * 3)
    plan = compile_ghost_plan(forest, spec, {0: {b.bid: i for i, b in enumerate(blocks)}})
    slabs, residual = split_halo_fill(lower_halo_fill(plan)[0], spec, B)
    assert residual is None and slabs is not None
    step = make_halo_stream_collide(
        np.zeros(0, np.int32), np.zeros(0, np.int32), level=0, mask=_mask(),
        slabs=slabs, backend="pallas", interpret=False, **KW
    )
    text = (
        jax.jit(lambda f: step(f, None))
        .lower(_sds((B, D3Q19.Q, N, N, N), jnp.float32, one_chip))
        .compile()
        .as_text()
    )
    assert "/ghost_fill/slab/L0/" in text
    assert "tpu_custom_call" in text
    assert not re.search(r"\bscatter\(", text)


def test_device_superstep_compiles_for_four_chips_and_names_its_halo_parts(topo):
    """The ``device_sharded`` superstep of a two-level forest split over four
    ranks compiles for the four chips of a v5e 2x2: its halo permutes are
    collective-permute instructions labelled ``halo_ppermute/p<p>``, and the
    gathers and fills around them carry ``halo_emit/p<p>`` and
    ``halo_absorb/p<p>``. The fill copies rank-local same-level slabs
    (``ghost_fill/slab/L0`` inside ``halo_absorb``) and places every other
    value, so the program holds no scatter."""
    nranks = 4
    forest = make_uniform_forest(ForestGeometry(root_grid=(2, 2, 2), max_level=1), nranks)
    first = min(b.bid for b in forest.all_blocks())
    forest, _ = AMRPipeline(
        balancer=DiffusionBalancer(mode="pushpull", flow_iterations=5),
        registry=BlockDataRegistry.trivial(),
    ).run_cycle(forest, Comm(nranks), lambda _r, blocks: {b: 1 for b in blocks if b == first})
    assert sorted(forest.levels_in_use()) == [0, 1]
    rank_slots: dict = {r: {} for r in range(nranks)}
    for r in range(nranks):
        for b in sorted(forest.local_blocks(r).values(), key=lambda b: b.bid):
            level = rank_slots[r].setdefault(b.level, {})
            level[b.bid] = len(level)
    spec = LBMBlockSpec(cells=(N - 2,) * 3)
    plans = {p: compile_rank_halo_plan(forest, spec, rank_slots, levels={l for l in (0, 1) if l >= 1 - p})
             for p in (0, 1)}
    schedules = {p: schedule_ppermute_rounds(plans[p].messages) for p in plans}
    assert all(schedules.values())
    mesh = jax.sharding.Mesh(np.array(topo.devices[:nranks]), ("ranks",))
    counts = padded_block_counts(rank_slots, nranks)
    fn = make_device_superstep(
        mesh=mesh, levels=(0, 1), plans=plans, schedules=schedules,
        steppers={l: make_stream_collide(backend="pallas", interpret=False, **KW) for l in (0, 1)},
        spec=spec, counts=counts,
    )
    sharded = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("ranks"))
    text = fn.lower(
        tuple(_sds((nranks, counts[l], D3Q19.Q, N, N, N), jnp.float32, sharded) for l in (0, 1)),
        tuple(_sds((nranks, counts[l], N, N, N), jnp.int32, sharded) for l in (0, 1)),
    ).compile().as_text()
    permutes = [ln for ln in text.splitlines()
                if re.search(r"= \S.* collective-permute(-start|-done)?\(", ln)]
    assert permutes and all("/halo_ppermute/p" in ln for ln in permutes)
    for part in ("halo_emit", "halo_absorb"):
        assert f"/{part}/p0/" in text and f"/{part}/p1/" in text
    assert re.search(r'op_name="[^"]*/halo_absorb/p1/[^"]*/ghost_fill/slab/L0/', text)
    assert not re.search(r"\bscatter\(", text)
    assert "tpu_custom_call" in text
