"""Conformance suite for the sharded and fused (device-resident) data planes.

Pins the invariants that make ``stepping_mode="sharded"`` a faithful
distributed execution of the single-rank reference, ``stepping_mode="fused"``
a faithful *device-resident* one, and ``stepping_mode="fused_sharded"`` both
at once — same fields to the float32 conformance tolerance
(``conftest.F32_ATOL``) across an AMR event, mass conserved, zero
host<->device transfers per substep in steady state (asserted on the
residency layers' counters), and cross-rank traffic that stays p2p-only with
byte-exact parity between the host patches and the device-built messages:

* **conformance** — the full AMR+LBM cycle at 1/4/13 simulated ranks
  reproduces the single-rank restack reference macroscopic fields
  (density/velocity) to float32 round-off after 8 coarse steps spanning at
  least one AMR event (identical kernels, identical exchange arithmetic, only
  ownership and so the stack shapes the compiler rounds for differ);
* **communication shape** — ghost exchange puts only point-to-point traffic
  on the fabric, every communicating rank pair is a process-graph neighbor
  pair, and stepping triggers no collectives at all;
* **storage shape** — each rank's arenas hold exactly its own blocks
  (O(local blocks) bytes), re-established after every AMR event.
"""

import numpy as np
import pytest

from conftest import assert_f32_match, assert_mass_match, macroscopic64
from repro.lbm import AMRLBM, LidDrivenCavityConfig, engines
from repro.lbm.halo import RankHaloPlan

COARSE_STEPS = 8
AMR_INTERVAL = 4  # -> AMR cycles after steps 4 and 8: the run spans >= 1 event

BASE = dict(
    root_grid=(2, 2, 2),
    cells_per_block=(8, 8, 8),
    omega=1.5,
    u_lid=(0.08, 0.0, 0.0),
    max_level=1,
    refine_upper=0.03,
    refine_lower=0.004,
    kernel_backend="ref",  # interpret-mode pallas is identical but far slower
)


def _run(mode: str, nranks: int) -> AMRLBM:
    sim = AMRLBM(LidDrivenCavityConfig(nranks=nranks, stepping_mode=mode, **BASE))
    sim.run(COARSE_STEPS, amr_interval=AMR_INTERVAL)
    return sim


@pytest.fixture(scope="module")
def reference() -> AMRLBM:
    """Single-rank restack run: the seed data path, one global arena."""
    return _run("restack", 1)


@pytest.mark.parametrize(
    "nranks", [1, 4, pytest.param(13, marks=pytest.mark.slow)]
)
def test_sharded_matches_single_rank_reference(reference, nranks):
    sim = _run("sharded", nranks)
    assert sim.amr_cycles >= 1, "the run must span at least one AMR event"
    assert len(sim.forest.levels_in_use()) > 1
    # ownership-independent topology + fields: same leaves, same physics
    _assert_macroscopic_match(sim, reference)
    assert_mass_match(
        sim.total_mass(), reference.total_mass(), what=sim.cfg.stepping_mode
    )


def test_sharded_stepping_uses_only_p2p_next_neighbor_traffic():
    sim = AMRLBM(LidDrivenCavityConfig(nranks=4, stepping_mode="sharded", **BASE))
    sim.advance(2)
    sim.adapt()  # develop two levels so coarse/fine exchange paths run too
    assert len(sim.forest.levels_in_use()) > 1

    before = sim.comm.stats.summary()
    sim.advance(2)
    after = sim.comm.stats.summary()
    # stepping is pure data plane: messages + delivery rounds, no collectives
    assert after["allreduce_calls"] == before["allreduce_calls"]
    assert after["allgather_calls"] == before["allgather_calls"]
    assert after["collective_bytes_per_rank"] == before["collective_bytes_per_rank"]
    assert after["p2p_bytes"] > before["p2p_bytes"]
    assert after["exchange_rounds"] > before["exchange_rounds"]
    # the driver attributes the same traffic to the "halo" data-plane stage
    halo = sim.data_stats["halo"]
    assert halo.p2p_bytes > 0 and halo.exchange_rounds > 0
    assert halo.collective_bytes_per_rank == 0

    # every communicating pair is a process-graph neighbor pair (paper §2:
    # next-neighbor communication only); cache entries are (plan, token)
    plans = [p for p, _tok in sim._halo_plans.values() if isinstance(p, RankHaloPlan)]
    assert plans, "sharded stepping must go through rank halo plans"
    for plan in plans:
        for src, dst in plan.rank_pairs():
            assert src != dst
            assert dst in sim.forest.neighbor_ranks(src), (src, dst)


def _assert_macroscopic_match(sim: AMRLBM, reference: AMRLBM) -> None:
    ref_blocks = {b.bid: b for b in reference.forest.all_blocks()}
    got_blocks = {b.bid: b for b in sim.forest.all_blocks()}
    assert set(ref_blocks) == set(got_blocks)
    for bid, rb in ref_blocks.items():
        gb = got_blocks[bid]
        rho_r, u_r = macroscopic64(rb.data["pdf"], sim.spec.lattice)
        rho_g, u_g = macroscopic64(gb.data["pdf"], sim.spec.lattice)
        g = sim.spec.ghost
        sl = (slice(g, -g),) * 3
        assert_f32_match(rho_g[sl], rho_r[sl], what=f"rho of block {bid:#x}")
        assert_f32_match(
            u_g[(Ellipsis, *sl)], u_r[(Ellipsis, *sl)], what=f"u of block {bid:#x}"
        )


def test_fused_matches_restack_reference_across_amr(reference):
    """The device-resident fused superstep is a faithful execution of the
    substep cycle: the same macroscopic fields to float32 round-off (the
    compiled exchange mirrors the host resampling arithmetic exactly) after 8
    coarse steps spanning an AMR event, and mass is conserved."""
    sim = _run("fused", 1)
    assert sim.amr_cycles >= 1, "the run must span at least one AMR event"
    assert len(sim.forest.levels_in_use()) > 1
    _assert_macroscopic_match(sim, reference)
    assert_mass_match(
        sim.total_mass(), reference.total_mass(), what=sim.cfg.stepping_mode
    )
    # mass conservation against the initial condition (equilibrium at rho=1:
    # one unit per fluid root-cell volume)
    fresh = AMRLBM(LidDrivenCavityConfig(nranks=1, stepping_mode="fused", **BASE))
    assert abs(sim.total_mass() - fresh.total_mass()) / fresh.total_mass() < 1e-3


def test_fused_steady_state_performs_zero_host_transfers():
    """Between AMR events the fused loop is fully device-resident: after the
    one-time upload, further coarse steps perform no host<->device transfer
    in either direction (asserted via the residency layer's counters)."""
    sim = AMRLBM(LidDrivenCavityConfig(nranks=1, stepping_mode="fused", **BASE))
    sim.advance(1)  # builds the program + uploads pdf/mask
    res = sim.arena.device()
    before = (res.h2d_transfers, res.d2h_transfers)
    assert res.h2d_transfers > 0  # the initial upload happened and was counted
    sim.advance(3)  # 3 coarse steps = 3 * 2^lmax substeps, all on device
    assert (res.h2d_transfers, res.d2h_transfers) == before
    # in-program exchanges are attributed to the "fused" data-plane stage
    fused = sim.data_stats["fused"]
    lmax = max(sim.forest.levels_in_use())
    assert fused.exchange_rounds == 4 * 2**lmax
    assert fused.seconds > 0.0
    # diagnostics rematerialize host views: exactly the flush transfers
    sim.total_mass()
    assert res.d2h_transfers > before[1]
    d2h = res.d2h_transfers
    sim.total_mass()  # already synced: no second download
    assert res.d2h_transfers == d2h


def test_fused_checkpoint_after_materialize_matches_reference(tmp_path):
    """External host-data consumers (checkpointing) see the current state
    after materialize_host(); an arena adopt with un-flushed device results
    fails loudly instead of silently losing steps."""
    from repro.core.checkpoint import load_checkpoint, save_checkpoint

    sim = AMRLBM(LidDrivenCavityConfig(nranks=1, stepping_mode="fused", **BASE))
    sim.run(COARSE_STEPS, amr_interval=AMR_INTERVAL)
    sim.advance(1)  # end on a plain advance: device is newer than host now
    sim.materialize_host()
    save_checkpoint(sim.forest, sim.registry, tmp_path / "ckpt")
    restored = load_checkpoint(tmp_path / "ckpt", sim.registry)
    ref2 = _run("restack", 1)
    ref2.advance(1)
    ref_blocks = {b.bid: b for b in ref2.forest.all_blocks()}
    got_blocks = {b.bid: b for b in restored.all_blocks()}
    assert set(ref_blocks) == set(got_blocks)
    g = sim.spec.ghost
    sl = (Ellipsis,) + (slice(g, -g),) * 3
    for bid, rb in ref_blocks.items():
        assert_f32_match(
            got_blocks[bid].data["pdf"][sl], rb.data["pdf"][sl],
            what=f"restored pdf of block {bid:#x}",
        )


def test_fused_adopt_without_flush_fails_loudly():
    sim = AMRLBM(LidDrivenCavityConfig(nranks=1, stepping_mode="fused", **BASE))
    sim.advance(1)  # device-newer pdf state pending
    with pytest.raises(AssertionError, match="flush"):
        sim.arena.adopt(sim.forest)
    sim.materialize_host()
    sim.arena.adopt(sim.forest)  # flushed: fine


def test_fused_transfers_only_on_amr_events():
    sim = AMRLBM(LidDrivenCavityConfig(nranks=1, stepping_mode="fused", **BASE))
    sim.advance(2)
    sim.adapt()
    assert len(sim.forest.levels_in_use()) > 1
    sim.advance(1)  # re-upload for the new topology
    res = sim.arena.device()
    before = (res.h2d_transfers, res.d2h_transfers)
    sim.advance(2)
    assert (res.h2d_transfers, res.d2h_transfers) == before


@pytest.mark.parametrize(
    "nranks", [1, 4, pytest.param(13, marks=pytest.mark.slow)]
)
def test_fused_sharded_matches_single_rank_reference(reference, nranks):
    """The per-rank device-resident data plane is a faithful distributed
    execution: fused_sharded at 1/4/13 ranks reproduces the single-rank
    restack reference to float32 round-off (identical kernels, identical
    exchange arithmetic on device, only ownership differs) after 8 coarse
    steps spanning an AMR event, and mass is conserved."""
    sim = _run("fused_sharded", nranks)
    assert sim.amr_cycles >= 1, "the run must span at least one AMR event"
    assert len(sim.forest.levels_in_use()) > 1
    _assert_macroscopic_match(sim, reference)
    assert_mass_match(
        sim.total_mass(), reference.total_mass(), what=sim.cfg.stepping_mode
    )


def test_fused_sharded_steady_state_performs_zero_host_transfers():
    """Between AMR events every rank's substep loop is fully device-resident:
    after the one-time upload, further coarse steps perform no host<->device
    transfer in either direction on ANY rank (asserted via each rank's
    residency counters) — the only per-substep host involvement is routing
    device-built message buffers through the Comm fabric."""
    sim = AMRLBM(
        LidDrivenCavityConfig(nranks=4, stepping_mode="fused_sharded", **BASE)
    )
    sim.advance(2)
    sim.adapt()
    assert len(sim.forest.levels_in_use()) > 1
    sim.advance(1)  # re-upload for the new topology
    res = [a.device() for a in sim.arenas.per_rank if a.levels()]
    before = [(r.h2d_transfers, r.d2h_transfers) for r in res]
    assert any(r.h2d_transfers > 0 for r in res)  # uploads happened, counted
    sim.advance(2)
    assert [(r.h2d_transfers, r.d2h_transfers) for r in res] == before
    # the coarse-step loop is attributed to the "fused" data-plane stage,
    # including the cross-rank device-message traffic it put on the fabric
    fused = sim.data_stats["fused"]
    assert fused.seconds > 0.0
    assert fused.p2p_bytes > 0 and fused.collective_bytes_per_rank == 0
    # diagnostics rematerialize host views: flush transfers only
    d2h0 = sum(r.d2h_transfers for r in res)
    sim.total_mass()
    assert sum(r.d2h_transfers for r in res) > d2h0
    d2h1 = sum(r.d2h_transfers for r in res)
    sim.total_mass()  # already synced: no second download
    assert sum(r.d2h_transfers for r in res) == d2h1


def test_fused_sharded_stepping_uses_only_p2p_next_neighbor_traffic():
    """The compiled rank-halo plan preserves the communication shape of the
    host-sharded exchange: p2p only, no collectives, every communicating
    pair a process-graph neighbor pair, and byte-for-byte the same traffic
    (sender-side resampling produces identically-sized messages)."""
    from repro.lbm.halo import compile_rank_halo_plan

    sim = AMRLBM(
        LidDrivenCavityConfig(nranks=4, stepping_mode="fused_sharded", **BASE)
    )
    sim.advance(2)
    sim.adapt()
    assert len(sim.forest.levels_in_use()) > 1
    before = sim.comm.stats.summary()
    sim.advance(2)
    after = sim.comm.stats.summary()
    assert after["allreduce_calls"] == before["allreduce_calls"]
    assert after["allgather_calls"] == before["allgather_calls"]
    assert after["collective_bytes_per_rank"] == before["collective_bytes_per_rank"]
    assert after["p2p_bytes"] > before["p2p_bytes"]
    assert after["exchange_rounds"] > before["exchange_rounds"]

    # every communicating pair is a process-graph neighbor pair, and the
    # per-pair message bytes equal the host plan's patch bytes exactly
    arenas = sim.arenas
    rank_slots = {
        r: {l: arenas.per_rank[r].slots(l) for l in arenas.per_rank[r].levels()}
        for r in range(4)
    }
    from repro.lbm.halo import build_rank_halo_plan

    plan = compile_rank_halo_plan(sim.forest, sim.fields, rank_slots)
    host_plan = build_rank_halo_plan(sim.forest, sim.fields)
    assert plan.rank_pairs() == host_plan.rank_pairs()
    assert plan.cross_rank_bytes() == host_plan.cross_rank_bytes()
    for m in plan.messages:
        assert m.src_rank != m.dst_rank
        assert m.dst_rank in sim.forest.neighbor_ranks(m.src_rank)
        assert m.nbytes == host_plan.nbytes[(m.src_rank, m.dst_rank)]

    # the static halo-protocol verifier proves the full contract on the same
    # plan: pairwise-matched messages, byte symmetry, in-bounds indices,
    # interior-only gathers, exact ghost-ring coverage
    from repro.analysis import verify_compiled_rank_plan

    assert verify_compiled_rank_plan(sim.forest, sim.fields, plan, rank_slots) == []


def test_rank_arenas_partition_data_by_owner_across_amr():
    sim = AMRLBM(LidDrivenCavityConfig(nranks=4, stepping_mode="sharded", **BASE))
    sim.arenas.check_consistent(sim.forest)
    sim.advance(2)
    sim.adapt()
    sim.advance(1)
    # after migration/refine/coarsen the per-rank arenas were rebuilt: every
    # block's storage lives in (and only in) its owner's arena
    sim.arenas.check_consistent(sim.forest)
    for r in range(4):
        arena = sim.arenas.per_rank[r]
        owned = {b.bid for b in sim.forest.local_blocks(r).values()}
        indexed = {bid for lvl in arena.levels() for bid in arena.slots(lvl)}
        assert indexed == owned
    held = sim.arenas.held_bytes_per_rank()
    per_block = sum(
        int(np.prod(spec.block_shape(sim.fields.cells))) * np.dtype(spec.dtype).itemsize
        for spec in sim.fields.fields.values()
    )
    for r in range(4):
        assert held[r] == len(sim.forest.local_blocks(r)) * per_block


# -- pallas-backend legs -------------------------------------------------------
# The pallas kernel computes moments with unrolled per-direction arithmetic
# (the ref kernel uses einsum contractions), so pallas runs agree with ref
# runs only to the cross-backend tolerance in tests/test_kernels_lbm.py.
# Within the backend the conformance contract is the same as for ref: every
# fused mode matches a pallas *restack* reference to the float32 conformance
# tolerance across an AMR event. Shorter schedule than the ref legs —
# interpret mode is slow.

PALLAS_STEPS = 4
PALLAS_INTERVAL = 2  # AMR cycles after steps 2 and 4: spans >= 1 event


def _run_pallas(mode: str, nranks: int, **over) -> AMRLBM:
    cfg = {**BASE, "kernel_backend": "pallas", **over}
    sim = AMRLBM(LidDrivenCavityConfig(nranks=nranks, stepping_mode=mode, **cfg))
    sim.run(PALLAS_STEPS, amr_interval=PALLAS_INTERVAL)
    return sim


@pytest.fixture(scope="module")
def pallas_reference() -> AMRLBM:
    """Single-rank restack run on the pallas (interpret-on-CPU) kernel."""
    return _run_pallas("restack", 1)


@pytest.mark.parametrize(
    "mode,nranks",
    [("fused", 1), ("fused_sharded", 1), ("fused_sharded", 4)],
)
def test_pallas_fused_modes_match_pallas_restack_reference(
    pallas_reference, mode, nranks
):
    """The halo-in-tile Pallas superstep (ghost ring scattered into the VMEM
    tile before the stencil reads) is a faithful execution of the substep
    cycle on its own backend, solo and sharded, across an AMR event."""
    sim = _run_pallas(mode, nranks)
    assert sim.amr_cycles >= 1, "the run must span at least one AMR event"
    assert len(sim.forest.levels_in_use()) > 1
    _assert_macroscopic_match(sim, pallas_reference)
    assert_mass_match(
        sim.total_mass(), pallas_reference.total_mass(), what=mode
    )


def test_pallas_fused_steady_state_performs_zero_host_transfers():
    """Halo-in-tile stepping keeps the zero-host-transfer contract: the
    ghost values are gathered and consumed inside the compiled superstep,
    never materialized through the host."""
    cfg = {**BASE, "kernel_backend": "pallas"}
    sim = AMRLBM(LidDrivenCavityConfig(nranks=1, stepping_mode="fused", **cfg))
    sim.advance(1)
    res = sim.arena.device()
    before = (res.h2d_transfers, res.d2h_transfers)
    sim.advance(3)
    assert (res.h2d_transfers, res.d2h_transfers) == before


def test_pallas_donated_superstep_consumes_buffers_and_survives_amr(
    pallas_reference,
):
    """The superstep donates its pdf buffers and ping-pongs them in place
    (inputs are deleted after each call); AMR events rebuild the programs
    without ever touching a stale donated buffer, and the physics matches
    the pallas restack reference on the same schedule."""
    cfg = {**BASE, "kernel_backend": "pallas"}
    don = AMRLBM(LidDrivenCavityConfig(nranks=1, stepping_mode="fused", **cfg))

    don.advance(1)
    lvl = min(don.forest.levels_in_use())
    held = don.arena.device().fetch(lvl, "pdf")
    don.advance(1)
    assert held.is_deleted(), "donated superstep must consume its inputs"

    # cross an AMR event: programs rebuild, residency re-uploads — a stale
    # donated buffer anywhere in the engine would raise on next use
    don.adapt()
    assert len(don.forest.levels_in_use()) > 1
    don.advance(PALLAS_INTERVAL)
    don.adapt()  # the schedule of _run_pallas: PALLAS_STEPS, AMR every interval

    assert don.amr_cycles >= 1
    _assert_state_match(don, pallas_reference)
    assert_mass_match(
        don.total_mass(), pallas_reference.total_mass(), what="donated fused"
    )


def _assert_state_match(sim: AMRLBM, reference: AMRLBM) -> None:
    """Interior pdfs, density and velocity of every block to the float32
    conformance tolerance."""
    sim.materialize_host()
    reference.materialize_host()
    _assert_macroscopic_match(sim, reference)
    ref_blocks = {b.bid: b for b in reference.forest.all_blocks()}
    g = sim.spec.ghost
    sl = (Ellipsis,) + (slice(g, -g),) * 3
    for b in sim.forest.all_blocks():
        assert_f32_match(
            b.data["pdf"][sl], ref_blocks[b.bid].data["pdf"][sl],
            what=f"pdf of block {b.bid:#x}",
        )


# Planted faults: each is a real bug's signature, planted in a pallas fused
# run on the reference's schedule. Every one must fail the conformance
# tolerance, so the tolerance is tight enough to see it.
FAULTS = ("cell", "behind", "omega")


def _faulted_fused_run(fault: str, monkeypatch) -> AMRLBM:
    if fault == "omega":  # one level relaxes at a rate off by 1e-4
        real = engines.omega_for_level
        monkeypatch.setattr(
            engines, "omega_for_level",
            lambda omega, level: real(omega, level) + (1e-4 if level == 0 else 0.0),
        )
    cfg = {**BASE, "kernel_backend": "pallas"}
    sim = AMRLBM(LidDrivenCavityConfig(nranks=1, stepping_mode="fused", **cfg))
    for i in range(PALLAS_STEPS):
        if fault == "cell" and i == PALLAS_INTERVAL:
            # one interior cell's populations off by 1e-5, after the first
            # AMR event, so the fault crosses steps and the second event
            sim.materialize_host()
            blk = min(sim.forest.all_blocks(), key=lambda b: b.bid)
            blk.data["pdf"][:, 4, 4, 4] += np.float32(1e-5)
            sim.pdfs_written()
        if fault == "behind" and i == PALLAS_STEPS - 1:
            # one block's pdfs left one coarse step behind
            sim.materialize_host()
            blk = max(sim.forest.all_blocks(), key=lambda b: (b.level, b.bid))
            before = np.array(blk.data["pdf"])
            sim.advance(1)
            sim.materialize_host()
            blk.data["pdf"][...] = before
            sim.pdfs_written()
        else:
            sim.advance(1)
        if (i + 1) % PALLAS_INTERVAL == 0:
            sim.adapt()
    return sim


@pytest.mark.parametrize("fault", FAULTS)
def test_planted_faults_fail_the_conformance_tolerance(
    pallas_reference, fault, monkeypatch
):
    """A fused run across an AMR event with one planted fault no longer
    matches the pallas restack reference under the float32 conformance
    tolerance (``conftest.F32_ATOL``), while the same run without a fault
    does (``test_pallas_fused_modes_match_pallas_restack_reference``)."""
    sim = _faulted_fused_run(fault, monkeypatch)
    assert sim.amr_cycles >= 1, "the run must span at least one AMR event"
    with pytest.raises(AssertionError, match="float32 conformance tolerance"):
        _assert_state_match(sim, pallas_reference)


# -- device-matrix legs --------------------------------------------------------
# device_sharded places each rank's padded block stack on its own XLA device
# (shard_map over a 1-D mesh, in-program ppermute for halo messages). Host
# devices come from XLA_FLAGS=--xla_force_host_platform_device_count=N, which
# must be set before the first jax import — the CI device-matrix job does
# exactly that; under the default single-device environment the wider legs
# skip and only the 1-device leg runs.

import jax  # noqa: E402


def _require_devices(n: int) -> None:
    if jax.device_count() < n:
        pytest.skip(
            f"needs {n} XLA devices (run under "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n})"
        )


@pytest.mark.parametrize("nranks", [1, 2, 4])
def test_device_sharded_matches_single_rank_reference(reference, nranks):
    """The real multi-device data plane is a faithful distributed execution:
    device_sharded at 1/2/4 devices reproduces the single-rank restack
    reference to float32 round-off (the per-rank switch branches run the
    identical exchange arithmetic, only placement differs) after 8 coarse
    steps spanning an AMR event, and mass is conserved."""
    _require_devices(nranks)
    sim = _run("device_sharded", nranks)
    assert sim.amr_cycles >= 1, "the run must span at least one AMR event"
    assert len(sim.forest.levels_in_use()) > 1
    _assert_macroscopic_match(sim, reference)
    assert_mass_match(
        sim.total_mass(), reference.total_mass(), what=sim.cfg.stepping_mode
    )


def test_device_sharded_traffic_is_p2p_with_host_plan_byte_parity():
    """ppermute traffic is p2p-only and byte-identical to the host fabric:
    the in-program permutes account exactly the CompiledRankMessage nbytes
    the fused_sharded mode puts on the simulated Comm for the same
    trajectory, every communicating pair is a process-graph neighbor pair,
    and the round schedule is a partial-permutation cover of the messages
    (zero-padding counted separately as wire overhead, never as traffic)."""
    _require_devices(4)
    from repro.lbm.halo import (
        build_rank_halo_plan,
        compile_rank_halo_plan,
        schedule_ppermute_rounds,
    )

    def traj(mode):
        sim = AMRLBM(
            LidDrivenCavityConfig(nranks=4, stepping_mode=mode, **BASE)
        )
        sim.advance(2)
        sim.adapt()
        assert len(sim.forest.levels_in_use()) > 1
        before = sim.comm.stats.summary()
        sim.advance(2)
        after = sim.comm.stats.summary()
        keys = (
            "p2p_bytes",
            "p2p_messages",
            "allreduce_calls",
            "allgather_calls",
            "collective_bytes_per_rank",
        )
        return sim, {k: after[k] - before[k] for k in keys}

    dev, ddelta = traj("device_sharded")
    _host, hdelta = traj("fused_sharded")
    assert ddelta["allreduce_calls"] == 0
    assert ddelta["allgather_calls"] == 0
    assert ddelta["collective_bytes_per_rank"] == 0
    assert ddelta["p2p_bytes"] > 0
    # byte parity message-for-message with the simulated fabric's accounting
    assert ddelta["p2p_bytes"] == hdelta["p2p_bytes"]
    assert ddelta["p2p_messages"] == hdelta["p2p_messages"]

    # the logical bytes are the host-sharded plan's patch bytes exactly
    arenas = dev.arenas
    rank_slots = {
        r: {l: arenas.per_rank[r].slots(l) for l in arenas.per_rank[r].levels()}
        for r in range(4)
    }
    plan = compile_rank_halo_plan(dev.forest, dev.fields, rank_slots)
    host_plan = build_rank_halo_plan(dev.forest, dev.fields)
    assert plan.cross_rank_bytes() == host_plan.cross_rank_bytes()
    for m in plan.messages:
        assert m.src_rank != m.dst_rank
        assert m.dst_rank in dev.forest.neighbor_ranks(m.src_rank)
        assert m.nbytes == host_plan.nbytes[(m.src_rank, m.dst_rank)]

    # the schedule covers every message once, each round a partial permutation
    rounds = schedule_ppermute_rounds(plan.messages)
    covered = [m for rnd in rounds for m in rnd.messages]
    assert sorted(m.key for m in covered) == sorted(m.key for m in plan.messages)
    for rnd in rounds:
        srcs = [s for s, _ in rnd.perm]
        dsts = [d for _, d in rnd.perm]
        assert len(set(srcs)) == len(srcs), rnd.perm
        assert len(set(dsts)) == len(dsts), rnd.perm
        assert rnd.num_cells == max(m.num_cells for m in rnd.messages)
        assert rnd.pad_cells() == sum(
            rnd.num_cells - m.num_cells for m in rnd.messages
        )
    assert dev.comm.ppermute_rounds > 0
    assert dev.comm.ppermute_pad_bytes >= 0


def test_device_sharded_resizes_across_device_counts():
    """Elastic resize works across device counts: a device_sharded run
    resized 2 -> 4 devices keeps its DeviceComm fabric and continues with
    physics matching the restack reference."""
    _require_devices(4)
    from repro.serving.elastic import resize_ranks

    sim = AMRLBM(
        LidDrivenCavityConfig(nranks=2, stepping_mode="device_sharded", **BASE)
    )
    for i in range(AMR_INTERVAL):
        sim.advance(1)
    sim.adapt()
    report = resize_ranks(sim, 4)
    assert report.new_nranks == 4
    assert hasattr(sim.comm, "ppermute"), "resize must preserve the fabric type"
    for i in range(AMR_INTERVAL):
        sim.advance(1)
    sim.adapt()
    sim.materialize_host()

    ref = _run("restack", 1)
    _assert_macroscopic_match(sim, ref)
