"""Serving subsystem: ensemble conformance, divergence splits, elastic
resize, and the submit/poll/stream job driver.

Conformance discipline matches tests/test_distributed_conformance.py: the
batched ensemble path must reproduce independent single-run references
(fused device superstep) across an AMR event, and an elastic resize mid-run
must continue a fixed-rank reference, both to the float32 conformance
tolerance (``conftest.F32_ATOL``).
"""

import numpy as np
import pytest

from conftest import assert_f32_match
from repro.core.checkpoint import load_checkpoint
from repro.lbm.driver import AMRLBM, LidDrivenCavityConfig
from repro.serving import JobSpec, SimulationService, is_batchable, resize_ranks

BASE = dict(
    root_grid=(2, 2, 2),
    cells_per_block=(8, 8, 8),
    omega=1.5,
    u_lid=(0.08, 0.0, 0.0),
    max_level=1,
    refine_upper=0.03,
    refine_lower=0.004,
    kernel_backend="ref",
)
COARSE_STEPS = 8
AMR_INTERVAL = 4

# four members with different physics; the last (omega=1.9, slow lid) never
# refines, so the batch hits a real divergence split at the AMR event
MEMBERS = [
    dict(omega=1.5, u_lid=(0.08, 0.0, 0.0)),
    dict(omega=1.7, u_lid=(0.06, 0.0, 0.0)),
    dict(omega=1.6, u_lid=(0.08, 0.02, 0.0)),
    dict(omega=1.9, u_lid=(0.05, 0.0, 0.0)),
]


def _cfg(**over) -> LidDrivenCavityConfig:
    return LidDrivenCavityConfig(**{**BASE, **over})


def _assert_same_fields(sim: AMRLBM, ref: AMRLBM, *, bitwise: bool = False) -> None:
    # pdf comparison covers the interior (physical) cells, matching the
    # distributed-conformance discipline: the ghost ring is scratch state,
    # overwritten by the next substep's fill before anything reads it.
    # ``bitwise`` is for two runs of one program on one input (determinism);
    # every other pair is held to the float32 conformance tolerance
    sim.materialize_host()
    ref.materialize_host()
    key = lambda f: sorted((b.bid, b.level) for b in f.all_blocks())
    assert key(sim.forest) == key(ref.forest), "topologies diverged"
    ref_blocks = {b.bid: b for b in ref.forest.all_blocks()}
    core = (slice(None), slice(1, -1), slice(1, -1), slice(1, -1))
    for b in sim.forest.all_blocks():
        rb = ref_blocks[b.bid]
        # masks are set from the topology, never stepped: exact
        np.testing.assert_array_equal(b.data["mask"], rb.data["mask"])
        p, q = b.data["pdf"][core], rb.data["pdf"][core]
        if bitwise:
            np.testing.assert_array_equal(p, q)
        else:
            assert_f32_match(p, q, what=f"pdf of block {b.bid:#x}")


def test_ensemble_matches_independent_references_across_amr():
    """>=4 batched members with different tau / lid velocities match solo
    fused references to float32 round-off across an AMR event, with at most
    one compile per (topology, activity-pattern) key for the whole batch."""
    refs = []
    for over in MEMBERS:
        ref = AMRLBM(_cfg(stepping_mode="fused", **over))
        ref.run(COARSE_STEPS, amr_interval=AMR_INTERVAL)
        refs.append(ref)

    svc = SimulationService()
    ids = [
        svc.submit(
            JobSpec(
                config=_cfg(stepping_mode="arena", **over),
                coarse_steps=COARSE_STEPS,
                amr_interval=AMR_INTERVAL,
            )
        )
        for over in MEMBERS
    ]
    svc.run()

    amr_happened = False
    for jid, ref in zip(ids, refs):
        job = svc.jobs[jid]
        assert job.status == "done" and job.step == COARSE_STEPS
        _assert_same_fields(job.sim, ref)
        amr_happened = amr_happened or job.sim.amr_cycles > 0
    assert amr_happened, "the run must cross an AMR event"

    s = svc.summary()
    assert s["jobs_completed"] == len(MEMBERS)
    assert s["ensembles_formed"] == 1
    # omega=1.9 never refines -> one real divergence split at the AMR event
    assert s["divergence_splits"] >= 1
    # compile-amortization contract: one program build per distinct
    # (topology, activity-pattern-set) key for the whole batch — here the
    # uniform level-0 forest plus the refined post-AMR forest — and the
    # post-split groups re-hit the cache instead of recompiling per member
    assert s["compile_misses"] <= 2
    assert s["compile_hits"] >= 1
    # per-job latency/throughput counters are exposed in data_stats["serving"]
    stats = svc.data_stats["serving"]
    for jid in ids:
        rec = stats["jobs"][jid]
        assert rec["status"] == "done"
        assert rec["steps_per_s"] > 0 and rec["latency_s"] > 0
    assert stats["stage"].seconds > 0
    assert stats["compile"]["misses"] == s["compile_misses"]


@pytest.mark.parametrize("nranks", [(4, 2), (2, 6)])
def test_elastic_resize_preserves_physics(nranks):
    """Resize mid-run (shrink 4->2 and grow 2->6) continues the fixed-rank
    reference to float32 round-off: the codec round-trip is exact, but the
    resized run steps differently sized per-rank stacks."""
    n0, n1 = nranks
    ref = AMRLBM(_cfg(nranks=n0, stepping_mode="sharded"))
    ref.run(COARSE_STEPS, amr_interval=AMR_INTERVAL)

    sim = AMRLBM(_cfg(nranks=n0, stepping_mode="sharded"))
    sim.run(AMR_INTERVAL, amr_interval=AMR_INTERVAL)
    report = resize_ranks(sim, n1)
    assert report.old_nranks == n0 and report.new_nranks == n1
    assert sim.cfg.nranks == n1 and sim.comm.nranks == n1
    owners = {b.owner for b in sim.forest.all_blocks()}
    assert owners <= set(range(n1))
    sim.run(COARSE_STEPS - AMR_INTERVAL, amr_interval=AMR_INTERVAL)

    _assert_same_fields(sim, ref)


def test_elastic_resize_via_disk_checkpoint(tmp_path):
    """The durable variant routes the same protocol through the on-disk
    checkpoint files and matches the fixed-rank reference the same way."""
    ref = AMRLBM(_cfg(nranks=2, stepping_mode="arena"))
    ref.run(6, amr_interval=AMR_INTERVAL)

    sim = AMRLBM(_cfg(nranks=2, stepping_mode="arena"))
    sim.run(4, amr_interval=AMR_INTERVAL)
    report = resize_ranks(sim, 3, checkpoint_dir=tmp_path / "ckpt")
    assert report.via_disk
    sim.run(2, amr_interval=AMR_INTERVAL)
    _assert_same_fields(sim, ref)


def test_service_stream_poll_and_checkpoints(tmp_path):
    """The job driver streams diagnostics + registry-codec checkpoints in
    order and reports completion through poll()."""
    svc = SimulationService(checkpoint_root=tmp_path)
    jid = svc.submit(
        JobSpec(
            config=_cfg(stepping_mode="arena"),
            coarse_steps=COARSE_STEPS,
            amr_interval=AMR_INTERVAL,
            checkpoint_every=4,
        )
    )
    events = list(svc.stream(jid))
    kinds = [e["type"] for e in events]
    assert kinds[-1] == "done"
    assert "diagnostics" in kinds and "checkpoint" in kinds
    diag_steps = [e["step"] for e in events if e["type"] == "diagnostics"]
    assert diag_steps == sorted(diag_steps)
    # mass is conserved along the stream (closed box + moving lid)
    masses = [e["mass"] for e in events if e["type"] == "diagnostics"]
    np.testing.assert_allclose(masses, masses[0], rtol=1e-5)

    job = svc.jobs[jid]
    assert job.checkpoints, "checkpoint_every=4 must have streamed checkpoints"
    restored = load_checkpoint(job.checkpoints[-1], job.sim.registry, 2)
    assert len(list(restored.all_blocks())) == len(
        list(job.sim.forest.all_blocks())
    )

    polled = svc.poll(jid)
    assert polled["status"] == "done"
    assert polled["step"] == COARSE_STEPS
    assert polled["checkpoints"] == len(job.checkpoints)


def test_service_runs_unbatchable_jobs_solo_and_resizes():
    """Non-batchable configs (sharded data plane) run solo through their own
    engine; the service can elastically resize them mid-run."""
    cfg = _cfg(nranks=4, stepping_mode="sharded")
    assert not is_batchable(cfg)
    svc = SimulationService()
    jid = svc.submit(JobSpec(config=cfg, coarse_steps=6, amr_interval=AMR_INTERVAL))
    svc.run_round()  # advances the solo job by one amr_interval chunk
    assert svc.jobs[jid].step == AMR_INTERVAL
    report = svc.resize(jid, 2)
    assert report.new_nranks == 2
    svc.run()
    assert svc.jobs[jid].status == "done"
    assert svc.counters["solo_steps"] == 6
    assert any(e["type"] == "resize" for e in svc.jobs[jid].events)


def test_pallas_solo_job_matches_fused_reference_bitwise():
    """A ``kernel_backend="pallas"`` job is unbatchable (the ensemble program
    is built from the ref coefficient kernel) and must run solo through its
    own fused engine — submit/poll/stream all work, and the final state is
    bitwise-identical to an independent fused run of the same config (one
    program, one input: determinism, not conformance)."""
    over = dict(stepping_mode="fused", kernel_backend="pallas")
    cfg = _cfg(**over)
    assert not is_batchable(cfg)

    steps, interval = 4, 2  # crosses one AMR event; interpret mode is slow
    ref = AMRLBM(_cfg(**over))
    ref.run(steps, amr_interval=interval)

    svc = SimulationService()
    jid = svc.submit(JobSpec(config=cfg, coarse_steps=steps, amr_interval=interval))
    assert svc.poll(jid)["status"] == "pending"

    events = list(svc.stream(jid))  # drives rounds from the consumer loop
    kinds = [e["type"] for e in events]
    assert kinds[-1] == "done" and "diagnostics" in kinds

    job = svc.jobs[jid]
    assert job.status == "done" and job.step == steps
    assert job.sim.amr_cycles >= 1, "the run must cross an AMR event"
    _assert_same_fields(job.sim, ref, bitwise=True)

    s = svc.summary()
    assert s["solo_steps"] == steps and s["ensembles_formed"] == 0
    assert s["compile_misses"] == 0, "solo jobs must not touch the batch cache"
    polled = svc.poll(jid)
    assert polled["status"] == "done" and polled["step"] == steps
