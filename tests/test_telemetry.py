"""Telemetry layer: bounded rings, disabled-path cost, span/StageStats
consistency, and Chrome-trace export validity.

The contracts pinned here are the ones the observability layer advertises:

* **Bounded buffers** — per-rank telemetry memory is a construction-time
  bound (capacity x nominal record size), independent of rank count and run
  length; evictions are counted, never silent.
* **Near-zero disabled path** — ``span()`` returns the shared ``NULL_SPAN``
  (no allocation, no clock reads) and ``instant()`` is a no-op, so leaving
  the instrumentation in hot loops costs ~nothing when telemetry is off.
* **Spans are the stats** — the instrumentation feeds the same ``seconds``
  into ``StageStats`` that it records as a span, and
  :func:`~repro.telemetry.export.stage_seconds` accumulates in recording
  order, so the span sums equal the ``data_stats`` / ``CycleReport``
  surfaces *exactly* (float-for-float), and the two can never disagree.
* **On the profiler's clock** — every ``stage()`` reaches a
  ``jax.profiler`` trace's host plane as ``<cat>:<name>``, with telemetry
  off too, while a disabled ``span()`` leaves nothing; an AMR cycle's flush
  and the engine rebind's three parts appear there once each.
* **Valid traces** — a traced 4-rank ``fused_sharded`` run spanning an AMR
  event exports Chrome-trace JSON that ``tools/trace_report.py`` accepts,
  including the per-substep emit/interior/route/absorb phases that make the
  PR 7 overlap visible; the committed example artifact stays valid too.
"""

import json
import sys
import time
from pathlib import Path

import pytest

from repro import telemetry
from repro.lbm.driver import AMRLBM, LidDrivenCavityConfig
from repro.telemetry import (
    NULL_SPAN,
    Counter,
    Histogram,
    MetricsRegistry,
    SECONDS_BUCKETS,
    Tracer,
)
from repro.telemetry.tracer import RECORD_NOMINAL_BYTES

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "tools"))

from trace_report import PHASES, check_trace  # noqa: E402

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


def _cfg(**over) -> LidDrivenCavityConfig:
    return LidDrivenCavityConfig(**{**BASE, **over})


@pytest.fixture(autouse=True)
def _restore_global_tracer():
    """Tests mutate the process-wide tracer; restore the defaults so the
    rest of the suite keeps its zero-overhead disabled path."""
    yield
    telemetry.configure(enabled=False, clock=time.perf_counter)
    telemetry.get_tracer().reset()


# ---------------------------------------------------------------------------
# bounded-buffer contract
# ---------------------------------------------------------------------------


def _fake_clock(step: float = 1.0):
    t = [0.0]

    def clock() -> float:
        t[0] += step
        return t[0]

    return clock


def test_ring_evicts_at_capacity_and_counts():
    tr = Tracer(enabled=True, capacity=8, clock=_fake_clock())
    for i in range(20):
        tr.instant(f"ev{i}", rank=0)
    recs = tr.records(rank=0)
    assert len(recs) == 8  # bounded: oldest 12 gone
    assert [r.name for r in recs] == [f"ev{i}" for i in range(12, 20)]
    stats = tr.buffer_stats()[0]
    assert stats == {"entries": 8, "capacity": 8, "evicted": 12, "total": 20}
    # chronological merge survives wrap-around
    t0s = [r.t0 for r in recs]
    assert t0s == sorted(t0s)


@pytest.mark.parametrize("nranks", [4, 13])
def test_per_rank_memory_bounded_independent_of_rank_count(nranks):
    """The Table-1 discipline for observability: each rank's telemetry
    memory hits the same construction-time bound whether the run has 4
    ranks or 13 — there is no global log anywhere."""
    cap = 16
    tr = Tracer(enabled=True, capacity=cap, clock=_fake_clock())
    for i in range(50 * nranks):  # far past capacity on every rank
        tr.instant("ev", rank=i % nranks)
    held = tr.held_bytes_per_rank()
    assert set(held) == set(range(nranks))
    bound = cap * RECORD_NOMINAL_BYTES
    assert all(b == bound for b in held.values())
    for stats in tr.buffer_stats().values():
        assert stats["entries"] == cap
        assert stats["evicted"] == stats["total"] - cap


def test_metrics_are_bounded():
    # label-set cap: later combinations fold into one overflow series
    c = Counter("c", max_series=2)
    for src in range(5):
        c.inc(10, src=src)
    assert c.total() == 50  # nothing lost, just folded
    assert len(c.series()) == 3  # 2 real + overflow
    assert c.overflowed == 3
    # histogram: fixed layout, correct bucket placement
    h = Histogram("h", buckets=SECONDS_BUCKETS)
    h.observe(5e-7)  # below first bound (1e-6)
    h.observe(0.5)  # -> 1e0 bucket
    h.observe(1e9)  # -> +inf bucket
    (series,) = h.series().values()
    assert series["n"] == 3 and sum(series["counts"]) == 3
    assert series["counts"][0] == 1 and series["counts"][-1] == 1
    # registry cap: past max_metrics, observations drop (counted), never grow
    reg = MetricsRegistry(max_metrics=2)
    reg.counter("a").inc()
    reg.counter("b").inc()
    reg.counter("c").inc()  # dropped
    assert len(reg) == 2 and reg.dropped_metrics == 1
    reg.counter("a").inc()  # existing metrics still reachable when full
    assert reg.counter("a").total() == 2


# ---------------------------------------------------------------------------
# disabled path
# ---------------------------------------------------------------------------


def test_disabled_path_is_null_and_records_nothing():
    tr = Tracer(enabled=False)
    assert tr.span("x") is NULL_SPAN  # shared instance, no allocation
    assert tr.span("y", cat="substep", rank=3) is NULL_SPAN
    with tr.span("x") as sp:
        sp.set(bytes=123)
    tr.instant("ev", rank=2)
    assert tr.records() == [] and tr.buffer_stats() == {}
    # stage() must still time (its .seconds feeds StageStats) but not record
    with tr.stage("halo") as sp:
        pass
    assert sp.seconds >= 0.0 and tr.records() == []


def test_disabled_span_overhead_is_negligible():
    """Pin the cost of leaving instrumentation in hot loops: 100k disabled
    span() round-trips must be far below anything a stepping loop notices,
    and so must 100k stage() round-trips, which time and enter a profiler
    annotation even when disabled (generous wall bounds to stay robust on
    loaded CI hosts; stages sit at layer boundaries, a few per coarse step
    or AMR cycle)."""
    tr = Tracer(enabled=False)
    t0 = time.perf_counter()
    for _ in range(100_000):
        with tr.span("hot", cat="substep", rank=0):
            pass
    assert time.perf_counter() - t0 < 1.0
    t0 = time.perf_counter()
    for _ in range(100_000):
        with tr.stage("hot", cat="stage", rank=0):
            pass
    assert time.perf_counter() - t0 < 2.0
    assert tr.records() == []


# ---------------------------------------------------------------------------
# the profiler's clock
# ---------------------------------------------------------------------------


def _profiled_host_spans(out_dir: Path, fn) -> list[str]:
    """Run ``fn`` under a profiler session of this process and return the
    names of the host plane's events, in order."""
    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(out_dir))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (pb,) = sorted(out_dir.rglob("*.xplane.pb"))[-1:]
    names = []
    for plane in ProfileData.from_file(str(pb)).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                names.extend((ev.start_ns, ev.name) for ev in line.events)
    return [n for _t, n in sorted(names)]


@pytest.mark.parametrize("kind, mirrored", [("stage", 1), ("span", 0)])
def test_disabled_tracer_mirrors_stages_and_not_spans(tmp_path, kind, mirrored):
    """With telemetry off a ``stage()`` still reaches the profiler's host
    plane as ``<cat>:<name>`` (it times anyway); a ``span()`` is the shared
    no-op and leaves no trace. Neither records."""
    tr = Tracer(enabled=False)

    def work():
        with getattr(tr, kind)("probe", cat="probe"):
            time.sleep(1e-3)

    names = _profiled_host_spans(tmp_path, work)
    assert names.count("probe:probe") == mirrored
    assert tr.records() == []


def test_amr_rebind_spans_reach_profiler(tmp_path):
    """One executed AMR cycle of a fused cavity shows the flush before the
    pipeline and the engine rebind's three parts once each on the
    profiler's host plane, with telemetry off; the next coarse step
    uploads the new stacks and compiles the new superstep under spans of
    their own."""
    sim = AMRLBM(_cfg(stepping_mode="fused", nranks=1))
    sim.advance(1)
    reports = []

    def work():
        reports.append(sim.adapt(force_rebalance=True))
        sim.advance(1)

    names = _profiled_host_spans(tmp_path, work)
    assert reports[0].executed
    for name in ("residency:flush", "rebind:adopt", "rebind:masks", "rebind:ghosts",
                 "compile:superstep"):
        assert names.count(name) == 1, (name, names.count(name))
    for stage in ("refine", "proxy", "balance", "migrate"):
        assert names.count(f"amr:{stage}") == 1
    assert names.count("stage:halo") == 1  # nested in rebind:ghosts
    uploads = [i for i, n in enumerate(names) if n == "residency:upload"]
    assert uploads and min(uploads) > names.index("rebind:ghosts")


# ---------------------------------------------------------------------------
# spans == stats
# ---------------------------------------------------------------------------


def test_stage_spans_equal_data_stats_exactly():
    """data_stats["halo"/"step"] and the recorded stage spans come from the
    same Span.seconds values accumulated in the same order — equality is
    exact, not approximate."""
    telemetry.configure(enabled=True, capacity=8192)
    tr = telemetry.get_tracer()
    tr.reset()
    sim = AMRLBM(_cfg(stepping_mode="arena", nranks=2))
    sim.run(4, amr_interval=2)
    sums = telemetry.export.stage_seconds(tr, cat="stage")
    assert sums["halo"] == sim.data_stats["halo"].seconds
    assert sums["step"] == sim.data_stats["step"].seconds


def test_amr_cycle_report_matches_spans_exactly():
    telemetry.configure(enabled=True, capacity=8192)
    tr = telemetry.get_tracer()
    sim = AMRLBM(_cfg(stepping_mode="arena", nranks=2))
    sim.advance(2)
    tr.reset()  # isolate exactly one AMR cycle
    report = sim.adapt(force_rebalance=True)
    assert report.executed
    sums = telemetry.export.stage_seconds(tr, cat="amr")
    for stage in ("refine", "proxy", "balance", "migrate"):
        assert sums[stage] == report.stages[stage].seconds


def test_injectable_clock_threads_through_serving():
    """With a deterministic clock injected, every serving latency is an
    exact whole-tick difference — proof that no instrumentation site fell
    back to time.perf_counter()."""
    from repro.serving import JobSpec, SimulationService

    telemetry.configure(enabled=True, clock=_fake_clock())
    svc = SimulationService()
    jid = svc.submit(
        JobSpec(config=_cfg(stepping_mode="arena"), coarse_steps=2,
                amr_interval=4)
    )
    svc.run()
    job = svc.jobs[jid]
    assert job.status == "done"
    latency = svc.data_stats["serving"]["jobs"][jid]["latency_s"]
    assert latency == job.finished_at - job.submitted_at
    assert latency == int(latency) and latency > 0  # whole fake-clock ticks


# ---------------------------------------------------------------------------
# Chrome-trace export
# ---------------------------------------------------------------------------


def test_fused_sharded_trace_is_valid_and_shows_all_phases(tmp_path):
    """A traced 4-rank fused_sharded run across an AMR event exports a valid
    Chrome trace whose substeps carry distinct emit/interior/route/absorb
    spans (the 6x6x6 grid gives every rank interior blocks at 4 ranks, so
    the overlap split actually engages; see examples/trace_fused_sharded.py).
    """
    telemetry.configure(enabled=True, capacity=8192)
    tr = telemetry.get_tracer()
    tr.reset()
    sim = AMRLBM(
        _cfg(
            root_grid=(6, 6, 6),
            cells_per_block=(4, 4, 4),
            nranks=4,
            stepping_mode="fused_sharded",
        )
    )
    sim.advance(1)
    report = sim.adapt(force_rebalance=True)
    assert report.executed, "the trace must span an AMR event"
    sim.advance(1)

    path = telemetry.export.write_chrome_trace(tmp_path / "t.json")
    trace = json.loads(path.read_text())
    assert check_trace(trace, require_substep_phases=True) == []
    names = {
        ev["name"] for ev in trace["traceEvents"]
        if ev.get("cat") == "substep"
    }
    assert set(PHASES) <= names
    assert any(
        ev["name"] == "amr.event" and ev["ph"] == "i"
        for ev in trace["traceEvents"]
    )
    # counter tracks synthesized from route bytes + compile events
    kinds = {ev["name"] for ev in trace["traceEvents"] if ev["ph"] == "C"}
    assert "substep.bytes" in kinds and "compiles" in kinds
    # per-pair p2p byte counters made it into the embedded metrics
    p2p = trace["metadata"]["metrics"]["comm.p2p_bytes"]["series"]
    assert p2p and all(v > 0 for v in p2p.values())
    # and the artifact itself proves the buffers stayed bounded
    for stats in trace["metadata"]["buffers"].values():
        assert stats["entries"] <= stats["capacity"] == 8192


def test_committed_example_trace_is_valid():
    path = ROOT / "examples" / "traces" / "fused_sharded_4rank.trace.json"
    trace = json.loads(path.read_text())
    assert check_trace(trace, require_substep_phases=True) == []
