"""The readers of the program's own host spans (``bench/spans.py``): each
``rebind_*`` metric sums the traced window's host spans of one exact name
over the AMR cycles, and reads nothing where the span or the cycles are
missing."""

from __future__ import annotations

import time

import pytest

from bench import harness
from bench.trace import Op, TraceSummary
from conftest import CHECKOUT

READERS = {
    "rebind_flush_s": "residency:flush",
    "rebind_adopt_s": "rebind:adopt",
    "rebind_masks_s": "rebind:masks",
    "rebind_ghosts_s": "rebind:ghosts",
    "rebind_upload_s": "residency:upload",
}


def _record(host, cycles: int, traced: bool = True) -> harness.RunRecord:
    rec = harness.RunRecord(chips=1, lattice_c=[])
    rec.cycles = [{"wall_s": 1.0, "stages": {}} for _ in range(cycles)]
    rec.trace = TraceSummary(window_s=1.0, host=list(host)) if traced else None
    return rec


def _reader(metric: str):
    return harness.load_module(CHECKOUT, "metrics", metric).read


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_sums_its_span_by_exact_name_per_cycle(metric):
    span = READERS[metric]
    host = [Op("bench:stress", 0, 2e9), Op(span, 1e8, 3e8), Op(span, 6e8, 1e8),
            Op(span + "s", 0, 9e9), Op(span.split(":")[1], 0, 7e9),
            Op(f"other:{span.split(':')[1]}", 0, 5e9)]
    assert _reader(metric)(_record(host, cycles=2)) == pytest.approx(0.2)


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_is_none_without_its_span_or_cycles(metric):
    read = _reader(metric)
    span = Op(READERS[metric], 0, 1e9)
    assert read(_record([Op("bench:stress", 0, 1e9)], cycles=2)) is None
    assert read(_record([span], cycles=0)) is None
    assert read(_record([span], cycles=2, traced=False)) is None


def test_traced_stress_window_reports_the_rebind_parts(tiny_root):
    """A traced CPU window of stress cycles at the tiny size: the program's
    spans reach the profiler, each part reads a positive time, and together
    they fit inside the host-clock rebind they split."""
    cell = harness.load_cell(tiny_root, "tiny.amr-stress")
    out = harness.run_cell(cell, seed=2**31 + 29, seconds=1, trace=True,
                           t_start=time.perf_counter())
    assert out["correct"], out["check"]
    parts = {m: out["metrics"][m]["value"] for m in READERS}
    assert all(v > 0 for v in parts.values()), parts
    assert sum(parts.values()) <= out["metrics"]["amr_rebind_s"]["value"]
