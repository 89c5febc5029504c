"""The reduction of a profiler trace to device numbers (``bench/trace.py``)."""

from __future__ import annotations

from pathlib import Path

import pytest

from bench import trace
from bench.trace import Op, TraceSummary

FIXTURE = Path(__file__).with_name("data") / "trace_small.xplane.pb"


def test_union_merges_overlaps_and_keeps_gaps():
    ops = [Op("a", 0, 10), Op("b", 5, 10), Op("c", 30, 5), Op("d", 31, 1)]
    assert trace.union_ns(ops) == 15 + 5
    loop = TraceSummary(window_s=1.0, devices={"tpu": [Op("%while.5", 0, 100),
                                                       Op("%dus.47", 10, 50)]})
    assert loop.op_seconds(lambda o: True) == pytest.approx(100e-9)  # body inside the loop


def test_summary_sums_idle_and_names_gaps_by_host_span():
    dev = [Op("%fusion.1 = fusion()", 0, 100),
           Op('%superstep.3 = custom-call(), custom_call_target="tpu_custom_call"', 100, 300),
           Op("%fusion.2 = fusion()", 1000, 100)]
    host = [Op("bench:advance", 0, 2000), Op("PjitFunction(superstep)", 450, 400)]
    s = TraceSummary(window_s=2e-6, devices={"/device:TPU:0": dev}, host=host)
    assert s.busy_s == pytest.approx(500e-9)
    assert s.op_seconds(lambda o: o.name.startswith("%fusion")) == pytest.approx(200e-9)
    assert s.op_seconds(trace.is_kernel) == pytest.approx(300e-9)
    gaps = s.idle_gaps()
    assert gaps == [["advance/PjitFunction(superstep)", pytest.approx(600e-9)]]
    assert s.top_ops(2)[0][0].startswith("%superstep.3")


def test_recorded_chip_trace_reduces_to_kernel_and_other_ops():
    """Three coarse steps of the two-level fused superstep recorded on a v5e
    (``record_trace.py``): the stream-collide kernel is found, other ops
    (ghost-fill gathers and scatters) take device time too, and the busy
    union lies within the summed op time."""
    s = trace.load(FIXTURE, window_s=1.0)
    assert list(s.devices) == ["/device:TPU:0"]
    kernel = s.op_seconds(trace.is_kernel)
    other = s.op_seconds(lambda o: not trace.is_kernel(o))
    assert kernel > 0 and other > 0
    assert max(kernel, other) < s.busy_s <= kernel + other + 1e-12
    kernels = [o for o in s.devices["/device:TPU:0"] if trace.is_kernel(o)]
    assert len(kernels) % 3 == 0  # the same kernel calls in each of three steps
