"""CPU rehearsal of each traffic mix at a tiny size (interpret-mode Pallas):
the harness finds a configuration, a traffic mix, its ops, check and
scenario and the metric readers by name alone, runs set-up, a two-second
window and the check, and the plain reference agrees with the program."""

from __future__ import annotations

import time

import pytest

from bench import harness
from conftest import CELLS


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_cell_runs_and_is_correct(tiny_root, workload):
    cell = harness.load_cell(tiny_root, workload)
    assert cell.config["name"] == "tiny"
    out = harness.run_cell(cell, seed=2**31 + 17, seconds=2, trace=False,
                           t_start=time.perf_counter())
    assert out["correct"], out["check"]
    assert list(out)[-1] == "check"
    want = {m["name"] for m in cell.end_to_end}
    assert set(out["metrics"]) == want and "setup_s" in want
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert out["attempted"] > 0 and out["failed"] == 0


def test_new_metric_reader_is_found_by_name(tiny_root):
    """A per-layer metric added as a file and an entry needs no code edit."""
    import json

    (tiny_root / "bench" / "metrics" / "window_units.py").write_text(
        "def read(rec):\n    return rec.coarse_steps + len(rec.cycles)\n")
    bench = json.loads((tiny_root / "BENCHMARK.json").read_text())
    bench["per_layer"].append({"name": "window_units", "unit": "1", "better": "higher",
                               "source": "program_counter", "layer": "AMR pipeline",
                               "moves": "amr_cycle_s", "workloads": ["tiny.amr-stress"]})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = harness.load_cell(tiny_root, "tiny.amr-stress")
    out = harness.run_cell(cell, seed=3, seconds=1, trace=True, t_start=time.perf_counter())
    assert out["correct"], out["check"]
    assert out["metrics"]["window_units"]["value"] == out["attempted"]
    assert {"amr_pipeline_s", "amr_rebind_s", "compile_s"} <= set(out["metrics"])


def test_new_traffic_op_check_and_scenario_are_found_by_name(tiny_root):
    """A traffic mix, a window op, a check and a scenario added as files,
    with a cell and a configuration entry, need no code edit."""
    import json
    import shutil

    bench_dir = tiny_root / "bench"
    (bench_dir / "ops" / "two_steps.py").write_text(
        "def run(sim):\n    sim.advance(2)\n    return {'coarse_steps': 2}\n")
    traffic = json.loads((bench_dir / "traffic" / "steady.json").read_text())
    traffic["unit"] = ["two_steps"]
    traffic["check"]["kind"] = "steps_again"
    shutil.copy(bench_dir / "checks" / "stepping.py", bench_dir / "checks" / "steps_again.py")
    (bench_dir / "traffic" / "pairs.json").write_text(json.dumps(traffic))
    shutil.copy(bench_dir / "scenarios" / "cavity.py", bench_dir / "scenarios" / "box.py")
    cfg = json.loads((bench_dir / "configs" / "tiny.json").read_text())
    cfg.update(name="tiny-box", scenario="box")
    (bench_dir / "configs" / "tiny-box.json").write_text(json.dumps(cfg))
    bench = json.loads((tiny_root / "BENCHMARK.json").read_text())
    bench["configs"].append(dict(bench["configs"][0], name="tiny-box",
                                 file="bench/configs/tiny-box.json"))
    bench["workloads"].append({"name": "tiny-box.pairs", "config": "tiny-box",
                               "traffic": "pairs", "chips": 1, "why": "test size"})
    for m in bench["end_to_end"]:
        if "tiny.steady" in m.get("workloads", ()):
            m["workloads"].append("tiny-box.pairs")
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = harness.load_cell(tiny_root, "tiny-box.pairs")
    out = harness.run_cell(cell, seed=2**31 + 23, seconds=1, trace=False,
                           t_start=time.perf_counter())
    assert out["correct"], out["check"]
    assert out["attempted"] > 0 and out["attempted"] % 2 == 0
    assert {"mlups", "setup_s"} <= set(out["metrics"])
