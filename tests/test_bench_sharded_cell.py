"""CPU rehearsal of the benchmark's four-chip cell (``bench/``): the
``cavity_sharded`` scenario on the ``device_sharded`` engine at four ranks,
its planted fault, and the halo-exchange metric readers.

The rehearsal runs the cell's configuration cut to the tiny root of
``bench/tests/conftest.py`` (2x2x2 roots of 8^3 cells) through
``bench.harness.run_cell`` on four XLA host devices, which must be
provisioned before JAX starts: this file runs itself as a script in a child
process with ``XLA_FLAGS=--xla_force_host_platform_device_count=4``.

The planted fault is a host write of the seed's state that the solver is not
told of (``pdfs_written`` silenced) while its device copy is current. The
steady check reads its starting state back from the program, so it cannot
see a write that never landed; the fault is judged by the same comparison
(``bench/checks/stepping.py::reduce``: the plain reference against the
program, two coarse steps) taken from the state the scenario wrote.
"""

from __future__ import annotations

import json
import subprocess
import sys
import tempfile
import time
from pathlib import Path

import pytest

CHECKOUT = Path(__file__).resolve().parents[1]
if str(CHECKOUT) not in sys.path:
    sys.path.insert(0, str(CHECKOUT))

from bench import harness, trace  # noqa: E402
from conftest import four_cpu_devices_env  # noqa: E402
from bench.trace import Op, TraceSummary  # noqa: E402

CELL = "tiny-x4.steady"
SEED = 2**31 + 29


def _tiny_root(tmp: Path) -> Path:
    """The tiny benchmark root plus the four-chip configuration at its size,
    with a cell that reports what the real cell reports."""
    from bench.tests.conftest import TINY, make_root

    root = make_root(tmp / "root")
    cfg = json.loads((CHECKOUT / "bench/configs/cavity16-r2048-x4.json").read_text())
    cfg.update(name="tiny-x4", **TINY)
    (root / "bench/configs/tiny-x4.json").write_text(json.dumps(cfg))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append(dict(bench["configs"][0], name="tiny-x4",
                                 file="bench/configs/tiny-x4.json"))
    bench["workloads"].append({"name": CELL, "config": "tiny-x4", "traffic": "steady",
                               "chips": 4, "why": "test size"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "tiny.steady" in m.get("workloads", ()):
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def _written_state_check(run, seed: int, *, announce: bool) -> dict:
    """Write the seed's state while the device copy is current, announced
    or not, take two coarse steps and compare them with the reference's
    two from the written state."""
    sim, scen = run.sim, run.scenario
    sim.advance(1)  # the device copy of the pdfs is now current
    if not announce:
        sim.pdfs_written = lambda: None
    written = scen.perturbed(scen.interiors(sim), run.cfg, seed,
                             float(run.traffic["perturbation"]))
    scen.write_interiors(sim, written)
    if not announce:
        del sim.pdfs_written
    for _ in range(2):
        run.ops["advance"](sim)
    data = {"before": written, "after": scen.interiors(sim),
            "bids": scen.topology(sim), "steps": 2}
    return run.check.reduce(data, run.cfg, log=lambda _m: None)


def rehearse() -> dict:
    """Run in the four-device child: the cell through the harness, then the
    written-state comparison with and without the announcement."""
    import jax

    assert jax.device_count() == 4, jax.devices()
    with tempfile.TemporaryDirectory() as tmp:
        cell = harness.load_cell(_tiny_root(Path(tmp)), CELL)
        out = harness.run_cell(cell, seed=SEED, seconds=1, trace=False,
                               t_start=time.perf_counter(), log=lambda _m: None)
        run = harness.Run(cell)
        run.prepare()
        layout = run.sim.engine.slot_layout()
        levels = [b.level for b in run.sim.forest.all_blocks()]
        silent = _written_state_check(run, SEED + 1, announce=False)
        announced = _written_state_check(run, SEED + 2, announce=True)
    return {"run": out, "silent": silent, "announced": announced,
            "limits": cell.traffic["check"]["limits"], "layout": layout,
            "blocks": {l: levels.count(l) for l in set(levels)}}


@pytest.fixture(scope="module")
def rehearsal() -> dict:
    proc = subprocess.run([sys.executable, __file__], env=four_cpu_devices_env(),
                          capture_output=True, text=True, timeout=300, cwd=CHECKOUT)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_four_rank_cell_runs_through_the_harness_and_is_correct(rehearsal):
    out = rehearsal["run"]
    assert out["correct"], out["check"]
    assert out["device"]["count"] == 4
    assert set(out["metrics"]) == {"mlups", "setup_s"}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert out["attempted"] > 0 and out["failed"] == 0


def test_unannounced_host_write_fails_the_comparison(rehearsal):
    limits = rehearsal["limits"]
    assert any(rehearsal["silent"][k] > limits[k] for k in limits), rehearsal["silent"]


def test_announced_host_write_passes_the_comparison(rehearsal):
    limits = rehearsal["limits"]
    assert all(rehearsal["announced"][k] <= limits[k] for k in limits), rehearsal["announced"]


def test_slot_layout_holds_every_block_once_in_equal_rank_stacks(rehearsal):
    layout, blocks = rehearsal["layout"], rehearsal["blocks"]
    assert sorted(layout) == sorted(blocks)
    for level, lay in layout.items():
        assert sum(lay["blocks"]) == blocks[level]
        assert lay["slots"] == 4 * max(lay["blocks"])
        assert lay["pad_share"] == pytest.approx(1 - blocks[level] / lay["slots"])


def test_scenario_refuses_a_solver_without_the_hook(monkeypatch):
    """The parent program (no ``pdfs_written``) fails at build, before any
    set-up work."""
    harness.program_path()
    from repro.lbm import AMRLBM

    scen = harness.load_module(CHECKOUT, "scenarios", "cavity_sharded")
    cfg = json.loads((CHECKOUT / "bench/configs/cavity16-r2048-x4.json").read_text())
    monkeypatch.delattr(AMRLBM, "pdfs_written")
    t = time.perf_counter()
    with pytest.raises(RuntimeError, match="pdfs_written"):
        scen.build(cfg)
    assert time.perf_counter() - t < 1.0


# -- the halo-exchange readers on a fixture trace ------------------------------

PERM_START = ("%collective-permute-start.1 = (f32[4,8]{1,0}, f32[4,8]{1,0}, u32[], u32[]) "
              "collective-permute-start(f32[4,8]{1,0} %fusion.3), channel_id=1, "
              "source_target_pairs={{0,1},{1,0}}")
PERM_DONE = ("%collective-permute-done.1 = f32[4,8]{1,0} collective-permute-done("
             "(f32[4,8]{1,0}, f32[4,8]{1,0}, u32[], u32[]) %collective-permute-start.1)")
PERM_SYNC = ("%collective-permute.2 = f32[4,8]{1,0:T(8,128)} collective-permute("
             "f32[4,8]{1,0:T(8,128)} %fusion.4), channel_id=2, source_target_pairs={{0,1}}")
READER = ("%fusion.7 = f32[4,8]{1,0} fusion(f32[4,8]{1,0} %collective-permute-done.1, "
          "f32[4,8]{1,0} %p.2), kind=kLoop, calls=%fused_computation.7")
KERNEL = ('%lbm_collide.4 = f32[4,19,18,18,18]{4,3,2,1,0} custom-call(f32[4,8]{1,0} '
          '%collective-permute-done.1), custom_call_target="tpu_custom_call"')
BRANCH = ("%conditional.3 = (f32[4,8]{1,0}) conditional(s32[] %select.1, "
          "f32[4,8]{1,0} %collective-permute-done.1), branch_computations={%b0, %b1}")


def _record(devices: dict) -> harness.RunRecord:
    rec = harness.RunRecord(chips=len(devices), lattice_c=[[0, 0, 0]], coarse_steps=2)
    rec.trace = TraceSummary(window_s=1e-5, devices=devices)
    return rec


def _metric(name: str):
    return harness.load_module(CHECKOUT, "metrics", name)


def test_collective_ms_counts_the_permute_instructions_only():
    chip0 = [Op(PERM_START, 100, 10), Op(PERM_DONE, 300, 50), Op(READER, 350, 100),
             Op(KERNEL, 600, 300), Op(PERM_SYNC, 1000, 20), Op(BRANCH, 290, 170)]
    chip1 = [Op(PERM_DONE, 300, 30), Op(READER, 330, 100)]
    rec = _record({"/device:TPU:0": chip0, "/device:TPU:1": chip1})
    # (10 + 50 + 20 ns on chip 0, 30 ns on chip 1) / 2 chips / 2 coarse steps
    assert _metric("collective_ms").read(rec) == pytest.approx(1e3 * (80 + 30) / 2 / 2 / 1e9)
    cm = _metric("collective_ms")
    assert [cm.is_permute(o) for o in chip0] == [True, True, False, False, True, False]
    assert not trace.is_kernel(Op(PERM_DONE, 0, 1))


def test_collective_exposed_ms_leaves_out_what_other_ops_cover():
    hider = "%fusion.8 = f32[4,8]{1,0} fusion(f32[4,8]{1,0} %p.1), kind=kLoop, calls=%f8"
    # the done op [300, 350) is half covered by fusion.8 [320, 345); the
    # conditional around it is control flow, not another op
    chip0 = [Op(PERM_START, 100, 10), Op(PERM_DONE, 300, 50), Op(hider, 320, 25),
             Op(BRANCH, 290, 170), Op(READER, 350, 100)]
    rec = _record({"/device:TPU:0": chip0})
    exposed = _metric("collective_exposed_ms").read(rec)
    assert exposed == pytest.approx(1e3 * (10 + 25) / 2 / 1e9)
    assert exposed < _metric("collective_ms").read(rec)


def test_collective_readers_find_nothing_without_a_trace():
    rec = harness.RunRecord(chips=4, lattice_c=[[0, 0, 0]], coarse_steps=2)
    assert _metric("collective_ms").read(rec) is None
    assert _metric("collective_exposed_ms").read(rec) is None
    rec.trace = TraceSummary(window_s=1.0, devices={"/device:TPU:0": [Op(KERNEL, 0, 10)]})
    assert _metric("collective_ms").read(rec) == 0.0


if __name__ == "__main__":
    print(json.dumps(rehearse()))
