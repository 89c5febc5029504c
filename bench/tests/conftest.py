"""Shared fixtures of the benchmark's own tests (CPU, interpret mode).

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q

``tiny_root`` builds, in a temporary directory, a benchmark root the harness
has never seen: a ``BENCHMARK.json`` whose cells run a configuration file
written there (the cavity at 2x2x2 roots of 8^3 cells), with the
repository's traffic mixes, ops, checks, scenarios and metric readers
copied beside it. The harness finds all of it by name, with no edit to its
code.
"""

from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
CHECKOUT = BENCH.parent
if str(CHECKOUT) not in sys.path:
    sys.path.insert(0, str(CHECKOUT))

TINY = {"cells_per_block": [8, 8, 8], "root_grid": [2, 2, 2]}
CELLS = {"tiny.steady": "steady", "tiny.amr-stress": "amr-stress"}


def make_root(root: Path) -> Path:
    (root / "bench" / "configs").mkdir(parents=True)
    for sub in ("traffic", "metrics", "ops", "checks", "scenarios"):
        shutil.copytree(BENCH / sub, root / "bench" / sub)
    cfg = json.loads((BENCH / "configs" / "cavity16-r64.json").read_text())
    cfg.update(name="tiny", **TINY)
    (root / "bench" / "configs" / "tiny.json").write_text(json.dumps(cfg))
    bench = json.loads((CHECKOUT / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "tiny", "source": cfg["source"],
                         "file": "bench/configs/tiny.json", "reduced": ["root_grid"],
                         "why": "test size"}]
    bench["workloads"] = [{"name": n, "config": "tiny", "traffic": t, "chips": 1,
                           "why": "test size"} for n, t in CELLS.items()]
    traffic_of = {w["name"]: w["traffic"] for w in json.loads(
        (CHECKOUT / "BENCHMARK.json").read_text())["workloads"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            kinds = {traffic_of[w] for w in m["workloads"]}
            m["workloads"] = [n for n, t in CELLS.items() if t in kinds]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.fixture
def tiny_root(tmp_path):
    return make_root(tmp_path / "root")
