"""``bench/run.py`` refuses a machine without the cell's TPU chips: it exits
non-zero and prints no result line."""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[2]


def test_cpu_device_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cell = json.loads((CHECKOUT / "BENCHMARK.json").read_text())["workloads"][0]["name"]
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", cell,
         "--seed", str(2**31 + 9), "--seconds", "1", "--trace", "0"],
        cwd=CHECKOUT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert "metrics" not in proc.stdout and "{" not in proc.stdout
    assert "TPU" in proc.stderr
