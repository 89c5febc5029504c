"""Record the small profiler trace that ``test_trace.py`` reads.

    python bench/tests/record_trace.py OUT_DIR

Runs the fused two-level cavity superstep (Pallas kernel, 2x2x2 roots of
16^3 cells, one criterion AMR event) for three traced coarse steps on the
chip, copies the ``.xplane.pb`` to ``OUT_DIR`` (``bench/tests/data`` for the test),
and prints every device plane's lines with their busiest event names, so
the names the reduction in ``bench/trace.py`` keys on can be read by hand.
Needs a TPU.
"""

from __future__ import annotations

import os
import shutil
import sys
import tempfile
from collections import defaultdict
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main() -> None:
    out_dir = Path(sys.argv[1])
    out_dir.mkdir(parents=True, exist_ok=True)
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    if jax.devices()[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX found {jax.devices()[0].platform}")
    from repro.lbm import AMRLBM, LidDrivenCavityConfig

    cfg = LidDrivenCavityConfig(
        root_grid=(2, 2, 2), cells_per_block=(16, 16, 16), nranks=1,
        omega=1.5, u_lid=(0.08, 0.0, 0.0), max_level=1, refine_upper=0.03,
        refine_lower=0.004, stepping_mode="fused", kernel_backend="pallas",
    )
    sim = AMRLBM(cfg)
    sim.advance(2)
    sim.adapt()
    sim.advance(1)
    levels = sorted({b.level for b in sim.forest.all_blocks()})
    print("blocks per level:", {l: sum(b.level == l for b in sim.forest.all_blocks())
                                for l in levels})
    tmp = Path(tempfile.mkdtemp(dir=out_dir))
    jax.profiler.start_trace(str(tmp))
    sim.advance(3)
    jax.profiler.stop_trace()
    (pb,) = tmp.rglob("*.xplane.pb")
    dest = out_dir / "trace_small.xplane.pb"
    shutil.copy(pb, dest)
    shutil.rmtree(tmp)
    print(f"wrote {dest} ({os.path.getsize(dest)} bytes)")

    from jax.profiler import ProfileData

    prof = ProfileData.from_file(str(dest))
    for plane in prof.planes:
        lines = list(plane.lines)
        print(f"plane {plane.name!r}: {len(lines)} lines")
        for line in lines:
            tot = defaultdict(float)
            count = defaultdict(int)
            stats_seen = {}
            for ev in line.events:
                tot[ev.name] += ev.duration_ns
                count[ev.name] += 1
                if ev.name not in stats_seen:
                    stats_seen[ev.name] = [(k, str(v)[:80]) for k, v in ev.stats]
            print(f"  line {line.name!r}: {sum(count.values())} events")
            for name, ns in sorted(tot.items(), key=lambda kv: -kv[1])[:12]:
                print(f"    {ns / 1e6:10.3f} ms x{count[name]:4d} {name[:100]!r} "
                      f"stats={stats_seen[name][:6]}")


if __name__ == "__main__":
    main()
