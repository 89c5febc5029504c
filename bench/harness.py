"""One run of one benchmark cell: set-up, measured window, traced metrics and
the check against the plain reference.

Everything that belongs to one cell is found by name, so that a new cell,
traffic mix, window op, check, scenario or metric is a new file and an
entry, with no edit to this one:

* ``BENCHMARK.json`` names the cell, its configuration and traffic mix, and
  the metrics it reports;
* ``bench/configs/<config>.json`` holds the configuration as it is run; its
  ``scenario`` names ``bench/scenarios/<scenario>.py``, which builds the
  program's solver from it and reads and writes the solver's state;
* ``bench/traffic/<traffic>.json`` holds the traffic mix as data: the set-up
  ops that fix the topology, the seed's perturbation, the warm-up units, the
  ops of one window unit, the ops that close the traced window, and the
  check with its limits;
* ``bench/ops/<op>.py`` drives one op with ``run(sim) -> dict``: a step
  returns ``{"coarse_steps": n}``, an AMR cycle ``{"stages": {name: s}}``;
* ``bench/checks/<kind>.py`` collects what the check compares from the
  program (``collect``) and reduces it against the plain reference
  (``reduce``);
* ``bench/metrics/<metric>.py`` reads one metric, end-to-end or per-layer,
  from a :class:`RunRecord` with ``read(record) -> float | None``.
"""

from __future__ import annotations

import gc
import importlib.util
import json
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parent
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list
    root: Path


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: Path, workload: str) -> Cell:
    """Find a cell's configuration, traffic and metrics by name under ``root``
    (the directory holding ``BENCHMARK.json``)."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in {root / 'BENCHMARK.json'}")
    w = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    traffic_file = root / "bench" / "traffic" / f"{w['traffic']}.json"
    return Cell(
        name=workload,
        chips=int(w["chips"]),
        config=json.loads((root / conf["file"]).read_text()),
        traffic=json.loads(traffic_file.read_text()),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)],
        root=root,
    )


def load_module(root: Path, kind: str, name: str):
    """``bench/<kind>/<name>.py`` under ``root``, imported once per path."""
    path = root / "bench" / kind / f"{name}.py"
    key = f"bench_{kind}_{name}_{abs(hash(str(path)))}".replace(".", "_").replace("-", "_")
    if key in sys.modules:
        return sys.modules[key]
    spec = importlib.util.spec_from_file_location(key, path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(f"no {kind[:-1]} {name!r}: {path} is missing")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[key] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclass
class RunRecord:
    """What a run measured; metric readers take their number from it."""

    chips: int
    lattice_c: list
    setup_s: float = 0.0
    setup_compile_s: float = 0.0
    window_s: float = 0.0
    coarse_steps: int = 0
    updates: int = 0
    # one entry per AMR op of the window: {"wall_s", "stages": {name: s}}
    cycles: list = field(default_factory=list)
    step_s: list = field(default_factory=list)  # wall time of each coarse step
    trace: object = None  # bench.trace.TraceSummary of the window, --trace 1
    peaks: dict = field(default_factory=dict)
    memory_peak_bytes: int = 0


def program_path() -> None:
    src = str(CHECKOUT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


class Run:
    """A cell's solver with the modules that drive and read it."""

    def __init__(self, cell: Cell):
        self.cell = cell
        self.cfg, self.traffic = cell.config, cell.traffic
        self.scenario = load_module(cell.root, "scenarios", self.cfg["scenario"])
        names = {*self.traffic["setup"], *self.traffic["unit"], *self.traffic.get("close", ())}
        self.ops = {n: load_module(cell.root, "ops", n).run for n in names}
        self.check = load_module(cell.root, "checks", self.traffic["check"]["kind"])
        self.sim = None
        self.upd = 0  # interior cell updates of one coarse step of the topology

    def prepare(self) -> dict:
        """Build the solver and run the set-up ops, which fix the topology;
        returns a copy of its interior state."""
        program_path()
        self.sim = self.scenario.build(self.cfg)
        for op in self.traffic["setup"]:
            self.ops[op](self.sim)
        self.upd = self.scenario.block_updates(self.sim)
        return self.scenario.interiors(self.sim)

    def start(self, base: dict, seed: int):
        """Put the seed's perturbation of ``base`` into the solver and run the
        warm-up units. Returns the perturbed state and the topologies so far."""
        scen = self.scenario
        state = scen.perturbed(base, self.cfg, seed, float(self.traffic["perturbation"]))
        scen.write_interiors(self.sim, state)
        topologies = [scen.topology(self.sim)]
        rec = RunRecord(chips=self.cell.chips, lattice_c=self.cfg["lattice"]["c"])
        for _ in range(int(self.traffic["warm_units"])):
            self.unit(rec, topologies)
        self.close()  # compiles what closes the traced window
        return state, topologies

    def unit(self, rec: RunRecord, topologies: list) -> None:
        """Run the traffic's unit once, recording its work into ``rec``."""
        from bench import trace as trace_mod

        scen, sim = self.scenario, self.sim
        for op in self.traffic["unit"]:
            t_op = time.perf_counter()
            with trace_mod.annotate(op):
                did = self.ops[op](sim)
            wall = time.perf_counter() - t_op
            if "coarse_steps" in did:
                rec.coarse_steps += did["coarse_steps"]
                rec.updates += self.upd * did["coarse_steps"]
                rec.step_s.append(wall)
            if "stages" in did:
                rec.cycles.append({"wall_s": wall, "stages": did["stages"]})
                topologies.append(scen.topology(sim))
                self.upd = scen.block_updates(sim)

    def window(self, rec: RunRecord, topologies: list, *, seconds: float | None = None,
               units: int | None = None) -> None:
        """Repeat the unit until ``seconds`` have passed (whole units only) or
        ``units`` units ran."""
        done = 0
        t0 = time.perf_counter()
        while True:
            self.unit(rec, topologies)
            done += 1
            rec.window_s = time.perf_counter() - t0
            if (seconds is not None and rec.window_s >= seconds) or done == units:
                return

    def close(self) -> None:
        """The ops that end the traced window, outside its timing."""
        from bench import trace as trace_mod

        for op in self.traffic.get("close", ()):
            with trace_mod.annotate(op):
                self.ops[op](self.sim)

    def collect(self, start_state: dict, topologies: list):
        return self.check.collect(self, self.traffic["check"], start_state, topologies)

    def free(self) -> None:
        """Drop the program's device state before the reference runs."""
        import jax

        self.sim = None
        gc.collect()
        jax.clear_caches()


def _device_info(chips: int) -> dict:
    import jax

    devs = jax.devices()[:chips]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}


def _memory_peak(chips: int) -> int:
    """Peak device memory of the fullest chip: its buffers' peak plus the
    peak it reserved for the programs' temporaries, which the TPU runtime
    keeps apart from the buffers (``peak_bytes_in_use`` leaves them out)."""
    import jax

    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0))
                     + int(stats.get("peak_bytes_reserved", 0)))
    return max(peaks)


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool, t_start: float,
             log=print, faults=None, control: bool = False) -> dict:
    """Run one cell and return the result object (without printing it).

    ``t_start`` is the process's start on ``time.perf_counter``'s clock.
    ``faults`` (tests only) is called as ``faults(sim)`` once the topology
    is fixed, before the warm-up, and may break the program underneath the
    timed path; ``control`` (tests only) puts the reference in bfloat16 in
    the program's place for the check."""
    import jax

    from bench import peaks as peaks_mod
    from bench import trace as trace_mod

    device = _device_info(cell.chips)
    rec = RunRecord(chips=cell.chips, lattice_c=cell.config["lattice"]["c"])
    if device["platform"] == "tpu":
        rec.peaks = peaks_mod.lookup(device["kind"])

    compiles = {"s": 0.0, "n": 0}

    def on_event(event: str, duration_secs: float, **_kw) -> None:
        if event == COMPILE_EVENT:
            compiles["s"] += duration_secs
            compiles["n"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        run = Run(cell)
        base = run.prepare()
        if faults is not None:
            faults(run.sim)
        start_state, topologies = run.start(base, seed)
        del base
        jax.effects_barrier()
        gc.collect()  # set-up's garbage is set-up's cost, not the window's
        rec.setup_s = time.perf_counter() - t_start
        rec.setup_compile_s = compiles["s"]
        log(f"[{cell.name}] set-up {rec.setup_s:.3f} s (backend compile "
            f"{compiles['s']:.3f} s in {compiles['n']} programs), "
            f"{len(topologies[-1])} blocks")

        n_compiles = compiles["n"]
        tracer = trace_mod.Tracer(cell.root / "bench_out" / "trace") if trace else None
        if tracer is not None:
            tracer.start()
        run.window(rec, topologies, seconds=seconds)
        if tracer is not None:
            run.close()
            rec.trace = tracer.stop(rec.window_s)
        if compiles["n"] > n_compiles:
            raise RuntimeError(f"{compiles['n'] - n_compiles} programs compiled inside the window")
        rec.memory_peak_bytes = _memory_peak(cell.chips)
        _log_window(cell, rec, log)

        data = run.collect(start_state, topologies)
        run.free()
        numbers = run.check.reduce(data, cell.config, control=control, log=log)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)

    limits = cell.traffic["check"]["limits"]
    compared = {k: {"value": float(numbers[k]), "limit": float(limits[k])} for k in limits}
    correct = all(np.isfinite(v["value"]) and v["value"] <= v["limit"] for v in compared.values())

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = load_module(cell.root, "metrics", m["name"]).read(rec)
        if v is None and not trace:
            raise RuntimeError(f"end-to-end metric {m['name']!r} found nothing to read")
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    if trace and rec.trace is not None:
        device["busy_s"] = rec.trace.busy_s
        device["window_s"] = rec.trace.window_s
    device["memory_peak_bytes"] = rec.memory_peak_bytes
    attempted = rec.coarse_steps + len(rec.cycles)
    out = {
        "correct": bool(correct),
        "attempted": attempted,
        "failed": 0 if correct else attempted,
        "metrics": metrics,
        "device": device,
    }
    if trace and rec.trace is not None:
        out["breakdown"] = rec.trace.breakdown()
    out["check"] = compared
    return out


def _log_window(cell: Cell, rec: RunRecord, log) -> None:
    log(f"[{cell.name}] window {rec.window_s:.4f} s: {rec.coarse_steps} coarse steps, "
        f"{len(rec.cycles)} AMR cycles, {rec.updates} cell updates")
    for i, c in enumerate(rec.cycles):
        log(f"  cycle {i}: {c['wall_s']:.4f} s, stages "
            + " ".join(f"{k} {v:.4f}" for k, v in c["stages"].items()))
    if rec.step_s:
        med = float(np.median(rec.step_s))
        slow = [t for t in rec.step_s if t > 1.25 * med]
        log(f"  coarse step: median {med:.5f} s, min {min(rec.step_s):.5f}, max "
            f"{max(rec.step_s):.5f}; {len(slow)} over 1.25x median, "
            f"{sum(slow) - med * len(slow):.4f} s above it")
