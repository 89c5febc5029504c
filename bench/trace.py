"""Reduction of a profiler trace of the measured window to device numbers.

The window is traced with ``jax.profiler`` (Python tracer off, host
TraceMe annotations on). The ``.xplane.pb`` it writes holds one plane per
device (``/device:TPU:<i>``), whose ``XLA Ops`` line has one event per
executed HLO op (its name is the whole HLO instruction), and a host plane
(``/host:CPU``) with one line per thread, which carries the harness's own
annotations of the window's ops and the runtime's spans. Event times are
nanoseconds from the start of the trace on one clock.

The stream-collide kernel is the one Pallas (Mosaic) custom call of the
superstep: an op whose instruction targets ``tpu_custom_call``, or whose
name says ``stream_collide``.
"""

from __future__ import annotations

import re
import shutil
from dataclasses import dataclass, field
from pathlib import Path

OP_LINE = "XLA Ops"
WINDOW_OP = "bench:"  # prefix of the harness's annotations of its ops
NAME_CHARS = 160  # op names are whole HLO instructions; the head names them
HOST_PLANE = "/host:CPU"
_KERNEL_NAME = re.compile(r"stream_collide|tpu_custom_call", re.IGNORECASE)


@dataclass(frozen=True)
class Op:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def is_kernel(op: Op) -> bool:
    """The stream-collide Pallas kernel."""
    return bool(_KERNEL_NAME.search(op.name))


def union_ns(ops) -> float:
    """Length of the union of the ops' intervals."""
    total = 0.0
    end = -float("inf")
    for op in sorted(ops, key=lambda o: o.start_ns):
        if op.start_ns >= end:
            total += op.dur_ns
            end = op.end_ns
        elif op.end_ns > end:
            total += op.end_ns - end
            end = op.end_ns
    return total


@dataclass
class TraceSummary:
    """Device ops per chip and host annotations of one traced window."""

    window_s: float
    devices: dict = field(default_factory=dict)  # plane name -> [Op]
    host: list = field(default_factory=list)  # [Op] of every host thread

    def _mean(self, fn) -> float:
        if not self.devices:
            return 0.0
        return sum(fn(ops) for ops in self.devices.values()) / len(self.devices)

    @property
    def busy_s(self) -> float:
        """Seconds in which some op ran, averaged over the chips."""
        return self._mean(union_ns) / 1e9

    def op_seconds(self, pred) -> float:
        """Seconds in which an op that ``pred`` selects ran, averaged over the
        chips: the union of their intervals, since an op such as a ``while``
        loop is traced around the ops of its body."""
        return self._mean(lambda ops: union_ns([o for o in ops if pred(o)])) / 1e9

    def top_ops(self, k: int = 10) -> list:
        tot: dict[str, float] = {}
        for ops in self.devices.values():
            for o in ops:
                tot[o.name] = tot.get(o.name, 0.0) + o.dur_ns
        n = max(1, len(self.devices))
        best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [[name[:NAME_CHARS], ns / n / 1e9] for name, ns in best]

    def idle_gaps(self, k: int = 10) -> list:
        """The longest gaps between device ops on the first chip, each named
        by the harness's op and the innermost host span that cover the gap's
        middle."""
        if not self.devices:
            return []
        ops = sorted(next(iter(self.devices.values())), key=lambda o: o.start_ns)
        gaps = []
        end = None
        for o in ops:
            if end is not None and o.start_ns > end:
                gaps.append((end, o.start_ns))
            end = o.end_ns if end is None else max(end, o.end_ns)
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for a, b in gaps[:k]:
            mid = 0.5 * (a + b)
            covering = [h for h in self.host if h.start_ns <= mid <= h.end_ns]
            window_op = [h.name[len(WINDOW_OP):] for h in covering
                         if h.name.startswith(WINDOW_OP)]
            inner = min(covering, key=lambda h: h.dur_ns).name if covering else "host"
            inner = inner.removeprefix(WINDOW_OP)
            out.append(["/".join(window_op[:1] + [inner])[:NAME_CHARS], (b - a) / 1e9])
        return out

    def breakdown(self) -> dict:
        return {"device_ops": self.top_ops(), "idle_gaps": self.idle_gaps()}


def load(path: str | Path, window_s: float) -> TraceSummary:
    """Read an ``.xplane.pb`` into a :class:`TraceSummary`."""
    from jax.profiler import ProfileData

    prof = ProfileData.from_file(str(path))
    out = TraceSummary(window_s=window_s)
    for plane in prof.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            for line in plane.lines:
                if line.name == OP_LINE:
                    out.devices[plane.name] = [
                        Op(ev.name, float(ev.start_ns), float(ev.duration_ns))
                        for ev in line.events
                    ]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                out.host.extend(
                    Op(ev.name, float(ev.start_ns), float(ev.duration_ns))
                    for ev in line.events if ev.duration_ns > 0
                )
    return out


def annotate(op: str):
    """A host span on the profiler's clock around one of the harness's ops
    (costs nothing when nothing traces)."""
    import jax

    return jax.profiler.TraceAnnotation(WINDOW_OP + op)


class Tracer:
    """Traces the measured window into ``out_dir`` and reduces it."""

    def __init__(self, out_dir: Path):
        self.out_dir = Path(out_dir)

    def start(self) -> None:
        import jax

        shutil.rmtree(self.out_dir, ignore_errors=True)
        self.out_dir.mkdir(parents=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(self.out_dir), profiler_options=opts)

    def stop(self, window_s: float) -> TraceSummary:
        """Stop tracing; ``window_s`` is the window's length on the host clock."""
        import jax

        jax.profiler.stop_trace()
        (pb,) = sorted(self.out_dir.rglob("*.xplane.pb"))[-1:]
        return load(pb, window_s)
