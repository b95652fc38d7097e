"""Reduction of a JAX profiler trace to device metrics.

The profiler writes ``<dir>/plugins/profile/<run>/<host>.xplane.pb``;
``jax.profiler.ProfileData`` reads it. Device planes are named
``/device:TPU:<n>``; on each, the ``XLA Ops`` line holds one event per
operation that ran and the ``XLA Modules`` line one per program
execution. The benchmark's own host spans (``chipbench.wave_*``,
``jax.profiler.TraceAnnotation``) lie on a host plane, on the same
clock, and bound the traced window.

- busy: the union of operation intervals inside the window, per chip,
  averaged over the chips that ran anything;
- per program: the summed durations of module executions whose name
  holds the program's name (``prefill_fn``, ``decode_fn``);
- top operations: self time per operation (less the operations nested
  in it: a loop's own event spans its body), named by its program and its
  HLO name (``jit_decode_fn/%fusion.176``);
- idle gaps: the longest stretches inside the window with no operation
  on a chip, each labeled by the innermost benchmark span around its
  middle.
"""
from __future__ import annotations

import bisect
import glob
import os
import shutil
import tempfile
import time
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "chipbench."
#: program name in the trace -> the name metrics use
PROGRAMS = {"prefill_fn": "prefill", "decode_fn": "decode"}
TOP = 10

Interval = Tuple[int, int]          # [start_ns, end_ns)


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals: Iterable[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def gaps(busy: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


@dataclass
class Summary:
    window_s: float
    busy_s: float
    program_s: Dict[str, float]
    host_window: Tuple[float, float]       # time.monotonic, seconds
    top_ops: List[Tuple[str, float]] = field(default_factory=list)
    idle_gaps: List[Tuple[str, float]] = field(default_factory=list)

    def breakdown(self) -> Dict:
        return {"device_ops": [[n, s] for n, s in self.top_ops],
                "idle_gaps": [[n, s] for n, s in self.idle_gaps]}


def short_op(name: str) -> str:
    """``%fusion.3 = bf16[...] fusion(...), ...`` -> ``%fusion.3``: the
    trace names an operation by its whole HLO text."""
    return name.split(" = ", 1)[0]


def short_module(name: str) -> str:
    """``jit_decode_fn(1656...)`` -> ``jit_decode_fn``."""
    return name.split("(", 1)[0]


def self_times(ops: List[Tuple[int, int, str]]) -> List[Tuple[str, int]]:
    """Each operation's time less the time of the operations nested in
    it (a loop's event spans its body's operations), so that the times
    add up to the busy time and a loop does not count its body twice."""
    out: List[Tuple[str, int]] = []
    stack: List[List] = []                 # [end, name, self time]
    for a, b, name in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and stack[-1][0] <= a:
            end, n, t = stack.pop()
            out.append((n, t))
        if stack:
            parent = stack[-1]
            parent[2] -= min(b, parent[0]) - a
        stack.append([b, name, b - a])
    out += [(n, t) for _, n, t in stack]
    return out


def _label(spans: List[Tuple[int, int, str]], t: int) -> str:
    inner = None
    for a, b, name in spans:
        if a <= t < b and (inner is None or b - a < inner[1] - inner[0]):
            inner = (a, b, name)
    return inner[2][len(SPAN_PREFIX):] if inner else "outside"


def reduce(planes, host_window: Tuple[float, float]) -> Optional[Summary]:
    """``planes``: objects with ``name`` and ``lines``, each line with
    ``name`` and ``events``, each event with ``name``, ``start_ns`` and
    ``duration_ns`` (``ProfileData``'s shape). None where the trace holds
    no benchmark span or no device operation."""
    spans: List[Tuple[int, int, str]] = []
    devices = []
    for plane in planes:
        if plane.name.startswith(DEVICE_PREFIX):
            devices.append(plane)
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    a = int(ev.start_ns)
                    spans.append((a, a + int(ev.duration_ns), ev.name))
    if not spans:
        return None
    lo = min(a for a, _, _ in spans)
    hi = max(b for _, b, _ in spans)
    busy_total, chips = 0, 0
    program_ns: Dict[str, int] = {v: 0 for v in PROGRAMS.values()}
    op_ns: Dict[str, int] = {}
    idle: List[Tuple[int, str]] = []
    for plane in devices:
        lines = {line.name: line for line in plane.lines}
        modules: List[Tuple[int, int, str]] = []
        if MODULES_LINE in lines:
            for ev in lines[MODULES_LINE].events:
                a = int(ev.start_ns)
                if not lo <= a < hi:
                    continue
                modules.append((a, a + int(ev.duration_ns),
                                short_module(ev.name)))
                for key, prog in PROGRAMS.items():
                    if key in ev.name:
                        program_ns[prog] += int(ev.duration_ns)
        modules.sort()
        starts = [m[0] for m in modules]
        named: List[Tuple[int, int, str]] = []
        if OPS_LINE in lines:
            for ev in lines[OPS_LINE].events:
                a = int(ev.start_ns)
                b = a + int(ev.duration_ns)
                if b <= lo or a >= hi:
                    continue
                i = bisect.bisect_right(starts, a) - 1
                mod = modules[i][2] if i >= 0 and a < modules[i][1] \
                    else "?"
                named.append((a, b, f"{mod}/{short_op(ev.name)}"))
        for key, ns in self_times(named):
            op_ns[key] = op_ns.get(key, 0) + ns
        ops = [(a, b) for a, b, _ in named]
        if not ops:
            continue
        chips += 1
        busy = clip(union(ops), lo, hi)
        busy_total += sum(b - a for a, b in busy)
        idle += [(b - a, _label(spans, (a + b) // 2))
                 for a, b in gaps(busy, lo, hi)]
    if not chips:
        return None
    idle.sort(key=lambda g: -g[0])
    return Summary(
        window_s=(hi - lo) * 1e-9,
        busy_s=busy_total / chips * 1e-9,
        program_s={k: v * 1e-9 for k, v in program_ns.items()},
        host_window=host_window,
        top_ops=[(n, ns * 1e-9) for n, ns in
                 sorted(op_ns.items(), key=lambda kv: -kv[1])[:TOP]],
        idle_gaps=[(name, ns * 1e-9) for ns, name in idle[:TOP]])


class Profiler:
    """``jax.profiler`` around the window, into a directory of its own
    under ``TMPDIR``, read and removed by ``summary``."""

    def __init__(self, enabled: bool):
        self.enabled = enabled
        self.dir: Optional[str] = None
        self.host_window = (0.0, 0.0)

    def start(self) -> None:
        if not self.enabled:
            return
        import jax
        self.dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        # no Python tracer (it records every call, slowing the host and
        # filling the trace) and no HLO protos: the device planes and
        # the benchmark's own annotations are what the reduction reads
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.host_window = (time.monotonic(), 0.0)

    def stop(self) -> None:
        if not self.enabled or self.host_window[1]:
            return
        import jax
        self.host_window = (self.host_window[0], time.monotonic())
        jax.profiler.stop_trace()

    def summary(self) -> Optional[Summary]:
        import jax
        try:
            files = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                              recursive=True)
            if not files:
                return None
            data = jax.profiler.ProfileData.from_file(files[0])
            return reduce(data.planes, self.host_window)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
