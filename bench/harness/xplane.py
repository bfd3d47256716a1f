"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

The trace holds device planes (``/device:TPU:<n>``) whose ``XLA Ops`` line
has one event per executed operation and whose ``XLA Modules`` line has one
event per executed program, and host planes whose lines carry the
benchmark's own ``jax.profiler.TraceAnnotation`` spans (names starting with
``bench.``) on the same clock. The traced window is the benchmark's
``bench.window`` span.

  busy        union of the device's op intervals inside the window
  modules     program executions by name, in start order
  ops         op events, each named by its HLO text
  gaps        stretches inside the window where no op ran on a device,
              named by the innermost ``bench.`` span open at their middle
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

import numpy as np

WINDOW = "bench.window"


@dataclasses.dataclass
class Event:
    name: str           # on device lines, the op's HLO text
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Trace:
    window: tuple[float, float]          # ns, the bench.window span
    devices: dict[str, dict[str, list[Event]]]  # plane -> line -> events
    spans: list[Event]                   # host bench.* spans

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def line(self, name: str) -> list[Event]:
        """Events of one line over all device planes, clipped to the
        window, in start order."""
        t0, t1 = self.window
        out = [e for lines in self.devices.values()
               for e in lines.get(name, ()) if e.end_ns > t0 and e.start_ns < t1]
        return sorted(out, key=lambda e: e.start_ns)

    def ops(self) -> list[Event]:
        return self.line("XLA Ops")

    def modules(self, contains: str = "") -> list[Event]:
        return [e for e in self.line("XLA Modules") if contains in e.name]

    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the device planes."""
        t0, t1 = self.window
        total = 0.0
        for lines in self.devices.values():
            iv = [(max(e.start_ns, t0), min(e.end_ns, t1))
                  for e in lines.get("XLA Ops", ())]
            total += _union([(a, b) for a, b in iv if b > a])
        return total * 1e-9 / max(len(self.devices), 1)

    def top_ops(self, n: int = 10) -> list[list]:
        """The ``n`` ops with the most device time, by instruction and
        opcode; loops and conditionals, which hold other ops, are left
        out."""
        acc: dict[str, float] = {}
        for e in self.ops():
            name = op_name(e.name)
            if name.split()[-1] in CONTAINERS:
                continue
            acc[name] = acc.get(name, 0.0) + e.dur_ns * 1e-9
        return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])
                [:n]]

    def gaps(self, n: int = 10) -> list[list]:
        """The ``n`` longest idle stretches of the first device plane."""
        t0, t1 = self.window
        if not self.devices:
            return []
        plane = sorted(self.devices)[0]
        iv = sorted((max(e.start_ns, t0), min(e.end_ns, t1))
                    for e in self.devices[plane].get("XLA Ops", ())
                    if e.end_ns > t0 and e.start_ns < t1)
        out, cur = [], t0
        for a, b in iv:
            if a > cur:
                out.append((cur, a))
            cur = max(cur, b)
        if t1 > cur:
            out.append((cur, t1))
        out.sort(key=lambda g: g[0] - g[1])
        return [[self.span_at((a + b) / 2), (b - a) * 1e-9]
                for a, b in out[:n]]

    def span_at(self, t: float) -> str:
        """Innermost host ``bench.`` span open at ``t`` (the window itself
        when nothing narrower is)."""
        best = None
        for s in self.spans:
            if s.start_ns <= t <= s.end_ns and (
                    best is None or s.dur_ns < best.dur_ns):
                best = s
        return best.name if best is not None else "outside"


STEP = "apply_ops_step"
CONTAINERS = ("while", "conditional", "call")
_OPCODE = re.compile(r"[\]})] ([a-z][\w-]*)\(")


def op_name(hlo: str) -> str:
    """``%fusion.3 = f32[8]{0} fusion(...)`` -> ``fusion.3 fusion``."""
    instr, _, rest = hlo.partition(" = ")
    m = _OPCODE.search(rest)
    return f"{instr.lstrip('%')} {m.group(1)}" if m else instr.lstrip("%")


def op_steps(trace: Trace, dispatched: list[str]) -> dict[str, list[float]]:
    """Device seconds of each op-step execution, keyed by the op the
    benchmark dispatched: the steps run in dispatch order on one stream, so
    the i-th ``apply_ops_step`` program in the trace is the i-th dispatch.
    Raises when the counts differ; empty when the trace has no device."""
    if not trace.devices:
        return {}
    mods = trace.modules(STEP)
    if len(mods) != len(dispatched):
        raise ValueError(f"{len(mods)} {STEP} executions in the trace, "
                         f"{len(dispatched)} dispatched in its window")
    out: dict[str, list[float]] = {}
    for op, e in zip(dispatched, mods):
        out.setdefault(op, []).append(e.dur_ns * 1e-9)
    return out


def _union(iv: list[tuple[float, float]]) -> float:
    total, end = 0.0, -np.inf
    for a, b in sorted(iv):
        if a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total


def find(trace_dir: str) -> str:
    """The newest ``.xplane.pb`` under a ``jax.profiler`` trace directory."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def read(path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: dict[str, dict[str, list[Event]]] = {}
    spans: list[Event] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = devices.setdefault(plane.name, {})
            for line in plane.lines:
                if line.name in ("XLA Ops", "XLA Modules"):
                    lines[line.name] = [
                        Event(e.name, e.start_ns, e.duration_ns)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(Event(e.name, e.start_ns, e.duration_ns)
                             for e in line.events
                             if e.name.startswith("bench."))
    win = [s for s in spans if s.name == WINDOW]
    if len(win) != 1:
        raise ValueError(f"expected one {WINDOW} span, found {len(win)}")
    return Trace((win[0].start_ns, win[0].end_ns), devices, spans)
