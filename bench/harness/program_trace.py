"""The program's own spans in a traced run, for the metrics that read them.

The program names its host-side blocks ``ipgm.<layer>.<what>`` (a
``jax.profiler.TraceAnnotation`` each, written on the line of the thread
that ran it, on the device trace's clock). The harness's reduction of the
run's trace (``xplane.Trace``, in the run's records as ``trace``) keeps only
the benchmark's ``bench.`` spans, so this reads the ``ipgm.`` spans of the
same ``.xplane.pb`` once and keeps, beside that reduction's window:

  lines   the ``ipgm.`` spans inside the window, per host thread line, as a
          forest: a span holds the spans that start inside it, each with
          its arguments
  busy    the stretches of the window in which an op ran on the first
          device plane, merged (None when the trace has no device)

A trace that holds no ``ipgm.`` span (a program that writes none) reads as
None, and so do the metrics built on it.
"""
from __future__ import annotations

import dataclasses
from pathlib import Path

from harness import xplane

PREFIX = "ipgm."
# spans in which the serving thread waits rather than works: for arrivals,
# and for the device to finish the step
NOT_HOST = (PREFIX + "serve.hold", PREFIX + "handle.wait")


@dataclasses.dataclass
class Span:
    name: str
    start_ns: float
    dur_ns: float
    args: dict
    children: list["Span"] = dataclasses.field(default_factory=list)

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns

    def walk(self):
        yield self
        for c in self.children:
            yield from c.walk()

    def within(self, names) -> float:
        """ns of this span covered by its outermost descendants named in
        ``names``."""
        return sum(c.dur_ns if c.name in names else c.within(names)
                   for c in self.children)


@dataclasses.dataclass
class ProgramTrace:
    window: tuple[float, float]
    lines: list[list[Span]]                  # root spans per thread line
    busy: list[tuple[float, float]] | None   # ns, merged, in start order

    def spans(self, name: str) -> list[Span]:
        """Every span named ``name``, at any depth, in start order."""
        out = [s for roots in self.lines for r in roots for s in r.walk()
               if s.name == name]
        return sorted(out, key=lambda s: s.start_ns)

    def line_of(self, name: str) -> list[Span] | None:
        """The thread line with the most spans named ``name``."""
        counts = [sum(s.name == name for r in roots for s in r.walk())
                  for roots in self.lines]
        if not counts or max(counts) == 0:
            return None
        return self.lines[counts.index(max(counts))]

    def idle_ns(self) -> float:
        t0, t1 = self.window
        return t1 - t0 - sum(b - a for a, b in self.busy or ())

    def idle_by_span(self, roots: list[Span]) -> dict[tuple[str, str],
                                                      float]:
        """Device-idle ns of the window by what ``roots``' thread was in:
        (outermost span, innermost open span); ("outside", "outside") where
        no span was open."""
        segs: list[tuple[float, float, str, str]] = []
        for r in roots:
            _own_time(r, r.name, segs)
        segs.sort()
        busy = self.busy or []
        out: dict[tuple[str, str], float] = {}
        i = 0
        for a, b, inner, root in segs:
            # busy stretches are disjoint and sorted, and so are the
            # segments of one thread: one pass over both
            while i < len(busy) and busy[i][1] <= a:
                i += 1
            covered, j = 0.0, i
            while j < len(busy) and busy[j][0] < b:
                covered += min(b, busy[j][1]) - max(a, busy[j][0])
                j += 1
            if b - a > covered:
                key = (root, inner)
                out[key] = out.get(key, 0.0) + b - a - covered
        rest = self.idle_ns() - sum(out.values())
        if rest > 0:
            out[("outside", "outside")] = rest
        return out


def _own_time(span: Span, root: str, out: list) -> None:
    """Append the stretches of ``span`` that none of its children cover,
    as (start, end, span name, root name)."""
    cur = span.start_ns
    for c in span.children:
        if c.start_ns > cur:
            out.append((cur, c.start_ns, span.name, root))
        _own_time(c, root, out)
        cur = max(cur, c.end_ns)
    if span.end_ns > cur:
        out.append((cur, span.end_ns, span.name, root))


def nest(events: list[Span]) -> list[Span]:
    """The roots of a thread line's spans, each holding the spans that
    start inside it."""
    roots: list[Span] = []
    stack: list[Span] = []
    for e in sorted(events, key=lambda e: (e.start_ns, -e.dur_ns)):
        while stack and e.start_ns >= stack[-1].end_ns:
            stack.pop()
        (stack[-1].children if stack else roots).append(e)
        stack.append(e)
    return roots


def merged(events: list[xplane.Event], t0: float, t1: float):
    """The union of ``events`` clipped to [t0, t1], as disjoint stretches
    in start order."""
    out: list[list[float]] = []
    for a, b in sorted((max(e.start_ns, t0), min(e.end_ns, t1))
                       for e in events if e.end_ns > t0 and e.start_ns < t1):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def host_lines(path: str, window: tuple[float, float]) -> list[list[Span]]:
    """The ``ipgm.`` spans that start inside ``window``, nested, one forest
    per host thread line that has any."""
    from jax.profiler import ProfileData

    t0, t1 = window
    lines = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans = [Span(e.name, e.start_ns, e.duration_ns, dict(e.stats))
                     for e in line.events
                     if e.name.startswith(PREFIX) and t0 <= e.start_ns < t1]
            if spans:
                lines.append(nest(spans))
    return lines


def read(path: str, tr: xplane.Trace) -> ProgramTrace | None:
    """The program spans of the trace at ``path``, whose harness reduction
    is ``tr``."""
    lines = host_lines(path, tr.window)
    if not lines:
        return None
    busy = None
    if tr.devices:
        ops = tr.devices[sorted(tr.devices)[0]].get("XLA Ops", [])
        busy = merged(ops, *tr.window)
    return ProgramTrace(tr.window, lines, busy)


def of_run(r: dict, bench_dir: Path) -> ProgramTrace | None:
    """The program spans of a traced run, read from the trace the harness
    left under ``bench_dir``/out/trace once and kept in the run's records
    for the other metrics; None in an untraced run."""
    if "program_trace" not in r:
        tr = r.get("trace")
        r["program_trace"] = None if tr is None else read(
            xplane.find(str(bench_dir / "out" / "trace")), tr)
    return r["program_trace"]


def op_calls(pt: ProgramTrace, ops=("insert", "delete")) -> list[Span]:
    """The session's ``ops`` calls (``ipgm.session.<op>``)."""
    return [c for op in ops for c in pt.spans(f"{PREFIX}session.{op}")]


def op_steps(pt: ProgramTrace, ops=("insert", "delete")) -> list[Span]:
    """The ``ipgm.session.step`` spans of the session's ``ops`` calls."""
    return [s for call in op_calls(pt, ops) for s in call.walk()
            if s.name == f"{PREFIX}session.step"]
