"""The program's own host spans (``repro.*``) on the trace's clock.

The program names its spans ``repro.<layer>.<what>`` (``repro.obs``): inside
GBD, the simulated round and ``Session.serve``.  :class:`Spans` holds them
beside a reduced :class:`chipbench.trace.Trace` and answers what the
per-layer readers ask: the summed seconds of a span, its self time less a
child span, the device-idle time under it, and idle gaps named by the
innermost covering span, harness or program.

Two sources give the spans.  :func:`from_profile` reads them from a
profiler trace's host planes, matching a name up to any ``#k=v`` suffix
that TraceMe metadata adds.  :func:`of` reads them, for a reader, from the
program's log in this process (``repro.obs.log``, kept while a trace is
taken), because the harness's reduced trace keeps only ``chipbench.*``
spans.  It puts them on the trace's clock by pairing a harness span that
wraps a call into the program with the program span that call opens (the
median start offset over all pairs), and returns ``None`` for a program that
logs no spans.  The readers of program spans depend on that log until
``chipbench/trace.py`` keeps the ``repro.*`` events itself; then
:func:`of`, :func:`aligned` and :data:`ANCHORS` go, with
``chipbench/span_breakdown.py``.
"""

from __future__ import annotations

import dataclasses
import statistics

from chipbench.trace import Event, Trace, merged, union_ns

PROGRAM_PREFIX = "repro."
#: (harness span, program span opened by the same call): the harness wraps
#: each decode-step call that ``repro.serve.step`` spans, and each
#: ``FLSimulation.run_round`` call, whose body is ``repro.sim.round``.
ANCHORS = (("chipbench.decode", "repro.serve.step"),
           ("chipbench.round", "repro.sim.round"))


@dataclasses.dataclass(frozen=True)
class Span(Event):
    args: dict = dataclasses.field(default_factory=dict, compare=False)


def base_name(name: str) -> tuple:
    """``(name, args)`` of a TraceMe name that may end in ``#k=v,...#``."""
    name, _, meta = name.partition("#")
    args = {}
    for kv in meta.strip("#").split(","):
        k, eq, v = kv.partition("=")
        if eq:
            args[k] = v
    return name, args


def from_profile(pd) -> list:
    """The ``repro.*`` spans on the host planes of a ``ProfileData``."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for e in line.events:
                name, args = base_name(e.name)
                if not name.startswith(PROGRAM_PREFIX):
                    continue
                args.update(dict(e.stats))
                start = float(e.start_ns)
                out.append(Span(name, start, start + float(e.duration_ns),
                                args))
    return out


def aligned(trace: Trace, records, harness: str, program: str):
    """Log records ``(name, start_ns, end_ns, args)`` as :class:`Span` on
    ``trace``'s clock, shifted so that the last ``program`` spans start with
    the last ``harness`` spans of the trace; ``None`` without a pair."""
    hs = sorted(s.start_ns for s in trace.spans if s.name == harness)
    ps = sorted(r[1] for r in records if r[0] == program)
    n = min(len(hs), len(ps))
    if n == 0:
        return None
    shift = statistics.median(h - p for h, p in zip(hs[-n:], ps[-n:]))
    return [Span(name, s + shift, e + shift, dict(args))
            for name, s, e, args in records]


def of(ctx):
    """The program's spans in a reader's traced window, or ``None``."""
    if ctx.trace is None:
        return None
    try:
        from repro import obs
    except ImportError:            # a program that logs no spans
        return None
    records = list(obs.log)
    for harness, program in ANCHORS:
        spans = aligned(ctx.trace, records, harness, program)
        if spans is not None:
            return Spans(ctx.trace, spans)
    return None


class Spans:
    """Program spans clipped to a trace's window, with the harness's."""

    def __init__(self, trace: Trace, program: list):
        self.trace = trace
        self.program = [s for s in program
                        if s.end_ns > trace.t0 and s.start_ns < trace.t1]

    def named(self, name: str, arg: str | None = None) -> list:
        """The ``name`` spans, harness or program; only the program spans
        that carry ``arg`` where it is given."""
        if arg is not None:
            return [s for s in self.program if s.name == name and arg in s.args]
        return [s for s in self.program + self.trace.spans if s.name == name]

    def count(self, name: str) -> int:
        return len(self.named(name))

    def span_s(self, name: str) -> float:
        """Summed seconds of the ``name`` spans inside the window."""
        return sum(e - s for s, e in
                   self.trace.intervals(self.named(name))) / 1e9

    def self_s(self, name: str, child: str) -> float:
        """Seconds of the ``name`` spans not covered by a ``child`` span."""
        kids = self.trace.intervals(self.named(child))
        out = 0.0
        for s, e in self.trace.intervals(self.named(name)):
            inside = [(max(a, s), min(b, e)) for a, b in kids
                      if b > s and a < e]
            out += (e - s) - union_ns(inside)
        return out / 1e9

    def idle_under(self, name: str, arg: str | None = None) -> float:
        """Seconds inside the ``name`` spans (those that carry ``arg``, where
        it is given) in which no operation ran on the device, averaged over
        the devices."""
        cover = merged(self.trace.intervals(self.named(name, arg)))
        total = sum(e - s for s, e in cover)
        if not self.trace.ops:
            return total / 1e9
        idle = [total - _overlap(cover, merged(self.trace.intervals(v)))
                for v in self.trace.ops.values()]
        return sum(idle) / len(idle) / 1e9

    def idle_gaps(self, top: int = 10) -> list:
        """Device 0's longest idle gaps, each named by the innermost harness
        or program span that covers its middle."""
        both = Trace(self.trace.ops, {}, self.trace.spans + self.program)
        return both.idle_gaps(top)

    def totals(self) -> dict:
        """Summed seconds and count of each program span name."""
        names = sorted({s.name for s in self.program})
        return {n: [self.span_s(n), self.count(n)] for n in names}


def _overlap(a: list, b: list) -> float:
    """Length of the intersection of two merged interval lists."""
    i = j = 0
    out = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out

