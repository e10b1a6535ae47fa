"""Capture a profiler trace of the measured window and reduce it.

The reduction reads the ``.xplane.pb`` with ``jax.profiler.ProfileData``.
Device planes are those named ``/device:TPU:<n>``; on each, the ``XLA Ops``
line holds one event per operation run and the ``XLA Modules`` line one per
program execution.  Host spans are the harness's
``jax.profiler.TraceAnnotation`` events, named ``chipbench.<what>``, on the
host planes; the profiler puts host and device events on one clock.

Programs and kernels are found by content.  An operation event is named by
its HLO instruction (``%quant_matmul.50 = f32[...] custom-call(...)``); a
Pallas kernel is the custom call whose instruction carries the kernel's
``pallas_call`` name.  A program is known by the kernels its execution
holds: every serving step is ``jit(sm)``.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
import shutil

SPAN_PREFIX = "chipbench."
WINDOW_SPAN = "chipbench.window"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_INSTRUCTION = re.compile(r"^%?([A-Za-z_][\w-]*?)(?:\.\d+)?\s*=")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    end_ns: float

    @property
    def dur_ns(self) -> float:
        return self.end_ns - self.start_ns

    def is_kernel(self, kernel: str) -> bool:
        """Whether this operation is a call of the Pallas kernel ``kernel``:
        a custom call whose instruction is named after it."""
        m = _INSTRUCTION.match(self.name)
        return bool(m) and m.group(1) == kernel and "custom-call" in self.name


def merged(intervals) -> list:
    """Sorted, non-overlapping ``[start, end]`` cover of the intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def union_ns(intervals) -> float:
    """Length of the union of ``(start, end)`` intervals."""
    return sum(e - s for s, e in merged(intervals))


class Trace:
    """Device operations, program executions and harness spans of a trace,
    clipped to the measured window."""

    def __init__(self, ops: dict, modules: dict, spans: list):
        self.ops = ops              # device index -> [Event]
        self.modules = modules      # device index -> [Event]
        self.spans = spans          # [Event], host
        win = [s for s in spans if s.name == WINDOW_SPAN]
        if win:
            self.t0, self.t1 = win[0].start_ns, win[0].end_ns
        else:
            evs = [e for v in ops.values() for e in v]
            self.t0 = min((e.start_ns for e in evs), default=0.0)
            self.t1 = max((e.end_ns for e in evs), default=0.0)
        clip = self._clip
        self.ops = {d: clip(v) for d, v in self.ops.items()}
        self.modules = {d: clip(v) for d, v in self.modules.items()}

    def _clip(self, events):
        return [e for e in events if e.end_ns > self.t0 and e.start_ns < self.t1]

    # -- loading -------------------------------------------------------------
    @classmethod
    def from_file(cls, path: str) -> "Trace":
        from jax.profiler import ProfileData

        return cls.from_profile(ProfileData.from_file(path))

    @classmethod
    def from_profile(cls, pd) -> "Trace":
        ops, modules, spans = {}, {}, []
        for plane in pd.planes:
            m = _DEVICE_PLANE.match(plane.name)
            for line in plane.lines:
                if m:
                    dev = int(m.group(1))
                    if line.name == "XLA Ops":
                        ops.setdefault(dev, []).extend(_events(line))
                    elif line.name == "XLA Modules":
                        modules.setdefault(dev, []).extend(_events(line))
                elif plane.name.startswith("/host"):
                    spans.extend(e for e in _events(line)
                                 if e.name.startswith(SPAN_PREFIX))
        return cls(ops, modules, spans)

    # -- reductions ------------------------------------------------------------
    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) / 1e9

    def intervals(self, events) -> list:
        """``(start, end)`` of each event, clipped to the window."""
        return [(max(e.start_ns, self.t0), min(e.end_ns, self.t1))
                for e in events]

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        if not self.ops:
            return 0.0
        per = [union_ns(self.intervals(v)) for v in self.ops.values()]
        return sum(per) / len(per) / 1e9

    def kernel_events(self, kernel: str) -> list:
        return [e for v in self.ops.values() for e in v if e.is_kernel(kernel)]

    def kernel_s(self, kernel: str) -> float:
        return sum(e.dur_ns for e in self.kernel_events(kernel)) / 1e9

    def executions(self, holds: str, lacks: str | None = None) -> list:
        """Program executions whose operations include kernel ``holds`` (and
        none named ``lacks``)."""
        out = []
        for dev, mods in self.modules.items():
            ops = sorted(self.ops.get(dev, []), key=lambda e: e.start_ns)
            starts = [e.start_ns for e in ops]
            for mod in mods:
                lo = bisect.bisect_left(starts, mod.start_ns)
                hi = bisect.bisect_right(starts, mod.end_ns)
                inside = ops[lo:hi]
                if any(e.is_kernel(holds) for e in inside) and not (
                        lacks and any(e.is_kernel(lacks) for e in inside)):
                    out.append(mod)
        return out

    def idle_gaps(self, top: int = 10) -> list:
        """The longest idle gaps of device 0 inside the window, each named by
        the innermost harness span that covers its middle."""
        if not self.ops:
            return []
        dev = min(self.ops)
        busy = merged(self.intervals(self.ops[dev]))
        gaps, prev = [], self.t0
        for s, e in busy:
            if s > prev:
                gaps.append((prev, s))
            prev = max(prev, e)
        if self.t1 > prev:
            gaps.append((prev, self.t1))
        out = []
        for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
            mid = (s + e) / 2
            cover = [sp for sp in self.spans
                     if sp.start_ns <= mid <= sp.end_ns and sp.name != WINDOW_SPAN]
            name = (min(cover, key=lambda sp: sp.dur_ns).name if cover
                    else "outside harness spans")
            out.append([name, (e - s) / 1e9])
        return out

    def top_ops(self, top: int = 10) -> list:
        """Device operations that took most time, grouped by name."""
        tot: dict = {}
        for v in self.ops.values():
            for e in v:
                tot[e.name] = tot.get(e.name, 0.0) + e.dur_ns
        n = max(len(self.ops), 1)
        return [[k, v / n / 1e9] for k, v in
                sorted(tot.items(), key=lambda kv: -kv[1])[:top]]


def _events(line):
    return [Event(e.name, float(e.start_ns),
                  float(e.start_ns) + float(e.duration_ns))
            for e in line.events]


class Capture:
    """``with Capture(dir):`` traces the block into ``dir`` (emptied first);
    ``.xplane`` is the written file afterwards."""

    def __init__(self, directory: str):
        self.dir = directory
        self.xplane = None

    def __enter__(self):
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0    # spans and device events only
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        return self

    def __exit__(self, *exc):
        import jax

        jax.profiler.stop_trace()
        found = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        self.xplane = found[0] if found else None
        return False
