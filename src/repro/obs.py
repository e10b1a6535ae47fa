"""Host spans of the program, written into the profiler's trace.

``span(name, **args)`` is a ``jax.profiler.TraceAnnotation``: the profiler
session is the only switch, and with no trace being taken a span costs
about a microsecond.  Spans are named ``repro.<layer>.<what>``.  Their args
carry the identifiers that tie a round's or a request's spans together;
:meth:`Span.set` adds counts that are known only when the span ends.  No
span goes inside a loop that runs more than once per round, decode step,
admission wave or GBD iteration: counts inside such loops are plain
integers on the result objects (``PrimalSolution.sweeps``).

While a trace is being taken, each span that ends is also appended to
:data:`log` as ``(name, start_ns, end_ns, args)`` on
``time.perf_counter_ns``, read inside the span's interval in the trace, so
that code in the same process can read the spans without the trace file.
The log belongs to the process, as the profiler session does; it keeps the
last :data:`LOG_LIMIT` spans.  It is a stopgap for the benchmark's
per-layer readers, whose reduced trace keeps no ``repro.*`` event: once
that trace keeps them, the log goes.
"""

from __future__ import annotations

import collections
import time

import jax

LOG_LIMIT = 1 << 16
log: collections.deque = collections.deque(maxlen=LOG_LIMIT)


class Span(jax.profiler.TraceAnnotation):
    """A ``TraceAnnotation`` that is also logged while a trace is taken."""

    def __init__(self, name: str, **args):
        super().__init__(name, **args)
        self.name, self.args = name, args
        self._t0 = None

    def __enter__(self):
        on = self.is_enabled()
        out = super().__enter__()
        # read inside the TraceMe's own interval, so that the logged span
        # leaves out the time the profiler takes to open and close it
        self._t0 = time.perf_counter_ns() if on else None
        return out

    def set(self, **args) -> None:
        """Add args known only at the span's end, such as counts."""
        self.args.update(args)
        self.set_metadata(**args)

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns() if self._t0 is not None else None
        out = super().__exit__(*exc)
        if t1 is not None:
            log.append((self.name, self._t0, t1, self.args))
        return out


def span(name: str, **args) -> Span:
    return Span(name, **args)
