"""Seconds of the measured ``Session.serve`` call spent outside its decode
loop: the harness's wall clock around the call less ``ServeStats.wall_s``
(the per-call trace and cache reload of the step programs, cache set-up,
the first admission and the first decode step)."""


def read(ctx):
    c = ctx.counters
    if "loop_s" not in c:
        return None
    return c["wall_s"] - c["loop_s"]
