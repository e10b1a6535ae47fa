"""Device milliseconds per decode-program execution: executions that hold
the ``flash_decode`` kernel (trace)."""


def read(ctx):
    t = ctx.trace
    if t is None:
        return None
    runs = t.executions("flash_decode")
    if not runs:
        return None
    return sum(r.dur_ns for r in runs) / len(runs) / 1e6
