"""Share of the device's busy time spent in prefill-program executions: the
executions that hold the ``flash_attention`` kernel and no ``flash_decode``
(trace)."""

from chipbench.trace import union_ns


def read(ctx):
    t = ctx.trace
    if t is None:
        return None
    runs = t.executions("flash_attention", lacks="flash_decode")
    busy = t.busy_s()
    if not runs or busy <= 0:
        return None
    return 100.0 * union_ns(t.intervals(runs)) / 1e9 / busy
