"""Device-idle seconds under the step and prefill calls of the measured
``Session.serve`` call that loaded their program: the ``repro.serve.step``
and ``repro.serve.prefill`` spans that carry ``load``, set where the call
added an entry to the program's jit cache, so traced it and loaded it from
the compilation cache (program spans on the device trace)."""

from chipbench import spans

CALLS = ("repro.serve.step", "repro.serve.prefill")


def read(ctx):
    v = spans.of(ctx)
    if v is None or not ctx.trace.ops:
        return None
    if not any(v.named(name, "load") for name in CALLS):
        return None
    return sum(v.idle_under(name, "load") for name in CALLS)
