"""Host milliseconds per decode step between a step's tokens and the next
dispatch: the summed ``repro.serve.slots`` spans (the slot walk, page
tables, the ``cur_tok`` upload) over the ``repro.serve.step`` spans of the
window (program spans)."""

from chipbench import spans


def read(ctx):
    v = spans.of(ctx)
    steps = v.count("repro.serve.step") if v is not None else 0
    if not steps or not v.count("repro.serve.slots"):
        return None
    return 1e3 * v.span_s("repro.serve.slots") / steps
