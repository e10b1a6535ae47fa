"""Share of the prefilled slots that admitted a request: the request ids
the window's ``repro.serve.prefill`` spans carry, over the cell's slots
times those spans (every prefill call computes the whole batch)."""

from chipbench import spans


def read(ctx):
    v = spans.of(ctx)
    slots = ctx.wl.get("slots") if ctx.wl else None
    calls = v.named("repro.serve.prefill") if v is not None else []
    if not slots or not calls:
        return None
    admitted = sum(len(str(s.args["requests"]).split()) for s in calls)
    return 100.0 * admitted / (slots * len(calls))
