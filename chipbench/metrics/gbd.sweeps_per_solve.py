"""Water-filling sweeps per GBD re-solve in the window: the mean of the
``primal_sweeps`` count that each ``repro.fl.resolve`` span carries
(``GBDResult.primal_sweeps``, summed over the solve's primal calls)."""

from chipbench import spans


def read(ctx):
    v = spans.of(ctx)
    if v is None:
        return None
    counts = [s.args["primal_sweeps"] for s in v.named("repro.fl.resolve")
              if "primal_sweeps" in s.args]
    return sum(counts) / len(counts) if counts else None
