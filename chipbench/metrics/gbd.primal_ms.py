"""Host milliseconds per window round spent in the GBD primal: the summed
``repro.gbd.primal`` spans (each ``solve_primal`` call of ``run_gbd``, the
nested bandwidth bisections) over the window's ``repro.sim.round`` spans
(program spans)."""

from chipbench import spans


def read(ctx):
    v = spans.of(ctx)
    if v is None or not v.count("repro.gbd.primal"):
        return None
    rounds = v.count("repro.sim.round")
    return 1e3 * v.span_s("repro.gbd.primal") / rounds if rounds else None
