"""Host milliseconds per window round spent in the GBD master: the summed
``repro.gbd.master`` spans (each ``solve_master`` call of ``run_gbd``, the
HiGHS MILP) over the window's ``repro.sim.round`` spans (program spans)."""

from chipbench import spans


def read(ctx):
    v = spans.of(ctx)
    if v is None or not v.count("repro.gbd.master"):
        return None
    rounds = v.count("repro.sim.round")
    return 1e3 * v.span_s("repro.gbd.master") / rounds if rounds else None
