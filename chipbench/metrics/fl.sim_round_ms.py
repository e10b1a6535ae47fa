"""Wall milliseconds per ``FLSimulation.run_round`` call, which ends in the
loss's host sync (harness proxy)."""


def read(ctx):
    c = ctx.counters
    if "sim_round_s" not in c or not c.get("rounds"):
        return None
    return 1e3 * c["sim_round_s"]
