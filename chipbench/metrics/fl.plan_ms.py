"""Host milliseconds per round between the return of
``FLSimulation.run_round`` and the next ``batch_fn`` call: the
orchestrator's planning (GBD re-solves, cohort control, energy
bookkeeping), timed by the harness's proxies."""


def read(ctx):
    c = ctx.counters
    if "plan_s" not in c or not c.get("rounds"):
        return None
    return 1e3 * c["plan_s"]
