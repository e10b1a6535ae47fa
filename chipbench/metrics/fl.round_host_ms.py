"""Host milliseconds per ``FLSimulation.run_round`` call outside its host
syncs: the self time of ``repro.sim.round`` less its ``repro.sim.wait``
child (bits to resolutions, the RNG fold, the round's dispatch and the
record), per round (program spans)."""

from chipbench import spans


def read(ctx):
    v = spans.of(ctx)
    rounds = v.count("repro.sim.round") if v is not None else 0
    if not rounds:
        return None
    return 1e3 * v.self_s("repro.sim.round", "repro.sim.wait") / rounds
