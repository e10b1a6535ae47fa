"""The program's own spans and counters: names, nesting and args in a
profiler trace taken on the CPU, the in-process log beside it, the counts
the spans carry, and the step programs' stable names."""

import glob
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs


def host_spans(log_dir) -> list:
    """``(name, start_ns, end_ns, args)`` of every ``repro.*`` event on the
    trace's host planes."""
    path = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)[0]
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for e in line.events:
                name = e.name.partition("#")[0]
                if name.startswith("repro."):
                    out.append((name, e.start_ns, e.start_ns + e.duration_ns,
                                dict(e.stats)))
    return out


def named(spans, name):
    return [s for s in spans if s[0] == name]


def inside(child, parents) -> bool:
    return any(p[1] <= child[1] and child[2] <= p[2] for p in parents)


def traced(tmp_path, fn):
    """Run ``fn`` under a profiler trace; its result, the trace's spans and
    the log's records of the run."""
    obs.log.clear()
    with jax.profiler.trace(str(tmp_path)):
        out = fn()
    return out, host_spans(tmp_path), list(obs.log)


def test_a_span_is_logged_only_while_a_trace_is_taken(tmp_path):
    obs.log.clear()
    with obs.span("repro.test.off", k=1) as sp:
        assert isinstance(sp, jax.profiler.TraceAnnotation)
    assert not obs.log

    def body():
        with obs.span("repro.test.outer", round=3):
            with obs.span("repro.test.inner") as sp:
                sp.set(sweeps=17)

    _, spans, log = traced(tmp_path, body)
    assert [r[0] for r in log] == ["repro.test.inner", "repro.test.outer"]
    assert log[0][3] == {"sweeps": 17} and log[1][3] == {"round": 3}
    assert all(r[1] < r[2] for r in log)
    (outer,), (inner,) = (named(spans, "repro.test.outer"),
                          named(spans, "repro.test.inner"))
    assert outer[3] == {"round": 3} and inner[3] == {"sweeps": 17}
    assert inside(inner, [outer])


@pytest.fixture(scope="module")
def fl_run(tmp_path_factory):
    """Three rounds of the orchestrated loop (one GBD solve) on a tiny
    ResNet, traced; ``_waterfill`` calls counted, and the sweeps of each
    primal solution kept."""
    from repro.core import gbd, primal

    from test_faults import _orch
    from test_fed_integration import batch_fn_for, make_data, make_sim

    orch = _orch(n=4, rounds=3)
    sim, _, _ = make_sim(n_clients=4)
    fn = batch_fn_for(make_data(n_clients=4))
    calls, sweeps = [], []
    plain, solve = primal._waterfill, gbd.solve_primal

    def counted(*a):
        calls.append(1)
        return plain(*a)

    def kept(*a):
        sol = solve(*a)
        sweeps.append(sol.sweeps)
        return sol

    primal._waterfill, gbd.solve_primal = counted, kept
    try:
        out, spans, log = traced(tmp_path_factory.mktemp("fl"),
                                 lambda: orch.run(sim, fn))
    finally:
        primal._waterfill, gbd.solve_primal = plain, solve
    return orch, out, spans, log, len(calls), sweeps


def test_fl_spans_nest_as_the_layers_do(fl_run):
    orch, out, spans, log, _, sweeps = fl_run
    plans = named(spans, "repro.fl.plan")
    rounds = named(spans, "repro.sim.round")
    assert [p[3]["round"] for p in plans] == [0, 1, 2]
    assert [r[3]["round"] for r in rounds] == [0, 1, 2]
    assert all(r[3]["cohort"] == len(h["client_loss"])
               for r, h in zip(rounds, out["history"]))
    (resolve,) = named(spans, "repro.fl.resolve")
    assert resolve[3]["round"] == 0 and resolve[3]["warm"] == 0
    assert inside(resolve, plans)
    for name in ("repro.gbd.primal", "repro.gbd.master"):
        its = named(spans, name)
        assert [s[3]["iter"] for s in its] == list(range(1, len(sweeps) + 1))
        assert all(inside(s, [resolve]) for s in its)
    for name in ("repro.sim.dispatch", "repro.sim.wait"):
        assert len(named(spans, name)) == 3
        assert all(inside(s, rounds) for s in named(spans, name))
    # the log holds the same spans as the trace
    assert sorted(r[0] for r in log) == sorted(s[0] for s in spans)


def test_primal_sweeps_add_up(fl_run):
    _, out, spans, _, waterfills, per_call = fl_run
    (resolve,) = named(spans, "repro.fl.resolve")
    # GBDResult.primal_sweeps, carried by the solve's span, is the sum over
    # its primal calls, each counting the water-fills it ran
    assert resolve[3]["primal_sweeps"] == sum(per_call) == waterfills > 0
    assert len(named(spans, "repro.gbd.primal")) == len(per_call)
    assert [e["resolved"] for e in out["energy_log"]] == [True, False, False]


def test_infeasible_primal_runs_no_sweep():
    from test_codesign import make_instance

    from repro.core.primal import PrimalData, solve_primal

    data, *_ = make_instance()
    tight = PrimalData(**{**data.__dict__, "t_max": 1e-6})
    assert solve_primal(tight, np.full(4, 8)).sweeps == 0


@pytest.fixture(scope="module")
def serve_run(tmp_path_factory):
    """A tiny paged ``Session.serve`` call with ragged prompts, traced; the
    step programs it built are kept, to read their jit caches after."""
    from repro.api import PrecisionPolicy, RunSpec, Session
    from repro.launch import steps as steps_mod

    sess = Session(RunSpec(arch="yi-6b", workload="serve", mesh="1x1",
                           smoke=True, batch=4, seq=32, seed=5,
                           precision=PrecisionPolicy.lazy_int8(7)))
    kw = dict(requests=10, prompt_len=16, vary_prompt=True, max_new=6,
              steps=24, s_max=32, page_size=8, kv_layout="paged",
              quiet=True)

    built = []

    def keeping(build):
        def wrapped(*a, **k):
            built.append(build(*a, **k))
            return built[-1]
        return wrapped

    def timed():
        t0 = time.perf_counter()
        st = sess.serve(**kw)
        return st, time.perf_counter() - t0

    plain = steps_mod.build_decode_step, steps_mod.build_cached_prefill
    steps_mod.build_decode_step, steps_mod.build_cached_prefill = map(
        keeping, plain)
    try:
        (stats, wall), spans, log = traced(
            tmp_path_factory.mktemp("serve"), timed)
    finally:
        steps_mod.build_decode_step, steps_mod.build_cached_prefill = plain
    return stats, wall, spans, log, built


def test_serve_spans_nest_as_the_table_says(serve_run):
    stats, _, spans, log, _ = serve_run
    (prep,) = named(spans, "repro.serve.prepare")
    admits = named(spans, "repro.serve.admit")
    prefills = named(spans, "repro.serve.prefill")
    steps = named(spans, "repro.serve.step")
    slots = named(spans, "repro.serve.slots")
    assert [a[3]["wave"] for a in admits] == list(range(1, len(admits) + 1))
    assert len(admits) >= 2                   # mid-flight admissions
    assert all(inside(p, admits) for p in prefills)
    assert sorted({p[3]["bucket"] for p in prefills}) == stats.prompt_buckets
    ids = [i for p in prefills for i in str(p[3]["requests"]).split()]
    assert len(ids) == len(set(ids)) == stats.admitted
    assert [s[3]["step"] for s in steps] == list(
        range(1, stats.decode_steps + 1))
    assert [s[3]["step"] for s in slots] == list(
        range(1, stats.decode_steps + 1))
    # set-up, the first admission and the first step are inside prepare;
    # the loop's slot walks are not
    assert inside(admits[0], [prep]) and inside(steps[0], [prep])
    assert not any(inside(s, [prep]) for s in slots)
    assert not any(inside(s, steps) for s in slots)
    assert sorted(r[0] for r in log) == sorted(s[0] for s in spans)


def test_serve_marks_each_program_load(serve_run):
    """A step or prefill span carries ``load`` exactly where its call added
    an entry to the program's jit cache: the first call of each program, and
    any later call on inputs of another placement or dtype."""
    stats, _, spans, _, built = serve_run
    calls = named(spans, "repro.serve.step") + named(spans,
                                                     "repro.serve.prefill")
    loads = [s[3]["load"] for s in calls if "load" in s[3]]
    assert len(loads) == sum(b.loads() for b in built)
    assert named(spans, "repro.serve.step")[0][3]["load"] == "serve_decode"
    firsts = {}
    for p in named(spans, "repro.serve.prefill"):
        firsts.setdefault(p[3]["bucket"], p)
    assert [firsts[b][3]["load"] for b in sorted(firsts)] == [
        f"serve_prefill_{b}" for b in stats.prompt_buckets]
    assert set(loads) <= {"serve_decode"} | {
        f"serve_prefill_{b}" for b in stats.prompt_buckets}


def test_serve_counts_prefill_slots(serve_run):
    stats, wall, spans, _, _ = serve_run
    # every prefill call computes all 4 slots, for the requests it admits
    prefills = named(spans, "repro.serve.prefill")
    assert 4 * len(prefills) >= stats.admitted
    assert len(prefills) >= 2
    # the loop is timed on the clock the caller's wall clock uses
    assert 0 < stats.wall_s < wall


def test_step_programs_have_stable_names():
    from repro.api import PrecisionPolicy, RunSpec, Session
    from repro.launch.steps import build_cached_prefill, build_decode_step

    sess = Session(RunSpec(arch="yi-6b", workload="serve", mesh="1x1",
                           smoke=True, batch=2, seq=16, seed=0,
                           precision=PrecisionPolicy.lazy_int8(7)))
    ptree = jax.eval_shape(lambda: sess.serving_params)
    common = dict(params_tree=ptree, s_max=16, batch_global=2,
                  policy=sess.policy)
    dec = build_decode_step(sess.model, sess.mesh, sess.axes, **common)
    pre = build_cached_prefill(sess.model, sess.mesh, sess.axes,
                               s_prompt=8, with_prompt_lens=True, **common)
    caches = dec.caches_shape
    tok = jax.ShapeDtypeStruct((2, 1), jnp.int32)
    text = dec.fn.lower(ptree, {"token": tok}, caches).as_text()
    assert text.startswith("module @jit_serve_decode")
    batch = {"tokens": jax.ShapeDtypeStruct((2, 8), jnp.int32)}
    mask = jax.ShapeDtypeStruct((2,), jnp.bool_)
    plens = jax.ShapeDtypeStruct((2,), jnp.int32)
    text = pre.fn.lower(ptree, batch, caches, mask, plens).as_text()
    assert text.startswith("module @jit_serve_prefill")


def test_the_fl_round_has_a_stable_name():
    from test_fed_integration import make_sim

    sim, _, _ = make_sim(n_clients=2)
    batch = {"x": jnp.zeros((2, 4, 16, 16, 3)),
             "y": jnp.zeros((2, 4), jnp.int32)}
    text = sim._round.lower(sim.params, sim.opt_state, batch,
                            jnp.zeros((2,)), jax.random.PRNGKey(0)).as_text()
    assert text.startswith("module @jit_fl_round")
