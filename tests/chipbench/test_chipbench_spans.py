"""The program's spans beside the harness's trace: summed, self and
device-idle seconds, idle gaps named by the innermost span, harness or
program, the reading of ``repro.*`` events (with a ``#k=v`` suffix) from a
profile, the log's alignment to the trace's clock, the per-layer readers
on both the new spans and the readers already accepted, and the load marks
that a serve call keeps under the harness's step recorder."""

import types

import pytest

from chipbench import registry, spans
from chipbench.run import Context
from chipbench.trace import Trace
from repro import obs

from chipbench_tiny import CPU, SEED, tiny
from test_chipbench_trace import DATA, ev, synthetic


def sp(name, s, e, **args):
    return spans.Span(name, float(s), float(e), args)


#: program spans on the clock of ``synthetic()``: set-up around a first
#: decode step, which loads its program, around the harness's
#: ``chipbench.decode`` (50-560); an admission whose prefill, around
#: ``chipbench.prefill`` (600-1010), loads its program; a second decode step
#: outside any harness span; the device is idle at 0-100, 300-400, 520-700,
#: 900-920, 960-1200 and 1300-1400 (device 0) and 0-100, 1100-1400 (device 1)
PROGRAM = [sp("repro.serve.prepare", 30, 570),
           sp("repro.serve.step", 40, 570, step=1, load="serve_decode"),
           sp("repro.serve.slots", 570, 590, step=1),
           sp("repro.serve.admit", 590, 1020, wave=2),
           sp("repro.serve.prefill", 595, 1015, bucket=16, requests="3 5",
              load="serve_prefill_16"),
           sp("repro.serve.slots", 1020, 1150, step=2),
           sp("repro.serve.step", 1150, 1350, step=2)]


@pytest.fixture
def view():
    return spans.Spans(synthetic(), PROGRAM)


def test_span_and_self_seconds(view):
    assert view.count("repro.serve.step") == 2
    assert view.span_s("repro.serve.step") == pytest.approx(730 / 1e9)
    assert view.span_s("chipbench.decode") == pytest.approx(510 / 1e9)
    # steps 40-570 and 1150-1350 less the harness's decode 50-560
    assert view.self_s("repro.serve.step", "chipbench.decode") == \
        pytest.approx(220 / 1e9)
    # admission 590-1020 less its prefill 595-1015
    assert view.self_s("repro.serve.admit", "repro.serve.prefill") == \
        pytest.approx(10 / 1e9)
    # prefill 595-1015 less the harness's 600-1010
    assert view.self_s("repro.serve.prefill", "chipbench.prefill") == \
        pytest.approx(10 / 1e9)
    assert view.self_s("repro.serve.slots", "repro.serve.step") == \
        pytest.approx(150 / 1e9)
    # only the spans that carry the arg
    assert [s.start_ns for s in view.named("repro.serve.step", "load")] == [
        40.0]
    assert view.named("chipbench.decode", "load") == []


def test_idle_under_averages_the_devices(view):
    # the loading step 40-570: device 0 idle 60 + 100 + 50, device 1 60 (its
    # one op runs 100-1100); the loading prefill 595-1015: device 0 105 + 20
    # + 55, device 1 none
    assert view.idle_under("repro.serve.step", "load") == pytest.approx(
        (210 + 60) / 2 / 1e9)
    assert view.idle_under("repro.serve.prefill", "load") == pytest.approx(
        180 / 2 / 1e9)
    # both steps: the second, 1150-1350, adds 50 + 50 and 200
    assert view.idle_under("repro.serve.step") == pytest.approx(
        ((210 + 100) + (60 + 200)) / 2 / 1e9)
    bare = spans.Spans(Trace({}, {}, synthetic().spans), PROGRAM)
    assert bare.idle_under("repro.serve.step", "load") == pytest.approx(
        530 / 1e9)


def test_gaps_are_named_by_the_innermost_span_harness_or_program(view):
    got = sorted((round(s * 1e9), n) for n, s in view.idle_gaps())
    # the harness's spans inside the program's stay innermost
    assert got == [(20, "chipbench.prefill"), (100, "chipbench.decode"),
                   (100, "chipbench.decode"), (100, "repro.serve.step"),
                   (180, "chipbench.prefill"), (240, "repro.serve.slots")]
    # the harness's own naming is unchanged
    assert synthetic().idle_gaps()[0] == ["chipbench.serve",
                                          pytest.approx(240 / 1e9)]


def test_spans_outside_the_window_are_dropped():
    t = Trace({0: [ev("a", 50, 60)]}, {}, [ev("chipbench.window", 40, 100)])
    v = spans.Spans(t, [sp("repro.x", 0, 30), sp("repro.x", 90, 120)])
    assert v.count("repro.x") == 1
    assert v.span_s("repro.x") == pytest.approx(10 / 1e9)


def test_a_name_is_matched_up_to_its_metadata_suffix():
    assert spans.base_name("repro.gbd.primal") == ("repro.gbd.primal", {})
    assert spans.base_name("repro.serve.slots#step=2,bucket=16#") == (
        "repro.serve.slots", {"step": "2", "bucket": "16"})


def fake_profile():
    def event(name, s, d, **stats):
        return types.SimpleNamespace(name=name, start_ns=s, duration_ns=d,
                                     stats=list(stats.items()))

    def plane(name, *events):
        line = types.SimpleNamespace(name="python", events=list(events))
        return types.SimpleNamespace(name=name, lines=[line])

    return types.SimpleNamespace(planes=[
        plane("/device:TPU:0", event("repro.not_a_host_event", 0, 5)),
        plane("/host:CPU",
              event("chipbench.window", 0, 100),
              event("repro.fl.resolve#round=5,warm=0#", 10, 40,
                    primal_sweeps=3),
              event("repro.gbd.master", 50, 5, iter=1),
              event("$contextlib.py:132 __enter__", 0, 1))])


def test_program_spans_are_read_from_the_host_planes():
    got = spans.from_profile(fake_profile())
    assert [(s.name, s.start_ns, s.end_ns) for s in got] == [
        ("repro.fl.resolve", 10.0, 50.0), ("repro.gbd.master", 50.0, 55.0)]
    assert got[0].args == {"round": "5", "warm": "0", "primal_sweeps": 3}
    assert got[1].args == {"iter": 1}


def test_the_log_is_put_on_the_traces_clock():
    t = Trace({}, {}, [ev("chipbench.window", 0, 1000),
                       ev("chipbench.round", 100, 300),
                       ev("chipbench.round", 500, 700)])
    # an earlier trace's round first, then this window's two, 1e6 ns apart
    # from the trace's clock and a few ns inside the harness's spans
    log = [("repro.sim.round", 10, 20, {"round": 0}),
           ("repro.sim.round", 1e6 + 103, 1e6 + 297, {"round": 1}),
           ("repro.gbd.primal", 1e6 + 320, 1e6 + 480, {"iter": 1}),
           ("repro.sim.round", 1e6 + 503, 1e6 + 697, {"round": 2})]
    got = spans.aligned(t, log, "chipbench.round", "repro.sim.round")
    assert [(s.name, s.start_ns) for s in got[1:]] == [
        ("repro.sim.round", 100.0), ("repro.gbd.primal", 317.0),
        ("repro.sim.round", 500.0)]
    v = spans.Spans(t, got)
    assert v.count("repro.sim.round") == 2       # the earlier one is dropped
    assert spans.aligned(t, log, "chipbench.decode",
                         "repro.serve.step") is None


def ctx_for(trace, log, monkeypatch, wl=None, counters=None):
    monkeypatch.setattr(obs, "log", list(log))
    return Context(trace, counters or {}, {}, wl or {}, None,
                   registry.peaks("TPU v5 lite"))


def read(name, ctx):
    return registry.metric_reader(name)(ctx)


def test_serve_readers(monkeypatch):
    # the program's spans, 5e5 ns off the trace's clock; the harness wraps
    # both decode calls, 10 ns after each step starts, so the log is put 10 ns
    # later than PROGRAM on the trace's clock
    log = [(s.name, s.start_ns - 5e5, s.end_ns - 5e5, s.args)
           for s in PROGRAM]
    t = synthetic()
    t = Trace(t.ops, t.modules, t.spans + [ev("chipbench.decode", 1160, 1340)])
    ctx = ctx_for(t, log, monkeypatch, wl={"slots": 4})
    # slots 20 + 130 over two decode steps
    assert read("serve.step_host_ms", ctx) == pytest.approx(75 / 1e6)
    # the loading step at 50-580: device 0 idle 50 + 100 + 60, device 1 50;
    # the loading prefill at 605-1025: device 0 95 + 20 + 65
    assert read("serve.load_s", ctx) == pytest.approx(
        (210 + 50 + 180) / 2 / 1e9)
    # 2 requests admitted by one prefill of 4 slots
    assert read("prefill.slot_use", ctx) == pytest.approx(50.0)
    for name in ("gbd.primal_ms", "gbd.master_ms", "gbd.sweeps_per_solve",
                 "fl.round_host_ms"):
        assert read(name, ctx) is None, name


def fl_trace():
    return Trace({0: [ev("%fusion.1 = f32[16] fusion(f32[16] %p.1)",
                         150, 250)]}, {},
                 [ev("chipbench.window", 0, 2000),
                  ev("chipbench.round", 100, 300),
                  ev("chipbench.plan", 300, 1500),
                  ev("chipbench.round", 1500, 1700)])


def test_fl_readers(monkeypatch):
    log = [("repro.sim.round", 101, 299, {"round": 0, "cohort": 10}),
           ("repro.sim.dispatch", 110, 140, {}),
           ("repro.sim.wait", 140, 290, {}),
           ("repro.gbd.primal", 320, 900, {"iter": 1}),
           ("repro.gbd.master", 900, 910, {"iter": 1}),
           ("repro.gbd.primal", 910, 1400, {"iter": 2}),
           ("repro.gbd.master", 1400, 1404, {"iter": 2}),
           ("repro.fl.resolve", 310, 1410,
            {"round": 1, "warm": False, "primal_sweeps": 7500}),
           ("repro.fl.plan", 305, 1490, {"round": 1}),
           ("repro.sim.round", 1501, 1699, {"round": 1, "cohort": 9}),
           ("repro.sim.dispatch", 1510, 1540, {}),
           ("repro.sim.wait", 1540, 1600, {})]
    ctx = ctx_for(fl_trace(), log, monkeypatch)
    assert read("gbd.primal_ms", ctx) == pytest.approx((580 + 490) / 2 / 1e6)
    assert read("gbd.master_ms", ctx) == pytest.approx((10 + 4) / 2 / 1e6)
    assert read("gbd.sweeps_per_solve", ctx) == 7500
    # rounds of 198 ns less waits of 150 and 60
    assert read("fl.round_host_ms", ctx) == pytest.approx(
        (48 + 138) / 2 / 1e6)
    for name in ("serve.step_host_ms", "serve.load_s", "prefill.slot_use"):
        assert read(name, ctx) is None, name


def test_new_readers_read_nothing_without_program_spans(monkeypatch):
    ctx = ctx_for(synthetic(), [], monkeypatch, wl={"slots": 4})
    for name in ("gbd.primal_ms", "gbd.master_ms", "gbd.sweeps_per_solve",
                 "fl.round_host_ms", "serve.step_host_ms", "serve.load_s",
                 "prefill.slot_use"):
        assert read(name, ctx) is None, name


#: The accepted readers' values on the test traces, with fixed counters: the
#: same as the parent commit's readers give.
ACCEPTED = {
    "synthetic": {
        "serve.outside_loop_s": 2.5, "prefill.busy_share": 42.16867469879518,
        "decode.step_ms": 0.00045, "quant_matmul_roofline": 75106014.00919427,
        "flash_decode_roofline": 589583.9413919414,
        "flash_attention_roofline": 1474920.087912088,
        "mfu.serve": 19.083969465648856,
        "device_idle_share.serve": 40.714285714285715, "fl.plan_ms": 900.0,
        "fl.sim_round_ms": 150.0, "mfu.fl": 0.6578680203045685,
        "device_idle_share.fl": 40.714285714285715},
    "recorded": {
        "serve.outside_loop_s": 2.5, "prefill.busy_share": 50.21603893906661,
        "decode.step_ms": 0.027809333333333332,
        "quant_matmul_roofline": 335949.8707947104,
        "flash_decode_roofline": 1093.96953536932,
        "flash_attention_roofline": 5901.686925202921,
        "mfu.serve": 19.083969465648856,
        "device_idle_share.serve": 95.73585710864829, "fl.plan_ms": 900.0,
        "fl.sim_round_ms": 150.0, "mfu.fl": 0.6578680203045685,
        "device_idle_share.fl": 95.73585710864829},
}


@pytest.mark.parametrize("which", sorted(ACCEPTED))
def test_accepted_readers_read_as_before(which, monkeypatch):
    yi = registry.config_module("yi-6b-int8")
    cfg = registry.config("yi-6b-int8")
    counters = {
        "wall_s": 40.0, "loop_s": 37.5, "model_ops": 3.0e15,
        "quant_matmul_rows": [16 * 256, 16, 16, 16],
        "flash_attention": [yi.flash_attention_cost(cfg, 16, 256)],
        "flash_decode": [yi.flash_decode_cost(cfg, [300, 400, 500], 16)] * 3,
        "rounds": 45, "images": 45 * 320, "plan_s": 0.9,
        "sim_round_s": 0.15, "train_ops_per_image": 3.6e9}
    trace = (synthetic() if which == "synthetic"
             else Trace.from_file(str(DATA / "kernels.xplane.pb")))
    # program spans in the log change none of them
    monkeypatch.setattr(obs, "log",
                        [(s.name, s.start_ns, s.end_ns, s.args)
                         for s in PROGRAM])
    ctx = Context(trace, counters, cfg, registry.workload("yi6b-int8-chat"),
                  yi, registry.peaks("TPU v5 lite"))
    got = {n: read(n, ctx) for n in ACCEPTED[which]}
    assert got == pytest.approx(ACCEPTED[which], rel=1e-12)


def test_a_traced_run_logs_what_its_trace_holds(tmp_path):
    """A tiny static FL cell through ``span_breakdown``: the program's spans
    read from the trace file and from the in-process log agree, and the
    cell's new per-layer metric is reported."""
    from chipbench import span_breakdown

    wl, cfg, mod = tiny("fl-resnet34-fwq-static")
    result, lines, found = span_breakdown.breakdown(
        wl, cfg, mod, SEED, 5, registry.peaks("TPU v5 lite"), CPU,
        str(tmp_path / "kept.xplane.pb"))
    assert result["correct"], lines
    assert "fl.round_host_ms" in result["metrics"]
    read_, logged = found["spans"], found["log_spans"]
    assert set(read_) == set(logged) == {
        "repro.fl.plan", "repro.sim.round", "repro.sim.dispatch",
        "repro.sim.wait"}
    for name, (secs, n) in read_.items():
        assert logged[name][1] == n == result["attempted"]
        # the same intervals on two clocks; a busy host may preempt the
        # process between a span's two readings
        assert logged[name][0] == pytest.approx(secs, rel=0.05, abs=5e-3)


def test_the_recorder_keeps_the_load_marks(tmp_path):
    """The recorder wraps each step's ``fn``; the serve call still marks
    the calls that traced and loaded a step program."""
    import jax

    from chipbench.drivers import serve
    from repro.api import PrecisionPolicy, RunSpec, Session

    wl, cfg, mod = tiny("yi6b-int8-chat")
    drv = serve.build(cfg, wl, mod, SEED, seconds=4)
    sess = Session(RunSpec(arch="yi-6b", workload="serve", mesh="1x1",
                           smoke=True, batch=wl["slots"], seq=wl["s_max"],
                           seed=SEED,
                           precision=PrecisionPolicy.lazy_int8(7)))
    rec = serve.StepRecorder()
    obs.log.clear()
    with jax.profiler.trace(str(tmp_path)), rec.installed():
        st = sess.serve(requests=wl["requests"], prompt_len=wl["prompt_len"],
                        vary_prompt=True, max_new=wl["max_new"], steps=8,
                        **drv.serve_kw())
    loads = {r[3]["load"] for r in obs.log if "load" in r[3]}
    assert loads == {"serve_decode"} | {f"serve_prefill_{b}"
                                        for b in st.prompt_buckets}
