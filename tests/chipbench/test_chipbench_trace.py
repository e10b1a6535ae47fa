"""The reduction from a profiler trace to per-layer numbers."""

import pathlib

import pytest

from chipbench.trace import Event, Trace, union_ns

DATA = pathlib.Path(__file__).resolve().parent / "data"


def ev(name, s, e):
    return Event(name, float(s), float(e))


def call(kernel, n):
    return (f"%{kernel}.{n} = f32[16,2048]{{1,0}} custom-call(f32[16,1024] "
            f"%x.1), custom_call_target=\"tpu_custom_call\"")


def synthetic():
    ops = {0: [ev("%fusion.1 = f32[16] fusion(f32[16] %p.1)", 100, 200),
               ev(call("quant_matmul", 3), 150, 300),
               ev(call("flash_decode", 4), 400, 500),
               # consumes a kernel's output: not a kernel call itself
               ev("%reduce_sum.2 = f32[] reduce(f32[16,2048] "
                  "%quant_matmul.3)", 500, 520),
               ev(call("flash_attention", 7), 700, 900),
               ev(call("quant_matmul", 8), 920, 960),
               ev("%fusion.2 = f32[16] fusion(f32[16] %p.2)", 1200, 1300)],
           1: [ev("%fusion.9 = f32[16] fusion(f32[16] %p.1)", 100, 1100)]}
    modules = {0: [ev("jit_sm", 100, 550), ev("jit_sm", 650, 1000),
                   ev("jit_other", 1150, 1350)]}
    spans = [ev("chipbench.window", 0, 1400), ev("chipbench.decode", 50, 560),
             ev("chipbench.serve", 0, 1400), ev("chipbench.prefill", 600, 1010)]
    return Trace(ops, modules, spans)


def test_union_of_intervals():
    assert union_ns([]) == 0
    assert union_ns([(0, 10), (5, 15), (20, 30)]) == 25
    assert union_ns([(20, 30), (0, 40)]) == 40
    assert union_ns([(0, 10), (10, 20)]) == 20


def test_busy_time_is_the_union_averaged_over_devices():
    t = synthetic()
    # device 0: [100, 300] + [400, 520] + [700, 900] + [920, 960] + [1200,
    # 1300] = 200 + 120 + 200 + 40 + 100; device 1: 1000
    assert t.busy_s() == pytest.approx((660 + 1000) / 2 / 1e9)
    assert t.window_s == pytest.approx(1400 / 1e9)


def test_kernels_match_by_their_instruction_name():
    t = synthetic()
    assert t.kernel_s("quant_matmul") == pytest.approx((150 + 40) / 1e9)
    assert t.kernel_s("flash_decode") == pytest.approx(100 / 1e9)
    assert t.kernel_s("no_such_kernel") == 0


def test_programs_match_by_the_kernels_they_hold():
    t = synthetic()
    dec = t.executions("flash_decode")
    pre = t.executions("flash_attention", lacks="flash_decode")
    assert [(m.start_ns, m.end_ns) for m in dec] == [(100, 550)]
    assert [(m.start_ns, m.end_ns) for m in pre] == [(650, 1000)]


def test_idle_gaps_are_named_by_the_innermost_span():
    gaps = synthetic().idle_gaps()
    assert gaps[0] == ["chipbench.serve", pytest.approx(240 / 1e9)]
    names = {n for n, _ in gaps}
    assert "chipbench.decode" in names and "chipbench.window" not in names
    assert sum(s for _, s in gaps) == pytest.approx((1400 - 660) / 1e9)


def test_events_outside_the_window_are_dropped():
    t = Trace({0: [ev("a", 0, 10), ev("b", 50, 60), ev("c", 95, 120)]}, {},
              [ev("chipbench.window", 40, 100)])
    assert [e.name for e in t.ops[0]] == ["b", "c"]
    assert t.busy_s() == pytest.approx(15 / 1e9)


@pytest.fixture(scope="module")
def recorded():
    """A trace recorded on a TPU v5e by ``python -m chipbench.record_trace``:
    three calls each of a decode-like program (``quant_matmul`` and
    ``flash_decode``) and a prefill-like one (``quant_matmul`` and
    ``flash_attention``), both ``jit(sm)``, under harness spans."""
    return Trace.from_file(str(DATA / "kernels.xplane.pb"))


def test_recorded_trace_kernels(recorded):
    assert list(recorded.ops) == [0]
    qmm = recorded.kernel_events("quant_matmul")
    assert len(qmm) == 6
    assert all(e.name.startswith("%quant_matmul.") for e in qmm)
    for k in ("quant_matmul", "flash_decode", "flash_attention"):
        assert 0 < recorded.kernel_s(k) < recorded.busy_s()
    assert len(recorded.kernel_events("flash_decode")) == 3
    assert len(recorded.kernel_events("flash_attention")) == 3


def test_recorded_trace_programs_by_content(recorded):
    names = {m.name.split("(")[0] for m in recorded.modules[0]}
    assert names == {"jit_sm"}
    dec = recorded.executions("flash_decode")
    pre = recorded.executions("flash_attention", lacks="flash_decode")
    assert len(dec) == 3 and len(pre) == 3
    assert not {(m.start_ns, m.end_ns) for m in dec} & {
        (m.start_ns, m.end_ns) for m in pre}


def test_recorded_trace_spans_and_gaps(recorded):
    spans = sorted(s.name for s in recorded.spans)
    assert spans == ["chipbench.decode"] * 3 + ["chipbench.prefill"] * 3
    assert 0 < recorded.busy_s() < recorded.window_s
    gaps = recorded.idle_gaps()
    assert 0 < len(gaps) <= 10
    assert {n for n, _ in gaps} <= {"chipbench.decode", "chipbench.prefill",
                                     "outside harness spans"}
