"""The serving driver at CPU sizes: a whole run through the harness is
correct, the copy of the request draw matches ``Session.serve``'s queue,
and the step recorder changes no result."""

import dataclasses

import numpy as np
import pytest

from chipbench.drivers import serve

from chipbench_tiny import SEED, run_tiny, tiny


@pytest.fixture(scope="module")
def chat_run():
    return run_tiny("yi6b-int8-chat", seconds=6, trace=True)


def test_a_traced_run_is_correct(chat_run):
    result, lines = chat_run
    assert result["correct"], lines
    assert set(result["checks"]) == {"gap"}
    assert result["checks"]["gap"]["value"] <= 1e-3     # float32 on the CPU
    assert result["attempted"] >= 4 and result["failed"] == 0
    assert result["compiles_in_window"] == 0
    # no device planes on the CPU: host-clock metrics only
    assert {"serve.outside_loop_s", "mfu.serve"} <= set(result["metrics"])
    assert "prefill.busy_share" not in result["metrics"]
    assert list(result)[-1] == "checks"


def test_draw_copies_the_programs_queue():
    wl, cfg, mod = tiny("yi6b-int8-chat")
    q = serve.draw_requests(SEED, 6, 16, 8, cfg["vocab_size"])
    again = serve.draw_requests(SEED, 6, 16, 8, cfg["vocab_size"])
    assert [r["prompt"].tolist() for r in q] == [r["prompt"].tolist()
                                                 for r in again]
    assert all(8 <= len(r["prompt"]) <= 16 and 4 <= r["max_new"] <= 8
               for r in q)
    assert serve.bucket_of(9, 32) == 16 and serve.bucket_of(16, 32) == 16
    assert serve.bucket_of(8, 32) == 8 and serve.bucket_of(40, 32) == 32


def test_recorder_changes_no_result():
    from repro.api import PrecisionPolicy, RunSpec, Session

    wl, cfg, mod = tiny("yi6b-int8-chat")
    drv = serve.build(cfg, wl, mod, SEED, seconds=4)
    sess = Session(RunSpec(arch="yi-6b", workload="serve", mesh="1x1",
                           smoke=True, batch=wl["slots"], seq=wl["s_max"],
                           seed=SEED,
                           precision=PrecisionPolicy.lazy_int8(7)))
    kw = dict(requests=wl["requests"], prompt_len=wl["prompt_len"],
              vary_prompt=True, max_new=wl["max_new"], steps=20,
              **drv.serve_kw())
    plain = sess.serve(**kw)
    rec = serve.StepRecorder()
    with rec.installed():
        recorded = sess.serve(**kw)
    skip = {"wall_s", "tok_s"}
    for f in dataclasses.fields(plain):
        if f.name not in skip:
            assert getattr(plain, f.name) == getattr(recorded, f.name), f.name
    queue = serve.draw_requests(SEED, wl["requests"], wl["prompt_len"],
                                wl["max_new"], cfg["vocab_size"])
    streams, contexts, prefills = rec.replay(queue)
    assert len(streams) == plain.admitted
    assert sum(len(s.served) - 1 for s in streams) == plain.decoded_tokens
    assert len(contexts) == plain.decode_steps
    first = min((s for s in streams if s.done), key=lambda s: s.done_at)
    assert first.served[:16] == plain.sample
    assert np.all([c > 0 for ctx in contexts for c in ctx])
