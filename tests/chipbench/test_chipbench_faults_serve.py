"""A whole serving run with the timed path broken underneath comes out not
correct: a served token altered where it is produced, and a request
admitted into a freed slot reading the pages its previous occupant left;
and the control, the reference with int4 weights in the program's place,
reads past the cell's limit."""

import dataclasses

import numpy as np
import pytest

from chipbench import registry

from chipbench_tiny import SEED, run_tiny, tiny


def test_an_altered_token_is_not_correct(monkeypatch):
    from repro.launch import steps

    orig = steps.build_decode_step
    vocab = tiny("yi6b-int8-chat")[1]["vocab_size"]

    def faulty(*a, **kw):
        step = orig(*a, **kw)
        fn, calls = step.fn, [0]

        def call(params, batch, caches):
            tok, new = fn(params, batch, caches)
            calls[0] += 1
            if calls[0] % 5 == 0:       # every slot's token, every 5th step
                tok = (tok + 1) % vocab
            return tok, new

        return dataclasses.replace(step, fn=call)

    monkeypatch.setattr(steps, "build_decode_step", faulty)
    result, lines = run_tiny("yi6b-int8-chat", seconds=6)
    assert not result["correct"], lines
    gap = result["checks"]["gap"]
    assert gap["value"] > gap["limit"]


def test_stale_pages_after_a_readmission_are_not_correct(monkeypatch):
    """Every prefill that admits into a freed slot keeps the pool as it
    was, so the new request decodes over its predecessor's keys and
    values."""
    import jax

    from repro.launch import steps
    from repro.models.attention import PagedKVCache

    orig_pf, orig_dec = steps.build_cached_prefill, steps.build_decode_step
    used: set = set()

    def decode(*a, **kw):
        used.clear()                    # each serve call builds one decode
        return orig_dec(*a, **kw)

    def keep_pool(old, new):
        if isinstance(new, PagedKVCache):
            return new._replace(k_pages=old.k_pages, v_pages=old.v_pages)
        return new

    def prefill(*a, **kw):
        step = orig_pf(*a, **kw)
        fn = step.fn

        def call(params, batch, caches, mask, plens):
            tok, new = fn(params, batch, caches, mask, plens)
            slots = set(np.flatnonzero(np.asarray(mask)).tolist())
            if slots & used:
                new = jax.tree_util.tree_map(
                    keep_pool, caches, new,
                    is_leaf=lambda x: isinstance(x, PagedKVCache))
            used.update(slots)
            return tok, new

        return dataclasses.replace(step, fn=call)

    monkeypatch.setattr(steps, "build_cached_prefill", prefill)
    monkeypatch.setattr(steps, "build_decode_step", decode)
    result, lines = run_tiny("yi6b-int8-chat", seconds=6)
    assert not result["correct"], lines
    gap = result["checks"]["gap"]
    assert gap["value"] > gap["limit"]


@pytest.fixture(scope="module")
def served():
    from chipbench.drivers import serve

    wl, cfg, mod = tiny("yi6b-int8-chat")
    drv = serve.build(cfg, wl, mod, SEED, seconds=6)
    drv.setup()
    drv.window()
    drv.replay()
    drv.release()
    return drv


def test_the_sample_holds_a_request_admitted_into_a_freed_slot(served):
    picked = served.sample()
    streams = served.replay()[0]
    assert picked[0] is max((s for s in streams if s.done),
                            key=lambda s: len(s.served))
    reused = [s for s in streams if s.reused and s is not picked[0]]
    assert reused and picked[1] is max(reused, key=lambda s: len(s.served))
    assert len(picked) == served.wl["check_requests"]
    assert not any(s.reused for s in streams[:served.wl["slots"]])


def test_the_control_reads_past_the_limit(served):
    drv = served
    cfg, mod = drv.cfg, drv.mod
    picked = drv.sample()
    ref = drv.reference(picked)
    ctl = drv.reference(picked, bits=cfg["control_weight_bits"])
    gaps = np.concatenate([mod.served_gaps(r, np.argmax(c, axis=-1))
                           for r, c in zip(ref, ctl)])
    limit = registry.workload("yi6b-int8-chat")["limits"]["gap"]
    assert gaps.max() > limit, (gaps.max(), limit)
