"""The FL driver at CPU sizes: a whole run through the harness is correct,
and the harness's proxies change no result."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.drivers import fl

from chipbench_tiny import SEED, run_tiny, tiny


@pytest.fixture(scope="module")
def static_run():
    return run_tiny("fl-resnet34-fwq-static")


def test_a_run_is_correct(static_run):
    result, lines = static_run
    assert result["correct"], lines
    assert set(result["checks"]) == {"loss", "grad", "change", "grad_diff"}
    assert result["attempted"] == 5 and result["failed"] == 0
    assert set(result["metrics"]) == {"fl_round_s", "setup_s"}
    assert result["compiles_in_window"] == 0
    assert list(result)[-1] == "checks"


def test_the_window_runs_whole_cycles():
    wl, cfg, mod = tiny("fl-resnet34-fwq")
    drv = fl.build(cfg, wl, mod, SEED, seconds=12)
    assert drv.window_rounds() == 10          # two re-solve cycles of 5
    assert fl.build(cfg, wl, mod, SEED, seconds=1).window_rounds() == 5


def test_proxies_change_no_result():
    """The rounds set-up drives through the proxies equal the same rounds
    driven straight through ``FLOrchestrator.run``."""
    from repro.fed.simulation import FLSimulation, SimConfig
    from repro.models.cnn import resnet, xent_loss

    wl, cfg, mod = tiny("fl-resnet34-fwq-static")
    drv = fl.build(cfg, wl, mod, SEED, seconds=5)
    drv.setup()
    got = [h["loss"] for h in drv.sim.history]
    got_params = jax.tree_util.tree_leaves(drv.sim.params)

    model = resnet(depth_blocks=tuple(cfg["depth_blocks"]),
                   width=cfg["width"], n_classes=cfg["n_classes"])
    sim = FLSimulation(xent_loss(model), model.init,
                       SimConfig(n_clients=cfg["n_clients"], lr=cfg["lr"],
                                 seed=SEED))
    x, y = fl.make_images(cfg, SEED)
    calls = []

    def feed(r, cohort):
        i = len(calls) % x.shape[0]
        calls.append(r)
        return {"x": jnp.asarray(x[i][cohort]), "y": jnp.asarray(y[i][cohort])}

    drv._orchestrator(len(got)).run(sim, feed)
    assert [h["loss"] for h in sim.history] == got
    for a, b in zip(jax.tree_util.tree_leaves(sim.params), got_params):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the reference follows the first rounds of set-up
    assert [r["round"] for r in drv.recording_done] == [0, 1, 2]


def test_images_are_drawn_from_the_seed():
    wl, cfg, mod = tiny("fl-resnet34-fwq")
    a = fl.make_images(cfg, SEED)
    b = fl.make_images(cfg, SEED)
    c = fl.make_images(cfg, SEED + 1)
    np.testing.assert_array_equal(a[0], b[0])
    assert not np.array_equal(a[0], c[0])
    n, bs = cfg["n_clients"], cfg["local_batch"]
    assert a[0].shape == (fl.POOL_ROUNDS, n, bs, 16, 16, 3)
    # rows differ from round to round: the reference's rounds see new data
    assert not np.array_equal(a[0][0], a[0][1])
