"""A whole FL run with the timed path broken underneath comes out not
correct, once for each fault a training cell can have; and the control,
the reference one precision down, reads past the cell's limits."""

import pytest

from chipbench import registry

from chipbench_tiny import SEED, run_tiny, tiny


@pytest.fixture
def broken(monkeypatch):
    from repro.fed.simulation import FLSimulation

    orig = FLSimulation.run_round

    def stuck(self, batch, bits, **kw):
        """A step that returns its state unchanged."""
        params, opt = self.params, self.opt_state
        rec = orig(self, batch, bits, **kw)
        self.params, self.opt_state = params, opt
        return rec

    def half(self, batch, bits, **kw):
        """Half of every client's batch left out, the mean over the rest."""
        kept = {k: v[:, :v.shape[1] // 2] for k, v in batch.items()}
        return orig(self, kept, bits, **kw)

    def install(kind):
        monkeypatch.setattr(FLSimulation, "run_round",
                            {"stuck": stuck, "half": half}[kind])

    return install


@pytest.mark.parametrize("fault", ["stuck", "half"])
def test_a_fault_is_not_correct(broken, fault):
    broken(fault)
    result, lines = run_tiny("fl-resnet34-fwq-static")
    assert not result["correct"], lines
    failing = [n for n, c in result["checks"].items()
               if not c["value"] <= c["limit"]]
    assert failing, result["checks"]
    if fault == "stuck":
        assert result["checks"]["change"]["value"] == pytest.approx(1.0)


def test_the_control_reads_past_a_limit():
    from chipbench.drivers import fl

    wl, cfg, mod = tiny("fl-resnet34-fwq")
    drv = fl.build(cfg, wl, mod, SEED, seconds=1)
    drv.setup()
    drv.release()
    ref = drv.reference()
    ctl = mod.compare(drv.reference(control=True), ref, cfg["lr"])
    lim = registry.workload("fl-resnet34-fwq")["limits"]
    assert any(ctl[k] > lim[k] for k in lim), (ctl, lim)
    sound = mod.compare(drv.program_readings(), ref, cfg["lr"])
    assert all(sound[k] <= lim[k] for k in lim), (sound, lim)
