"""The GBD primal's water-fill: the exact solution by sorted breakpoints
against the 60-step log-space bisection on omega1 it replaced, kept here as
the reference, per round and through whole GBD solves of a deployment."""

import warnings

import numpy as np
import pytest

from repro.core import gbd, primal

#: Bisection steps of the reference.
REF_ITERS = 60


def waterfill_bisect(alpha1_r, bmin_r, b_max):
    """The reference: bisect omega1 in log space so that sum_i B = b_max."""
    over = bmin_r.sum(axis=1) / b_max
    bmin_r = np.where(over[:, None] > 1.0, bmin_r / over[:, None] * (1 - 1e-12), bmin_r)
    hi = np.max(alpha1_r / np.maximum(bmin_r, 1e-30) ** 2, axis=1)
    lo = np.full_like(hi, 1e-30)
    for _ in range(REF_ITERS):
        mid = np.sqrt(lo * hi)
        B = np.maximum(bmin_r, np.sqrt(alpha1_r / mid[:, None]))
        too_big = B.sum(axis=1) > b_max
        lo = np.where(too_big, mid, lo)
        hi = np.where(too_big, hi, mid)
    omega1 = np.sqrt(lo * hi)
    B = np.maximum(bmin_r, np.sqrt(alpha1_r / omega1[:, None]))
    free = B > bmin_r * (1 + 1e-9)
    slack = b_max - B.sum(axis=1)
    nfree = np.maximum(free.sum(axis=1), 1)
    B = B + free * (slack / nfree)[:, None]
    B = np.maximum(B, bmin_r)
    return B, omega1


B_MAX = 20e6


def _random(seed, r=4, n=10):
    """alpha1 and bmin of the scale the orchestrator's instances have,
    with sum_i bmin a random share (5-95%) of b_max in every round."""
    rng = np.random.default_rng(seed)
    alpha1 = 10.0 ** rng.uniform(3, 7, (r, n))
    bmin = rng.uniform(0.1, 1.0, (r, n))
    bmin *= (rng.uniform(0.05, 0.95, r) * B_MAX / bmin.sum(axis=1))[:, None]
    return alpha1, bmin


def _over():
    """sum bmin a hair over b_max: the scaling branch."""
    alpha1, bmin = _random(11)
    return alpha1, bmin / bmin.sum(axis=1, keepdims=True) * B_MAX * (1 + 1e-10)


def _ties():
    """Equal breakpoints: whole groups of devices leave bmin together."""
    alpha1, bmin = _random(12)
    bmin[:, 3:7] = bmin[:, 3:4]
    alpha1[:, 3:7] = alpha1[:, 3:4]
    bmin[:, 7:] = 2 * bmin[:, 0:1] * np.sqrt(alpha1[:, 7:] / alpha1[:, 0:1])
    return alpha1, bmin


def _all_bound():
    """sum bmin == b_max exactly: every device held at bmin."""
    alpha1, bmin = _random(13)
    bmin = bmin / bmin.sum(axis=1, keepdims=True) * B_MAX
    return alpha1, bmin


def _single_free():
    """One device's breakpoint far below the rest, and just enough band
    past sum bmin that it alone is free."""
    alpha1 = np.full((4, 10), 1e5)
    alpha1[:, 5] = 1e7
    return alpha1, np.full_like(alpha1, 0.099 * B_MAX)


def _zero_alpha1():
    """A device with alpha1 = 0 (its breakpoint is +inf): never free."""
    alpha1, bmin = _random(15)
    alpha1[:, 2] = 0.0
    alpha1[1, 7] = 0.0
    return alpha1, bmin


CASES = {**{f"random{s}": (lambda s=s: _random(s)) for s in range(6)},
         "random_wide": lambda: _random(6, r=7, n=64),
         "over": _over, "ties": _ties, "all_bound": _all_bound,
         "single_free": _single_free, "zero_alpha1": _zero_alpha1}


@pytest.mark.parametrize("case", list(CASES))
def test_waterfill_matches_the_bisection(case):
    alpha1, bmin = CASES[case]()
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # no division by zero, no 0/0
        B, w1 = primal._waterfill(alpha1, bmin, B_MAX)
    B_ref, w1_ref = waterfill_bisect(alpha1, bmin, B_MAX)
    assert B.shape == alpha1.shape and w1.shape == (alpha1.shape[0],)
    assert np.all(np.isfinite(B)) and np.all(np.isfinite(w1))
    np.testing.assert_allclose(B, B_ref, rtol=1e-9, atol=0)
    np.testing.assert_allclose(w1, w1_ref, rtol=1e-9, atol=0)
    np.testing.assert_allclose(B.sum(axis=1), B_MAX, rtol=1e-12, atol=0)
    scale = np.maximum(bmin.sum(axis=1) / B_MAX, 1.0)[:, None]
    assert np.all(B >= bmin / scale * (1 - 1e-12))
    if case == "single_free":
        assert np.all((B > bmin * (1 + 1e-9)).sum(axis=1) == 1)
    if case == "zero_alpha1":
        np.testing.assert_array_equal(B[:, 2], bmin[:, 2])
        assert B[1, 7] == bmin[1, 7]


def _fwq_deployment():
    """The benchmark's fwq deployment, built as its FL cell builds it: fleet
    seed 0, 10 clients, ResNet-34 (d = 21,280,330), bits 8/16/32, horizon
    4, memory 0.5-1.5x the float32 model."""
    from repro.api.precision import PrecisionPolicy
    from repro.core.energy import heterogeneous_fleet, memory_capacities
    from repro.fed.orchestrator import FLOrchestrator, OrchestratorConfig

    n, d = 10, 21_280_330
    gb = 4.0 * d
    fleet = heterogeneous_fleet(n, seed=0, group_step_mhz=5.0)
    caps = memory_capacities(n, lo_mb=0.5 * gb / 1e6,
                             hi_mb=1.5 * gb / 1e6) * 1e6
    return FLOrchestrator(
        OrchestratorConfig(n_devices=n, n_rounds=35, scheme="fwq",
                           model_dim_d=d, error_tolerance=0.05,
                           resolve_every=5, seed=0,
                           precision=PrecisionPolicy(bit_options=(8, 16, 32))),
        fleet, caps, grad_bytes=gb)


@pytest.mark.parametrize("round_idx", [0, 5])
def test_gbd_on_the_fwq_deployment_matches_the_bisection(round_idx,
                                                         monkeypatch):
    orch = _fwq_deployment()
    assert orch.cfg.horizon == 4
    data = orch._primal_data(round_idx)
    new = gbd.run_gbd(data, orch.spec, max_rounds=30)
    monkeypatch.setattr(primal, "_waterfill", waterfill_bisect)
    ref = gbd.run_gbd(data, orch.spec, max_rounds=30)
    np.testing.assert_array_equal(new.q, ref.q)
    np.testing.assert_array_equal(new.q, np.full(10, 16))
    assert new.primal_sweeps == ref.primal_sweeps == 7566
    assert new.energy == pytest.approx(ref.energy, rel=1e-9, abs=0)
    np.testing.assert_allclose(new.bandwidth, ref.bandwidth, rtol=1e-9, atol=0)
    np.testing.assert_allclose(new.t_rounds, ref.t_rounds, rtol=1e-9, atol=0)
