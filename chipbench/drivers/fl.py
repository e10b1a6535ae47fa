"""The FL driver: the paper's round, ``FLOrchestrator.run`` over an
``FLSimulation`` of the configuration's model.

Set-up builds the simulation once from the seed and drives it through one
re-solve cycle with the window's own call (``FLOrchestrator.run``) and feed
(:meth:`FLDriver.batch_fn`); the reference follows the first three of those
rounds.  It then compiles the simulated round for every cohort size the
window's rounds yield (the workload file lists them: the deployment is
fixed, so its plan is the same for every seed), so the window compiles
nothing.  The window is one ``FLOrchestrator.run`` call over a whole number
of re-solve cycles (or, where the strategy is solved once, a fixed number of
rounds), sized from ``--seconds`` and the cell's nominal rate.

The harness owns two proxies, which change no result: :class:`SimProxy`
around ``FLSimulation.run_round`` and :meth:`FLDriver.batch_fn`.  They time
each round and each stretch of planning between rounds, and wrap them and
the feed in ``chipbench.round``, ``chipbench.plan`` and ``chipbench.batch``
spans.
"""

from __future__ import annotations

import math
import time

import numpy as np

#: Rounds of one set-up cycle that the reference follows.
REF_ROUNDS = 3
#: Rounds of images kept in the pool the feed cycles through.
POOL_ROUNDS = 8
#: The re-solve interval of a strategy solved once: past every window.
SOLVE_ONCE = 10 ** 6


def make_images(cfg: dict, seed: int, rounds: int = POOL_ROUNDS):
    """CIFAR-shaped images of ``rounds`` rounds from the seed: class
    templates (low-frequency sinusoids, one per class) at a class-dependent
    amplitude plus Gaussian noise.  Returns ``x (rounds, clients, batch, H,
    W, C)`` float32 and ``y (rounds, clients, batch)`` int32."""
    n, b = cfg["n_clients"], cfg["local_batch"]
    hw, ch, k = cfg["image_hw"], cfg["channels"], cfg["n_classes"]
    rng = np.random.default_rng(seed)
    y = rng.integers(0, k, (rounds, n, b)).astype(np.int32)
    yy, xx = np.mgrid[0:hw, 0:hw] / hw
    tmpl = np.stack([np.sin(2 * np.pi * ((c % 3 + 1) * xx + (c % 5) * yy
                                         + c / k)) for c in range(k)])
    amp = 0.5 + 0.1 * (y % 4)
    x = tmpl[y][..., None] * amp[..., None, None, None]
    x = x + 0.22 * rng.standard_normal(x.shape[:-1] + (ch,))
    return x.astype(np.float32), y


class SimProxy:
    """Stands in for the ``FLSimulation`` handed to ``FLOrchestrator.run``:
    ``run_round`` is timed and spanned, everything else passes through."""

    def __init__(self, sim, driver: "FLDriver"):
        self._sim = sim
        self._d = driver

    def run_round(self, batch, bits, **kw):
        import jax

        d = self._d
        d._close_plan()
        idx = self._sim.round_idx
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("chipbench.round"):
            rec = self._sim.run_round(batch, bits, **kw)
        d.round_s.append(time.perf_counter() - t0)
        d.losses.append(float(rec["loss"]))
        d.cohorts.append(int(np.asarray(bits).shape[0]))
        if d.recording is not None and len(d.recording) < REF_ROUNDS:
            d.recording.append({
                "x": np.asarray(batch["x"]), "y": np.asarray(batch["y"]),
                "bits": np.asarray(bits).copy(), "round": idx,
                "loss": float(rec["loss"])})
            if len(d.recording) in (1, REF_ROUNDS):
                d.snapshots[len(d.recording)] = d.host_params()
        d._open_plan()
        return rec

    def __getattr__(self, name):
        return getattr(self._sim, name)


class FLDriver:
    kind = "fl"

    def __init__(self, cfg: dict, wl: dict, mod, seed: int, seconds: float):
        self.cfg, self.wl, self.mod = cfg, wl, mod
        self.seed, self.seconds = int(seed), float(seconds)
        self.round_s: list = []
        self.plan_s: list = []
        self.losses: list = []
        self.cohorts: list = []
        self.recording = None
        self.snapshots: dict = {}
        self._plan = None
        self._calls = 0

    # -- the feed and the spans ---------------------------------------------
    def batch_fn(self, r, cohort):
        import jax
        import jax.numpy as jnp

        self._close_plan()
        with jax.profiler.TraceAnnotation("chipbench.batch"):
            slot = self._calls % self.x.shape[0]
            self._calls += 1
            idx = np.asarray(cohort, np.int64)
            return {"x": jnp.asarray(self.x[slot][idx]),
                    "y": jnp.asarray(self.y[slot][idx])}

    def _open_plan(self):
        import jax

        self._plan = (time.perf_counter(),
                      jax.profiler.TraceAnnotation("chipbench.plan"))
        self._plan[1].__enter__()

    def _close_plan(self, count: bool = True):
        if self._plan is None:
            return
        t0, span = self._plan
        span.__exit__(None, None, None)
        if count:
            self.plan_s.append(time.perf_counter() - t0)
        self._plan = None

    def host_params(self) -> list:
        import jax

        return [np.asarray(l, np.float64)
                for l in jax.tree_util.tree_leaves(self.sim.params)]

    # -- building -------------------------------------------------------------
    def _orchestrator(self, n_rounds: int):
        from repro.api.precision import PrecisionPolicy
        from repro.core.energy import heterogeneous_fleet, memory_capacities
        from repro.fed.orchestrator import FLOrchestrator, OrchestratorConfig

        cfg, wl = self.cfg, self.wl
        n = cfg["n_clients"]
        d = self.n_params
        gb = 4.0 * d
        lo, hi = cfg["mem_capacity_frac"]
        fleet = heterogeneous_fleet(n, seed=cfg["fleet_seed"],
                                    group_step_mhz=cfg["group_step_mhz"])
        caps = memory_capacities(n, lo_mb=lo * gb / 1e6,
                                 hi_mb=hi * gb / 1e6) * 1e6
        return FLOrchestrator(
            OrchestratorConfig(
                n_devices=n, n_rounds=n_rounds, scheme=wl["scheme"],
                model_dim_d=d, error_tolerance=cfg["error_tolerance"],
                resolve_every=self.resolve_every, seed=cfg["fleet_seed"],
                precision=PrecisionPolicy(
                    bit_options=tuple(cfg["bit_options"]))),
            fleet, caps, grad_bytes=gb)

    @property
    def solve_once(self) -> bool:
        return bool(self.wl.get("solve_once", False))

    @property
    def resolve_every(self) -> int:
        return SOLVE_ONCE if self.solve_once else self.wl["resolve_every"]

    def window_rounds(self) -> int:
        """Whole re-solve cycles, or any number of rounds where the strategy
        is solved once."""
        mult = 1 if self.solve_once else self.resolve_every
        return mult * max(1, round(self.seconds * self.wl["rounds_per_s"]
                                   / mult))

    def setup(self):
        import jax

        from repro.fed.simulation import FLSimulation, SimConfig
        from repro.models.cnn import resnet, xent_loss

        cfg, wl = self.cfg, self.wl
        t0 = time.perf_counter()
        model = resnet(depth_blocks=tuple(cfg["depth_blocks"]),
                       width=cfg["width"], n_classes=cfg["n_classes"])
        self.sim = FLSimulation(xent_loss(model), model.init,
                                SimConfig(n_clients=cfg["n_clients"],
                                          lr=cfg["lr"], seed=self.seed))
        self.n_params = int(sum(l.size for l in
                                jax.tree_util.tree_leaves(self.sim.params)))
        self.x, self.y = make_images(cfg, self.seed)
        self.rounds = self.window_rounds()
        static = self.solve_once
        warm = max(wl["setup_rounds"] if static else self.resolve_every,
                   REF_ROUNDS)
        self.snapshots[0] = self.host_params()
        self.recording = []
        t1 = time.perf_counter()
        warm_orch = self._orchestrator(warm)
        warm_orch.run(SimProxy(self.sim, self), self.batch_fn)
        self._close_plan(count=False)
        t2 = time.perf_counter()
        self.recording_done = list(self.recording)
        self.recording = None
        # The deployment is fixed, so the window's cohorts are too: the
        # workload file lists the sizes its rounds yield (planned on the
        # host); each is compiled here unless the warm cycle already ran it.
        self.warmed = set(self.cohorts) | set(wl["cohort_sizes"])
        for n in sorted(set(wl["cohort_sizes"]) - set(self.cohorts)):
            self._warm_cohort(n)
        if static:
            # the strategy solved in the warm cycle holds for the window
            self.orch = warm_orch
            warm_orch.cfg.n_rounds = self.rounds
        else:
            self.orch = self._orchestrator(self.rounds)
        self.phases = {"build_s": t1 - t0, "warm_cycle_s": t2 - t1,
                       "warm_cohorts_s": time.perf_counter() - t2}
        self.round_s, self.plan_s = [], []
        self.losses, self.cohorts = [], []

    def _warm_cohort(self, n: int):
        """Compile the simulated round for an ``n``-client cohort without
        moving the simulation: run it, then restore the state and history."""
        state, idx = self.sim.state(), self.sim.round_idx
        batch = self.batch_fn(0, np.arange(n))
        self.sim.run_round(batch, np.full((n,), 16, np.int64))
        self.sim.load_state(state, idx)
        self.sim.history.pop()

    # -- the window -------------------------------------------------------------
    def window(self) -> dict:
        t0 = time.perf_counter()
        self._open_plan()
        self.orch.run(SimProxy(self.sim, self), self.batch_fn)
        self._close_plan(count=False)
        wall = time.perf_counter() - t0
        n = len(self.round_s)
        self.wall_s = wall
        self.attempted = n
        self.failed = sum(not math.isfinite(x) for x in self.losses)
        self.notes = {"rounds": n, "plan_s": sum(self.plan_s),
                      "round_s": sum(self.round_s),
                      "cohorts": sorted(set(self.cohorts)),
                      "unwarmed": sorted(set(self.cohorts) - self.warmed)}
        return {"fl_round_s": wall / max(n, 1)}

    def counters(self) -> dict:
        n = max(len(self.round_s), 1)
        return {
            "rounds": len(self.round_s),
            "images": self.cfg["local_batch"] * sum(self.cohorts),
            "plan_s": sum(self.plan_s) / n,
            "sim_round_s": sum(self.round_s) / n,
            "wall_s": self.wall_s,
            "train_ops_per_image": self.mod.train_ops_per_image(self.cfg),
        }

    def release(self):
        self.sim = None
        self.orch = None
        self.x = self.y = None

    # -- correctness --------------------------------------------------------------
    def program_readings(self) -> dict:
        rec = self.recording_done[:REF_ROUNDS]
        return {"losses": [r["loss"] for r in rec], "p0": self.snapshots[0],
                "p1": self.snapshots[1], "pN": self.snapshots[REF_ROUNDS]}

    def reference(self, **kw) -> dict:
        return self.mod.reference_rounds(self.cfg, self.seed,
                                         self.recording_done[:REF_ROUNDS], **kw)

    def check(self) -> list:
        """``(name, value, limit)`` of every number compared."""
        self.ref = ref = self.reference()
        got = self.mod.compare(self.program_readings(), ref, self.cfg["lr"])
        lim = self.wl["limits"]
        return [(k, got[k], lim[k]) for k in lim]


def build(cfg, wl, mod, seed, seconds):
    return FLDriver(cfg, wl, mod, seed, seconds)
