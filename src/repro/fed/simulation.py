"""vmap-based FWQ-FL simulator (the paper-experiment path; CPU-friendly).

One jitted round = Algorithm 1 exactly: per-client SR tree-quantization at
traced resolutions, gradients at quantized weights, full-precision server
SGD.  Clients map onto the vmapped leading axis; the pod trainer
(launch/steps.py) is the shard_map twin of this for datacenter scale.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import numpy as np

from repro import obs
from repro.core.fwq import (
    FWQConfig,
    delta_for_clients,
    make_fwq_apply,
    make_fwq_client_grads,
    make_fwq_round,
    make_tree_quant_loss,
)
from repro.faults.executor import UpdateFaults, gate_mask, inject_corruption
from repro.optim import Optimizer, build_optimizer


@dataclasses.dataclass
class SimConfig:
    n_clients: int
    lr: float = 0.05
    optimizer: str = "sgd"
    momentum: float = 0.0
    seed: int = 0


class FLSimulation:
    """Stateful wrapper: holds params/opt, steps one FL round at a time."""

    def __init__(self, loss_fn: Callable, init_fn: Callable, cfg: SimConfig):
        """loss_fn(params, batch, rng) -> (loss, aux); init_fn(key) -> params."""
        self.cfg = cfg
        self.opt: Optimizer = build_optimizer(cfg.optimizer, cfg.lr,
                                              **({"momentum": cfg.momentum}
                                                 if cfg.optimizer == "sgd" else {}))
        key = jax.random.PRNGKey(cfg.seed)
        self.params = init_fn(key)
        self.opt_state = self.opt.init(self.params)
        client_loss = make_tree_quant_loss(loss_fn)
        fl_round = make_fwq_round(client_loss, self.opt.update,
                                  FWQConfig(n_clients=cfg.n_clients))
        self._round = jax.jit(fl_round)
        self._client_loss = client_loss
        self._gated = None  # (grads_fn, apply_fn) — built on first fault use
        self.round_idx = 0
        self.history: list[dict] = []

    def state(self):
        return {"params": self.params, "opt": self.opt_state}

    def load_state(self, state, round_idx: int):
        self.params, self.opt_state = state["params"], state["opt"]
        self.round_idx = round_idx

    def run_round(self, batch, bits, *, faults: UpdateFaults | None = None,
                  comm_bits: int | None = None) -> dict:
        """batch: leaves with leading dim n_clients; bits: (n_clients,) ints
        or a :class:`repro.api.precision.PrecisionPolicy` whose weights role
        covers exactly this round's cohort.

        ``faults`` (from the resilient orchestrator) switches to the gated
        two-phase round: per-client grads -> host-side payload corruption ->
        aggregation gate (finite check + relative norm bound) -> masked
        server step.  ``faults=None`` is the legacy single-jit round,
        bit-identical to before the gate existed.

        ``comm_bits`` records this round's gradient wire bit-width in the
        history row (adaptive programs change it mid-run, so per-round
        truth lives in the rows, not the spec); it does not change the
        simulator's math — the vmap round aggregates in full precision per
        Algorithm 1, wire compression is the pod trainer's concern.
        """
        n = jax.tree_util.tree_leaves(batch)[0].shape[0]
        with obs.span("repro.sim.round", round=self.round_idx, cohort=n):
            return self._run_round(batch, bits, n, faults, comm_bits)

    def _run_round(self, batch, bits, n: int, faults: UpdateFaults | None,
                   comm_bits: int | None) -> dict:
        if hasattr(bits, "bits_vector"):  # PrecisionPolicy
            if comm_bits is None:
                comm_bits = int(bits.comm)
            if bits.heterogeneous and len(bits.weights) != n:
                # a device-indexed policy cannot be positionally mapped onto
                # an elastic sub-cohort: the caller must select the cohort's
                # bits itself (see FLOrchestrator.run)
                raise ValueError(
                    f"policy carries {len(bits.weights)} per-device bits but "
                    f"the round batch has {n} clients; pass the cohort's own "
                    "bits (policy.bits_vector(n_devices)[cohort_idx])")
            bits = bits.bits_vector(n)
        delta = delta_for_clients(np.asarray(bits))
        rng = jax.random.fold_in(jax.random.PRNGKey(self.cfg.seed), self.round_idx)
        if faults is None:
            with obs.span("repro.sim.dispatch"):
                self.params, self.opt_state, m = self._round(
                    self.params, self.opt_state, batch, delta, rng)
            with obs.span("repro.sim.wait"):
                rec = {
                    "round": self.round_idx,
                    "loss": float(m.loss),
                    "grad_norm_sq": float(m.grad_norm_sq),
                    "client_loss": np.asarray(m.client_loss),
                    "bits": np.asarray(bits).copy(),
                }
        else:
            rec = self._run_gated_round(batch, delta, rng, bits, faults)
        if comm_bits is not None:
            rec["comm_bits"] = int(comm_bits)
        self.history.append(rec)
        self.round_idx += 1
        return rec

    def _run_gated_round(self, batch, delta, rng, bits,
                         faults: UpdateFaults) -> dict:
        if self._gated is None:
            self._gated = (jax.jit(make_fwq_client_grads(self._client_loss)),
                           jax.jit(make_fwq_apply(self.opt.update)))
        grads_fn, apply_fn = self._gated
        losses, grads, gsqs, finite = grads_fn(self.params, batch, delta, rng)
        norms_sq = np.array(gsqs, dtype=np.float64)
        finite = np.array(finite, dtype=bool)

        kinds = np.asarray(faults.kinds)
        if (kinds > 0).any():
            # pull per-client updates to the host, damage the flagged ones in
            # their flattened-payload view, and re-stage for aggregation
            leaves = [np.array(g) for g in jax.tree_util.tree_leaves(grads)]
            for ci in np.flatnonzero(kinds):
                vec = np.concatenate([leaf[ci].ravel() for leaf in leaves])
                vec = inject_corruption(vec, int(kinds[ci]), faults.rngs[ci])
                off = 0
                for leaf in leaves:
                    size = leaf[ci].size
                    leaf[ci] = vec[off:off + size].reshape(leaf[ci].shape)
                    off += size
                with np.errstate(over="ignore", invalid="ignore"):
                    norms_sq[ci] = float(sum(
                        np.sum(leaf[ci].astype(np.float64) ** 2)
                        for leaf in leaves))
                finite[ci] = all(np.isfinite(leaf[ci]).all() for leaf in leaves)
            grads = jax.tree_util.tree_unflatten(
                jax.tree_util.tree_structure(grads), leaves)

        accept = gate_mask(norms_sq, finite, faults.gate_factor)
        n_rejected = int((~accept).sum())
        if accept.any():
            self.params, self.opt_state, gnorm = apply_fn(
                self.params, self.opt_state, grads,
                jax.numpy.asarray(accept.astype(np.float32)))
            gnorm = float(gnorm)
            skipped = False
        else:
            # every update rejected: hold the global model for this round
            gnorm = 0.0
            skipped = True
        return {
            "round": self.round_idx,
            "loss": float(jax.numpy.mean(losses)),
            "grad_norm_sq": gnorm,
            "client_loss": np.asarray(losses),
            "bits": np.asarray(bits).copy(),
            "accepted": accept,
            "n_rejected": n_rejected,
            "gate_skipped": skipped,
        }

    def evaluate(self, loss_fn, batch) -> dict:
        loss, aux = jax.jit(loss_fn)(self.params, batch, jax.random.PRNGKey(0))
        out = {"loss": float(loss)}
        out.update({k: float(v) for k, v in aux.items()})
        return out
