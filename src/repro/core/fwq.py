"""FWQ — Flexible Weight-Quantized federated learning (paper Algorithm 1).

This is the paper's primary contribution as a composable JAX module.  A round:

    1.  server broadcasts full-precision ``w^r``                     (line 2)
    2.  client i quantizes:  ``w~_i = Q_i(w^r)``  (SR, bit-width q_i) (line 4)
    3.  client i computes    ``g_i = (1/M) sum grad f(w~_i)``         (line 6)
        — the gradient is *evaluated at* the quantized weights; SR is
        piecewise-constant so there is no gradient through Q itself.
    4.  server aggregates    ``G = (1/N) sum_i g_i``  in full precision
        and applies          ``w^{r+1} = w^r - eta * G``         (lines 10-11)

The per-client bit-widths arrive as a *traced* vector
``delta[i] = 1/(2**q_i - 1)`` so the compiled program is reused across every
strategy the GBD layer emits (no recompilation when ``q`` changes between
rounds — critical at pod scale).

Two integration modes:

* ``tree``   — quantize the whole parameter tree per client up front
  (simple; right for the CIFAR-scale paper repro where the tree is small).
* ``inline`` — the model quantizes each weight at its use site via a
  ``param_transform`` callback, keeping per-client quantized copies transient
  inside the layer scan (right for FSDP/TP-sharded multi-billion-param archs;
  see DESIGN.md §4).

Distribution: the client axis of ``batch``/``delta``/``rng`` is laid out on
the mesh ``("pod","data")`` axes by the caller's ``in_shardings``; the mean
over clients lowers to the cross-data-parallel all-reduce of Algorithm 1
line 10.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import quantization as quantlib

Params = Any
Batch = Any
# client_loss_fn(params, batch_i, delta_i, rng_i) -> (loss, aux)
ClientLossFn = Callable[[Params, Batch, jnp.ndarray, jax.Array], tuple[jnp.ndarray, Any]]


class FWQMetrics(NamedTuple):
    loss: jnp.ndarray              # mean client loss
    grad_norm_sq: jnp.ndarray      # ||G||^2 of the aggregated gradient
    client_grad_norm_sq: jnp.ndarray  # (n_clients,) per-client ||g_i||^2
    client_loss: jnp.ndarray       # (n_clients,)


@dataclasses.dataclass(frozen=True)
class FWQConfig:
    n_clients: int
    quantize_mode: str = "tree"        # "tree" | "inline"
    server_in_f32: bool = True         # keep the global model in f32 (paper)
    donate_params: bool = True


def make_tree_quant_loss(
    plain_loss_fn: Callable[[Params, Batch, jax.Array], tuple[jnp.ndarray, Any]],
    *,
    exempt=quantlib.default_exempt,
) -> ClientLossFn:
    """Wrap a plain loss into a client loss that tree-quantizes first (mode=tree)."""

    def client_loss(params, batch, delta, rng):
        qkey, lkey = jax.random.split(rng)
        qparams = quantlib.quantize_tree(params, delta, qkey, exempt=exempt)
        return plain_loss_fn(qparams, batch, lkey)

    return client_loss


def make_fwq_round(
    client_loss_fn: ClientLossFn,
    opt_update: Callable,          # (grads, opt_state, params) -> (updates, opt_state)
    cfg: FWQConfig,
):
    """Build the jittable FWQ round function.

    Returns ``fl_round(params, opt_state, batch, delta, rng) ->
    (params, opt_state, FWQMetrics)`` where

    * ``batch``  — pytree whose leaves have leading dim ``n_clients``
    * ``delta``  — (n_clients,) f32, ``s * Delta_{q_i}`` resolutions (0 = fp)
    * ``rng``    — single key; folded per client deterministically
    """

    def client_grad(params, batch_i, delta_i, rng_i):
        # Algorithm 1 line 6: gradient evaluated AT Q_i(w).  The quantization
        # happens inside client_loss_fn (tree mode) or inside the model
        # (inline mode); either way grad flows to the *quantized values*,
        # which numerically equals d f / d w~ evaluated at w~ = Q(w).
        (loss, _aux), grads = jax.value_and_grad(
            lambda p: client_loss_fn(p, batch_i, delta_i, rng_i), has_aux=True
        )(params)
        gsq = sum(jnp.vdot(g, g).real for g in jax.tree_util.tree_leaves(grads))
        return loss, grads, gsq

    def fl_round(params, opt_state, batch, delta, rng):
        n = delta.shape[0]  # cohort size from the data: elastic across rounds
        client_keys = jax.vmap(lambda i: jax.random.fold_in(rng, i))(jnp.arange(n))
        losses, grads, gsqs = jax.vmap(
            client_grad, in_axes=(None, 0, 0, 0)
        )(params, batch, delta, client_keys)
        # Server aggregation, full precision (line 10).  Mean over the client
        # axis lowers to an all-reduce across the ("pod","data") mesh axes.
        G = jax.tree_util.tree_map(
            lambda g: jnp.mean(g.astype(jnp.float32), axis=0), grads
        )
        updates, opt_state = opt_update(G, opt_state, params)
        params = jax.tree_util.tree_map(lambda p, u: (p + u).astype(p.dtype), params, updates)
        gnorm = sum(jnp.vdot(g, g).real for g in jax.tree_util.tree_leaves(G))
        metrics = FWQMetrics(
            loss=jnp.mean(losses),
            grad_norm_sq=gnorm,
            client_grad_norm_sq=gsqs,
            client_loss=losses,
        )
        return params, opt_state, metrics

    return fl_round


def make_fwq_client_grads(client_loss_fn: ClientLossFn):
    """Phase 1 of a *gated* round: per-client losses/grads, no aggregation.

    The resilient executor (fault injection + aggregation gate) needs the
    per-client updates on the host before the server step; pairing this with
    :func:`make_fwq_apply` splits :func:`make_fwq_round` at exactly the
    uplink boundary of Algorithm 1 (between lines 6 and 10).
    """

    def grads_fn(params, batch, delta, rng):
        n = delta.shape[0]
        client_keys = jax.vmap(lambda i: jax.random.fold_in(rng, i))(jnp.arange(n))

        def client_grad(params, batch_i, delta_i, rng_i):
            (loss, _aux), grads = jax.value_and_grad(
                lambda p: client_loss_fn(p, batch_i, delta_i, rng_i), has_aux=True
            )(params)
            gsq = sum(jnp.vdot(g, g).real for g in jax.tree_util.tree_leaves(grads))
            finite = jnp.stack([jnp.all(jnp.isfinite(g))
                                for g in jax.tree_util.tree_leaves(grads)]).all()
            return loss, grads, gsq, finite

        return jax.vmap(client_grad, in_axes=(None, 0, 0, 0))(
            params, batch, delta, client_keys)

    return grads_fn


def make_fwq_apply(opt_update: Callable):
    """Phase 2 of a gated round: masked aggregation + server step.

    ``accept`` is an (n_clients,) 0/1 mask from the aggregation gate;
    rejected clients are excluded via ``where`` *before* the sum (a NaN
    times zero is still NaN) and survivors are reweighted by 1/n_accepted —
    the unbiased mean over the cohort that actually delivered valid updates.
    """

    def apply_fn(params, opt_state, grads, accept):
        w = accept.astype(jnp.float32)
        denom = jnp.maximum(jnp.sum(w), 1.0)

        def agg(g):
            gf = g.astype(jnp.float32)
            mask = w.reshape((-1,) + (1,) * (gf.ndim - 1))
            return jnp.sum(jnp.where(mask > 0, gf, 0.0), axis=0) / denom

        G = jax.tree_util.tree_map(agg, grads)
        updates, opt_state = opt_update(G, opt_state, params)
        params = jax.tree_util.tree_map(
            lambda p, u: (p + u).astype(p.dtype), params, updates)
        gnorm = sum(jnp.vdot(g, g).real for g in jax.tree_util.tree_leaves(G))
        return params, opt_state, gnorm

    return apply_fn


def delta_for_clients(
    bits,
    *,
    scale: float | jnp.ndarray = 1.0,
    n_clients: int | None = None,
) -> jnp.ndarray:
    """(n_clients,) resolutions ``s * Delta_{q_i}`` from a bit-width vector.

    ``bits`` is a per-client bit vector, or a
    :class:`repro.api.precision.PrecisionPolicy` (pass ``n_clients`` then —
    the policy's ``weights`` role supplies the per-device bits).

    ``scale`` defaults to 1.0 because :func:`repro.core.quantization.sr_quantize`
    applies the per-tensor ``s = ||w||_inf`` internally; pass an explicit scale
    only for pre-normalized weight schemes.
    """
    if hasattr(bits, "bits_vector"):  # PrecisionPolicy
        if n_clients is None:
            raise ValueError("delta_for_clients(policy) needs n_clients=")
        bits = bits.bits_vector(n_clients)
    return (jnp.asarray(scale, jnp.float32)
            * quantlib.delta_from_bits(jnp.asarray(bits))).astype(jnp.float32)


# ---------------------------------------------------------------------------
# Inline mode: weight transform threaded through model forward passes.
# ---------------------------------------------------------------------------


def make_inline_quantizer(delta: jnp.ndarray, rng: jax.Array, *, exempt=quantlib.default_exempt):
    """A ``param_transform(path, w) -> w_q`` callback for inline-mode models.

    ``delta``/``rng`` are the *per-client* scalar/key (already vmapped by the
    round function).  Each call site derives its own SR key from a stable hash
    of the parameter path so quantization noise is i.i.d. across tensors but
    deterministic per (client, round).
    """

    def transform(path: str, w: jnp.ndarray) -> jnp.ndarray:
        if exempt is not None and exempt(path, w):
            return w
        site_key = jax.random.fold_in(rng, _stable_hash(path))
        return quantlib.sr_quantize(w, delta, site_key)

    return transform


@functools.lru_cache(maxsize=4096)
def _stable_hash(path: str) -> int:
    h = 2166136261
    for ch in path.encode():
        h = ((h ^ ch) * 16777619) & 0x7FFFFFFF
    return h


def identity_transform(path: str, w: jnp.ndarray) -> jnp.ndarray:
    """Full-precision baseline transform (no quantization)."""
    return w
