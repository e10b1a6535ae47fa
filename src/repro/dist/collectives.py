"""Mesh-axis context and collectives for the shard_map model zoo.

:class:`AxisCtx` is the one object threaded through every layer (via
``ParamCtx.ctx``): it names the mesh axes a computation runs under and turns
them into sizes, indices, and collectives.  All model code is *local
per-shard* code (Megatron-JAX style), so the context is how a layer asks
"which tensor-parallel rank am I" or "all-reduce this over the clients".

Design rules
------------
* **Sizes are static.**  ``ctx.dp`` / ``ctx.tp`` / ``ctx.fsdp`` use the
  constant-folding of ``lax.psum(1, axis)``, which inside ``shard_map``
  returns a Python int.  That staticness is load-bearing: the FSDP
  participation rules in :mod:`repro.models.common` branch on these values
  at trace time.  Outside any mesh context every size is 1 and every index
  is 0, so the same model code runs unsharded (unit tests, ``eval_shape``
  probes) with all collectives degenerating to identities.
* **Flattened batch index.**  Multi-axis data parallelism (``("pod",
  "data")``) is flattened row-major by ``lax.axis_index`` with the axis
  tuple; ``lax.all_gather`` over the same tuple tiles in the identical
  order, so the FSDP slice/gather pair in ``models/common.py`` round-trips
  by construction.
* **Quantized gradient all-reduce.**  :func:`quantized_psum_batch` is the
  paper's Eq. 1 stochastic-rounding quantizer applied to *model updates on
  the wire* (cf. arXiv:2402.12957, arXiv:1911.02417): clients agree on a
  shared grid via a ``pmax`` of the per-client scale, SR-quantize onto
  integer codes, ``psum`` the codes (integers sum exactly — no
  re-quantization error at the server), and dequantize to the mean.
  Unbiased for every bit-width because SR is unbiased per client.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.quantization import FULL_PRECISION_BITS, _sr_round


def _axis_size(names: tuple[str, ...]) -> int:
    """Static product of the named axis sizes; 1 when unbound/empty.

    ``lax.psum`` of a Python constant is constant-folded to ``size * x``
    inside shard_map/pmap, so this is a trace-time int, not a tracer.
    """
    if not names:
        return 1
    try:
        return int(jax.lax.psum(1, names if len(names) > 1 else names[0]))
    except NameError:      # outside any mesh context (eval_shape, unit tests)
        return 1


def _axis_index(names: tuple[str, ...]):
    """Flattened (row-major) index over ``names``; 0 when unbound/empty."""
    if not names:
        return 0
    try:
        return jax.lax.axis_index(names if len(names) > 1 else names[0])
    except NameError:
        return 0


@dataclasses.dataclass(frozen=True)
class AxisCtx:
    """Named mesh axes of one launch configuration.

    ``batch_axes``: data-parallel axes — one FL client per group.
    ``model_axis``: tensor-parallel axis (None = no TP).
    ``fsdp_axes``:  axes parameters are fully-sharded over (in practice the
    batch axes: FSDP rides on data parallelism).
    """

    batch_axes: tuple[str, ...]
    model_axis: str | None
    fsdp_axes: tuple[str, ...]

    # --- static sizes ----------------------------------------------------
    @property
    def dp(self) -> int:
        """Number of data-parallel groups (= FL clients) in scope."""
        return _axis_size(tuple(self.batch_axes))

    @property
    def tp(self) -> int:
        return _axis_size((self.model_axis,) if self.model_axis else ())

    @property
    def fsdp(self) -> int:
        return _axis_size(tuple(self.fsdp_axes))

    # --- indices ---------------------------------------------------------
    def dp_index(self):
        """Flattened data-parallel rank (client id); 0 outside a mesh."""
        return _axis_index(tuple(self.batch_axes))

    def tp_index(self):
        return _axis_index((self.model_axis,) if self.model_axis else ())

    # --- model-axis collectives -----------------------------------------
    def psum_model(self, x):
        if self.model_axis is None:
            return x
        return jax.lax.psum(x, self.model_axis)

    def pmean_model(self, x):
        if self.model_axis is None:
            return x
        return jax.lax.pmean(x, self.model_axis)

    def all_gather_model(self, x, *, axis: int):
        if self.model_axis is None:
            return x
        return jax.lax.all_gather(x, self.model_axis, axis=axis, tiled=True)

    def psum_scatter_model(self, x, *, axis: int):
        if self.model_axis is None:
            return x
        return jax.lax.psum_scatter(x, self.model_axis,
                                    scatter_dimension=axis, tiled=True)

    # --- batch/FSDP collectives -----------------------------------------
    def psum_batch(self, x):
        if not self.batch_axes:
            return x
        return jax.lax.psum(x, tuple(self.batch_axes))

    def pmean_batch(self, x):
        if not self.batch_axes:
            return x
        return jax.lax.pmean(x, tuple(self.batch_axes))

    def gather_fsdp(self, x, *, axis: int):
        """Tiled all-gather of FSDP-sharded storage along ``axis``.

        The transpose under autodiff is a reduce-scatter, which is what
        makes FSDP gradients come back sharded for free (DESIGN.md §4).
        """
        if self.fsdp == 1:
            return x
        names = tuple(self.fsdp_axes)
        return jax.lax.all_gather(x, names if len(names) > 1 else names[0],
                                  axis=axis, tiled=True)


def code_bound(bits: int) -> int:
    """Largest |code| a ``bits``-wide SR quantizer can emit: ``2^bits - 1``.

    This is the *exactness contract* between the runtime and the static
    analyzer: :func:`quantized_psum_batch` clips its codes to
    ``±code_bound(bits)`` before the integer all-reduce, and both
    :func:`wire_dtype` (runtime) and ``repro.analyze`` (static, via the
    interval interpreter and the analytic per-cell proof) reason from the
    same bound — ``n_clients * code_bound(bits)`` must fit the accumulator.
    """
    return 2 ** int(bits) - 1


def wire_dtype(bits: int, n_clients: int):
    """Narrowest signed integer dtype whose sum of codes is exact.

    Per-client codes lie in ``[-code_bound(bits), code_bound(bits)]``; an
    all-reduce over ``n_clients`` needs the accumulator to hold
    ``n * code_bound(bits)``.  This is the dtype that actually crosses the
    wire, so lower ``comm`` bits shrink the measured all-reduce bytes
    (s8/s16 vs f32 in the HLO) instead of always paying the int32
    accumulator.
    """
    need = n_clients * code_bound(bits)
    if need <= jnp.iinfo(jnp.int8).max:
        return jnp.int8
    if need <= jnp.iinfo(jnp.int16).max:
        return jnp.int16
    if need <= jnp.iinfo(jnp.int32).max:
        return jnp.int32
    # int64 is no escape hatch: without jax_enable_x64 it silently becomes
    # int32 again, so refuse rather than wrap around
    raise ValueError(
        f"comm bits={bits} with {n_clients} clients needs an accumulator "
        f"holding {need} > int32 max; lower the bit-width (<= 16 is always "
        "safe below 32768 clients) or use 32 (uncompressed)")


def envelope_wire_dtype(bits_options, n_clients: int):
    """Widest accumulator ANY bit-width in an adaptive program's comm
    envelope needs, or ``None`` when the whole envelope is uncompressed.

    Calls :func:`wire_dtype` on every compressed member, so it raises if any
    round of any schedule the program can emit would overflow the int32
    accumulator — proving the envelope proves the whole run.
    """
    compressed = [b for b in sorted({int(b) for b in bits_options})
                  if b < FULL_PRECISION_BITS]
    if not compressed:
        return None
    dts = [wire_dtype(b, n_clients) for b in compressed]
    return max(dts, key=lambda d: jnp.dtype(d).itemsize)


def _nonfinite_guard(gf, on_nonfinite: str, ax=()):
    """Keep NaN/Inf gradients out of the wire quantizer.

    A non-finite leaf would poison the shared scale (``pmax`` of Inf/NaN)
    and quantize every client's codes into garbage *silently*.  ``"raise"``
    surfaces it as a runtime error via a host callback whose result is tied
    into the dataflow (so DCE cannot drop the check); ``"saturate"`` maps
    NaN to 0 and clamps ±Inf to the client's largest finite magnitude.

    ``ax`` names the batch axes when called inside a collective: the bad
    count is psum'd over them first so every shard reaches the same
    verdict.  Without this the clean shards enter the scale ``pmax`` while
    the poisoned shards raise in the callback, and the all-reduce
    rendezvous deadlocks waiting for participants that will never arrive.
    """
    if on_nonfinite == "raise":
        bad = jnp.sum(jnp.where(jnp.isfinite(gf), 0, 1))
        if ax:
            bad = jax.lax.psum(bad, tuple(ax))

        def _host_check(nbad):
            if int(nbad):
                raise FloatingPointError(
                    f"quantized_psum_batch: {int(nbad)} non-finite gradient "
                    "values reached the wire quantizer (pass "
                    "on_nonfinite='saturate' to clamp instead)")
            return np.int32(0)

        token = jax.pure_callback(
            _host_check, jax.ShapeDtypeStruct((), jnp.int32), bad)
        # fold the (always-zero) token into the values so the callback is a
        # real dependency of the result, not dead code
        return gf + token.astype(jnp.float32)
    if on_nonfinite == "saturate":
        fmax = jnp.max(jnp.where(jnp.isfinite(gf), jnp.abs(gf), 0.0))
        return jnp.clip(jnp.where(jnp.isnan(gf), 0.0, gf), -fmax, fmax)
    raise ValueError(f"on_nonfinite must be 'raise' or 'saturate', "
                     f"got {on_nonfinite!r}")


def quantized_psum_batch(axes: AxisCtx, grad, rng, bits, *,
                         on_nonfinite: str = "raise"):
    """SR-quantized all-reduce **mean** of ``grad`` over the batch axes.

    Drop-in replacement for ``lax.pmean(grad, batch_axes)`` that moves
    ``bits``-wide integer codes on the wire instead of f32:

    1. shared grid: ``s = pmax_i max|g_i|``, resolution ``delta = 1/(2^b-1)``
       (paper Eq. 1 with the scale agreed across clients so codes are
       summable);
    2. each client stochastically rounds ``g_i / (s*delta)`` to integers
       with an independent key (folded by client id) — unbiased per Eq. 1;
    3. ``psum`` the codes: integer sums are exact, so the only error is the
       per-client SR noise — the server introduces none;
    4. dequantize and divide by the client count -> the mean.

    ``bits >= 32`` bypasses quantization (exact ``pmean``); a 1-group
    context is a no-op.  Returns E[out] == pmean(grad) for every bit-width.

    ``on_nonfinite`` guards the quantizer against NaN/Inf inputs (see
    :func:`_nonfinite_guard`): ``"raise"`` (default) fails loudly at
    runtime, ``"saturate"`` clamps and continues.
    """
    n = axes.dp
    if n == 1:
        return grad                       # single client: nothing to reduce
    ax = tuple(axes.batch_axes)
    if int(bits) >= FULL_PRECISION_BITS:
        return jax.lax.pmean(grad, ax)    # full precision: exact mean

    gf = _nonfinite_guard(grad.astype(jnp.float32), on_nonfinite, ax)
    s = jax.lax.pmax(jnp.max(jnp.abs(gf)), ax)
    s = jnp.where(s > 0, s, 1.0)
    lim = float(code_bound(int(bits)))
    step = s / lim                        # = s * Delta_q, the grid pitch
    ckey = jax.random.fold_in(rng, axes.dp_index())
    codes = _sr_round(gf / step, ckey)
    codes = jnp.clip(codes, -lim, lim)    # numeric guard; |t| <= lim already
    # Integer accumulation is exact as long as the dtype holds
    # n * (2^bits - 1) — wire_dtype picks the narrowest such dtype (s8/s16/
    # s32), so the all-reduce moves bits-scaled bytes instead of a fixed
    # int32 (f32 would round past 2^24: reachable at bits=16, ~257 clients).
    total = jax.lax.psum(codes.astype(wire_dtype(int(bits), n)), ax)
    return ((total.astype(jnp.float32) * step) / n).astype(grad.dtype)
