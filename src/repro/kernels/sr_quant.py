"""Pallas TPU kernel: fused stochastic-rounding quantization (paper Eq. 1).

This is the FWQ hot spot: every client quantizes every weight every round.
The kernel fuses scale-divide + floor + Bernoulli(frac) + snap in one VMEM
pass (vs. ~5 HBM round-trips when left to op-by-op jnp), streaming
``(block_m, block_n)`` tiles HBM->VMEM->HBM.

Randomness is supplied as a pre-generated uniform tensor so the kernel is
bit-exact against :func:`repro.kernels.ref.sr_quant_fake_ref` and portable to
``interpret=True`` on CPU (pltpu PRNG primitives would pin it to real TPUs).

Two variants:
* ``sr_quant_fake_kernel``  — fp values snapped to the grid (training path)
* ``sr_quant_pack_kernel``  — int8 codes (serving path, 4x HBM saving)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK = (256, 512)   # f32 tile: 512 lanes = 4 * 128, 256 sublanes


def _fake_body(w_ref, u_ref, step_ref, o_ref):
    w = w_ref[...]
    u = u_ref[...]
    step = step_ref[0, 0]
    safe = jnp.where(step > 0, step, 1.0)
    t = w / safe
    lower = jnp.floor(t)
    q = (lower + (u < (t - lower)).astype(w.dtype)) * safe
    o_ref[...] = jnp.where(step > 0, q, w)


def _pack_body(w_ref, u_ref, step_ref, o_ref, *, lim: int):
    w = w_ref[...]
    u = u_ref[...]
    step = step_ref[0, 0]
    safe = jnp.where(step > 0, step, 1.0)
    t = w / safe
    lower = jnp.floor(t)
    codes = lower + (u < (t - lower)).astype(w.dtype)
    o_ref[...] = jnp.clip(codes, -lim, lim).astype(jnp.int8)


def _grid_specs(shape, block):
    bm, bn = block
    m, n = shape
    grid = (pl.cdiv(m, bm), pl.cdiv(n, bn))
    tile = pl.BlockSpec((bm, bn), lambda i, j: (i, j))
    scalar = pl.BlockSpec((1, 1), lambda i, j: (0, 0))
    return grid, tile, scalar


def sr_quant_fake_kernel(w, u, step, *, block=DEFAULT_BLOCK, interpret=False):
    """w, u: (M, N) f32; step: (1,1) f32.  Returns grid-snapped f32."""
    grid, tile, scalar = _grid_specs(w.shape, block)
    return pl.pallas_call(
        _fake_body,
        grid=grid,
        in_specs=[tile, tile, scalar],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct(w.shape, w.dtype),
        interpret=interpret,
        name="sr_quant_fake",
    )(w, u, step)


def sr_quant_pack_kernel(w, u, step, *, bits: int = 7, block=DEFAULT_BLOCK,
                         interpret=False):
    """Same, but emits int8 codes in [-(2^bits - 1), 2^bits - 1]."""
    lim = 2**bits - 1
    grid, tile, scalar = _grid_specs(w.shape, block)
    return pl.pallas_call(
        functools.partial(_pack_body, lim=lim),
        grid=grid,
        in_specs=[tile, tile, scalar],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct(w.shape, jnp.int8),
        interpret=interpret,
        name="sr_quant_pack",
    )(w, u, step)
