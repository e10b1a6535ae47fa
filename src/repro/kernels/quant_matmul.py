"""Pallas TPU kernel: int8-weight dequantize-matmul (serving hot spot).

Computes ``x @ (codes * scale)`` streaming the weight as int8: the HBM
traffic on the weight stream is 1/4 of f32 (1/2 of bf16) — exactly the
memory-roofline win the paper's storage argument becomes on a TPU serving
path (decode is weight-bandwidth-bound).

Tiling: grid (M/bm, N/bn, K/bk), K innermost; an f32 VMEM scratch accumulates
partial products; dequantization happens tile-by-tile in VMEM right before
the MXU dot (128-aligned dims).

The index maps are module-level functions shared between the ``pallas_call``
and the :func:`kernel_spec` metadata the static checker
(``repro.analyze.kernel_check``) enumerates — so the checked BlockSpecs are
the lowered BlockSpecs, by construction.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.spec import BlockOperand, KernelSpec, ScratchSpec

DEFAULT_BLOCKS = (256, 256, 512)  # (bm, bn, bk): MXU-aligned multiples of 128


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def choose_blocks(M: int, K: int, N: int, x_dtype=jnp.float32):
    """Adaptive (bm, bn, bk) for a raw (possibly ragged) M x K x N problem.

    Sublane minima: 8 rows for f32 x-blocks, 16 for bf16; 128-lane alignment
    on the contraction/output dims (see pallas_guide §Tiling Constraints).
    Decode-sized M (a handful of rows) gets an 8/16-row block instead of
    padding the batch to 256.
    """
    bm = min(DEFAULT_BLOCKS[0], _round_up(M, 8 if x_dtype == jnp.float32
                                          else 16))
    bn = min(DEFAULT_BLOCKS[1], _round_up(N, 128))
    bk = min(DEFAULT_BLOCKS[2], _round_up(K, 128))
    return bm, bn, bk


def _x_map(i, j, k):
    return (i, k)


def _w_map(i, j, k):
    return (k, j)


def _scale_map(i, j, k):
    return (0, 0)


def _out_map(i, j, k):
    return (i, j)


def _quant_matmul_body(x_ref, c_ref, scale_ref, o_ref, acc_ref, *, n_k: int):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)
    w = c_ref[...].astype(jnp.float32) * scale_ref[0, 0]
    acc_ref[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)

    @pl.when(ik == n_k - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def quant_matmul_kernel(x, codes, scale, *, blocks=DEFAULT_BLOCKS,
                        out_dtype=jnp.float32, interpret=False):
    """x: (M, K) f32/bf16; codes: (K, N) int8; scale: (1,1) f32 -> (M, N)."""
    M, K = x.shape
    K2, N = codes.shape
    assert K == K2, (x.shape, codes.shape)
    bm, bn, bk = blocks
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    grid = (pl.cdiv(M, bm), pl.cdiv(N, bn), pl.cdiv(K, bk))
    return pl.pallas_call(
        functools.partial(_quant_matmul_body, n_k=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), _x_map),
            pl.BlockSpec((bk, bn), _w_map),
            pl.BlockSpec((1, 1), _scale_map),
        ],
        out_specs=pl.BlockSpec((bm, bn), _out_map),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        name="quant_matmul",
    )(x, codes, scale)


def kernel_spec(M: int, K: int, N: int, *, x_dtype=jnp.float32,
                blocks=None) -> KernelSpec:
    """Static BlockSpec metadata for the wrapper-level call at (M, K, N).

    Mirrors :func:`repro.kernels.ops.quant_matmul` exactly: block choice via
    :func:`choose_blocks`, operands zero-padded to block multiples.
    """
    bm, bn, bk = blocks if blocks is not None else choose_blocks(
        M, K, N, x_dtype)
    Mp, Kp, Np = _round_up(M, bm), _round_up(K, bk), _round_up(N, bn)
    grid = (Mp // bm, Np // bn, Kp // bk)
    return KernelSpec(
        name="quant_matmul",
        source="quant_matmul.py:quant_matmul_kernel",
        grid=grid,
        inputs=(
            BlockOperand("x", (Mp, Kp), (bm, bk), _x_map),
            BlockOperand("codes", (Kp, Np), (bk, bn), _w_map),
            BlockOperand("scale", (1, 1), (1, 1), _scale_map,
                         coverage="any"),
        ),
        outputs=(BlockOperand("out", (Mp, Np), (bm, bn), _out_map),),
        scratch=(ScratchSpec("acc", (bm, bn), "float32", binds="out"),),
    )
