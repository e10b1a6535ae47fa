"""Pallas TPU kernels: flash attention forward + batched paged flash-decode.

``flash_attention_kernel`` is the prefill path: causal online softmax with no
S x S materialization; running (max, sum, acc) live in VMEM scratch across
the KV grid dimension (TPU grids execute the last axis sequentially, so
scratch carries state between k-steps).  Fully-masked (k-block above the
diagonal) tiles are skipped with ``pl.when`` — for causal attention that
halves the work.

``flash_decode_kernel`` is the long-context decode path: one query token per
slot against a PAGED KV cache.  The per-slot page table rides in as a
scalar-prefetch operand (``pltpu.PrefetchScalarGridSpec``), so the BlockSpec
index map dereferences it to DMA exactly the pages each slot owns — K/V
stream page-by-page from HBM in logical order, honoring per-sequence lengths,
with the same online softmax carried in scratch.  It returns unnormalized
``(acc, m, l)`` partials so sequence-parallel launches can merge shards with
a distributed online softmax.

Both match their jnp references to fp32 tolerance.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.spec import (
    BlockOperand,
    KernelSpec,
    ScalarOperand,
    ScratchSpec,
)

DEFAULT_BLOCKS = (256, 256)  # (block_q, block_k)

_NEG_INF = -1e30


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


# Index maps are module-level so the pallas_call and the static-checker
# metadata (attention_spec / decode_spec) share one definition.


def _q_map(b, i, j):
    return (b, i, 0)


def _kv_map(b, i, j):
    return (b, j, 0)


def _body(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
          *, scale: float, block_q: int, block_k: int, n_k: int, causal: bool,
          s_valid: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # causal skip: this k-block starts after the last query of the q-block.
    # Blocks entirely past the valid (unpadded) key range are skipped too.
    run = ik * block_k < s_valid
    if causal:
        run = jnp.logical_and(run, ik * block_k <= iq * block_q + block_q - 1)

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale            # (bq, d)
        k = k_ref[0].astype(jnp.float32)                    # (bk, d)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (bq, bk)
        cols = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        if causal:
            rows = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            s = jnp.where(cols <= rows, s, _NEG_INF)
        if s_valid % block_k:
            # ragged sequence: mask the zero-padded tail keys
            s = jnp.where(cols < s_valid, s, _NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ik == n_k - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention_kernel(q, k, v, *, causal: bool = True,
                           blocks=DEFAULT_BLOCKS, interpret=False,
                           s_valid: int | None = None):
    """q, k, v: (BH, S, D) — batch*heads flattened.  Returns (BH, S, D).

    ``s_valid``: true sequence length when the inputs were zero-padded to a
    block multiple; padded keys are masked inside the kernel (padded query
    rows produce garbage the caller slices off).
    """
    BH, S, D = q.shape
    bq, bk = blocks
    bq, bk = min(bq, S), min(bk, S)
    grid = (BH, pl.cdiv(S, bq), pl.cdiv(S, bk))
    scale = D ** -0.5
    return pl.pallas_call(
        functools.partial(_body, scale=scale, block_q=bq, block_k=bk,
                          n_k=grid[2], causal=causal,
                          s_valid=S if s_valid is None else s_valid),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, D), _q_map),
            pl.BlockSpec((1, bk, D), _kv_map),
            pl.BlockSpec((1, bk, D), _kv_map),
        ],
        out_specs=pl.BlockSpec((1, bq, D), _q_map),
        out_shape=jax.ShapeDtypeStruct((BH, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),   # running max
            pltpu.VMEM((bq, 1), jnp.float32),   # running sum
            pltpu.VMEM((bq, D), jnp.float32),   # output accumulator
        ],
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)


def attention_spec(BH: int, S: int, D: int, *,
                   blocks=DEFAULT_BLOCKS) -> KernelSpec:
    """Static BlockSpec metadata for the wrapper-level flash-attention call.

    ``S`` is the RAW sequence length; the spec mirrors
    :func:`repro.kernels.ops.flash_attention`'s padding to the 128-aligned
    block multiple.
    """
    bq = bk = min(blocks[0], _round_up(S, 128))
    Sp = _round_up(S, bq)
    grid = (BH, Sp // bq, Sp // bk)
    return KernelSpec(
        name="flash_attention",
        source="flash_attention.py:flash_attention_kernel",
        grid=grid,
        inputs=(
            BlockOperand("q", (BH, Sp, D), (1, bq, D), _q_map),
            BlockOperand("k", (BH, Sp, D), (1, bk, D), _kv_map),
            BlockOperand("v", (BH, Sp, D), (1, bk, D), _kv_map),
        ),
        outputs=(BlockOperand("out", (BH, Sp, D), (1, bq, D), _q_map),),
        scratch=(
            ScratchSpec("m", (bq, 1), "float32"),
            ScratchSpec("l", (bq, 1), "float32"),
            ScratchSpec("acc", (bq, D), "float32", binds="out"),
        ),
    )


# ---------------------------------------------------------------------------
# Batched paged flash-decode
# ---------------------------------------------------------------------------


def _decode_body(pt_ref, len_ref, q_ref, k_ref, v_ref, acc_out, m_out, l_out,
                 m_ref, l_ref, acc_ref, *, scale: float, page: int,
                 n_pmax: int, n_kv: int):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # skip pages past the slot's length and unallocated (-1) table entries;
    # the index map clamps -1 to page 0 for the DMA, but the compute guard
    # means that page's contents are never read into the softmax.
    pid = pt_ref[b * n_pmax + j]
    n_valid = len_ref[b]

    @pl.when(jnp.logical_and(pid >= 0, j * page < n_valid))
    def _compute():
        # one DMA brings every KV head of the page; heads are walked with
        # static indices (KV is small), each a (G, hd) x (page, hd) problem
        for h in range(n_kv):
            q = q_ref[0, h].astype(jnp.float32) * scale           # (G, hd)
            k = k_ref[0, :, h, :].astype(jnp.float32)             # (page, hd)
            v = v_ref[0, :, h, :].astype(jnp.float32)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            cols = j * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(cols < n_valid, s, _NEG_INF)           # (G, page)
            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * corr + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[h] = acc_ref[h] * corr + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[h] = m_new

    @pl.when(j == n_pmax - 1)
    def _finish():
        acc_out[0] = acc_ref[...]
        m_out[0] = m_ref[...]
        l_out[0] = l_ref[...]


def _decode_maps(n_pmax: int):
    """The decode grid's index maps, closed over the page-table stride.

    Shared by the ``pallas_call`` (which passes the prefetched scalars
    ``pt``/``ln``) and :func:`decode_spec` (which binds a concrete table).
    ``kv_map`` clamps unallocated (-1) entries to page 0; the kernel body's
    validity guard keeps that page's contents out of the softmax.
    """

    def q_map(b, j, pt, ln):
        return (b, 0, 0, 0)

    def kv_map(b, j, pt, ln):
        return (jnp.maximum(pt[b * n_pmax + j], 0), 0, 0, 0)

    return q_map, kv_map


def flash_decode_kernel(q, k_pages, v_pages, page_table, lengths, *,
                        interpret=False):
    """One decode token per slot against a paged KV cache.

    ``q``: (B, KV, G, hd) — q heads grouped under their KV head (GQA).
    ``k_pages``/``v_pages``: (N_pool, page, KV, hd) shared page pool (f32 or
    bf16 — the ``PrecisionPolicy.kv_cache`` storage dtype).
    ``page_table``: (B, n_pmax) int32, -1 = unallocated.
    ``lengths``: (B,) int32 — valid tokens per slot in local coordinates.

    Grid is (B, n_pmax) with the page axis innermost (sequential on TPU, so
    the online-softmax scratch carries across a slot's pages); the page
    table and lengths are scalar-prefetched so each k/v BlockSpec can DMA the
    pool row the table names.  A k/v block is one whole page, all KV heads
    included: its trailing (KV, hd) dims equal the pool's, which is what the
    TPU lowering requires of a block whose KV extent is below the sublane
    tile.  Returns UNNORMALIZED fp32 partials
    ``(acc (B,KV,G,hd), m (B,KV,G,1), l (B,KV,G,1))`` — normalize with
    ``acc / max(l, eps)``, or pmax/psum-merge across sequence-parallel shards
    first.
    """
    B, KV, G, hd = q.shape
    page = k_pages.shape[1]
    n_pmax = page_table.shape[1]
    scale = hd ** -0.5
    q_map, kv_map = _decode_maps(n_pmax)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_pmax),
        in_specs=[
            pl.BlockSpec((1, KV, G, hd), q_map),
            pl.BlockSpec((1, page, KV, hd), kv_map),
            pl.BlockSpec((1, page, KV, hd), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, KV, G, hd), q_map),
            pl.BlockSpec((1, KV, G, 1), q_map),
            pl.BlockSpec((1, KV, G, 1), q_map),
        ],
        scratch_shapes=[
            pltpu.VMEM((KV, G, 1), jnp.float32),    # running max
            pltpu.VMEM((KV, G, 1), jnp.float32),    # running sum
            pltpu.VMEM((KV, G, hd), jnp.float32),   # output accumulator
        ],
    )
    return pl.pallas_call(
        functools.partial(_decode_body, scale=scale, page=page,
                          n_pmax=n_pmax, n_kv=KV),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, KV, G, hd), jnp.float32),
                   jax.ShapeDtypeStruct((B, KV, G, 1), jnp.float32),
                   jax.ShapeDtypeStruct((B, KV, G, 1), jnp.float32)],
        interpret=interpret,
        name="flash_decode",
    )(page_table.reshape(-1), lengths, q, k_pages, v_pages)


def decode_spec(B: int, KV: int, G: int, hd: int, *, page: int, n_pool: int,
                page_table, lengths) -> KernelSpec:
    """Static BlockSpec metadata for one flash-decode launch.

    ``page_table`` (B, n_pmax) / ``lengths`` (B,) are CONCRETE int arrays
    (numpy is fine): the checker enumerates the same table-dereferencing
    index maps the scalar-prefetch machinery would, so an index pointing
    outside the page pool is a static finding, not a silent DMA.  The G
    axis must already be padded to the fp32 sublane minimum (8), as
    :func:`repro.kernels.ops.flash_paged_decode` does.
    """
    import numpy as np

    pt = np.asarray(page_table, dtype=np.int64)
    ln = np.asarray(lengths, dtype=np.int64)
    n_pmax = pt.shape[1]
    pt_flat = pt.reshape(-1)
    q_map, kv_map = _decode_maps(n_pmax)

    def _bind(m):
        return lambda b, j: m(b, j, pt_flat, ln)

    grid = (B, n_pmax)
    # pool rows are addressed through the table: repeated / skipped rows are
    # legal, so the k/v pools check OOB only ("any" coverage)
    return KernelSpec(
        name="flash_decode",
        source="flash_attention.py:flash_decode_kernel",
        grid=grid,
        inputs=(
            BlockOperand("q", (B, KV, G, hd), (1, KV, G, hd), _bind(q_map)),
            BlockOperand("k_pages", (n_pool, page, KV, hd),
                         (1, page, KV, hd), _bind(kv_map), coverage="any"),
            BlockOperand("v_pages", (n_pool, page, KV, hd),
                         (1, page, KV, hd), _bind(kv_map), coverage="any"),
        ),
        outputs=(
            BlockOperand("acc", (B, KV, G, hd), (1, KV, G, hd), _bind(q_map)),
            BlockOperand("m", (B, KV, G, 1), (1, KV, G, 1), _bind(q_map)),
            BlockOperand("l", (B, KV, G, 1), (1, KV, G, 1), _bind(q_map)),
        ),
        scratch=(
            ScratchSpec("m_run", (KV, G, 1), "float32"),
            ScratchSpec("l_run", (KV, G, 1), "float32"),
            ScratchSpec("acc_run", (KV, G, hd), "float32", binds="acc"),
        ),
        # the scalar-prefetch contract: kv_map clamps -1 to page 0 and the
        # compute guard masks it, so -1 is legal; anything >= n_pool would
        # DMA outside the page pool regardless of masking.  Lengths bound
        # the compute guard: at most every owned page fully used.
        scalars=(
            ScalarOperand("page_table", pt_flat, -1, n_pool - 1,
                          note="-1 = unallocated (masked); valid pool rows "
                               f"are [0, {n_pool})"),
            ScalarOperand("lengths", ln, 0, n_pmax * page,
                          note=f"{n_pmax} pages x {page} slots owned at "
                               "most"),
        ),
    )
