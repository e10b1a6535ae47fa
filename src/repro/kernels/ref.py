"""Pure-jnp oracles for every Pallas kernel (the ``assert_allclose`` targets).

The kernels must match these references bit-for-bit where the math is exact
(sr_quant with shared uniforms) or to fp32 tolerance (matmul/attention).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def sr_quant_fake_ref(w: jnp.ndarray, u: jnp.ndarray, step: jnp.ndarray) -> jnp.ndarray:
    """Stochastic rounding onto a grid of pitch ``step`` (paper Eq. 1).

    w, u: (M, N) f32 (u ~ U[0,1) supplied by the caller — kernel and ref share
    the same randomness); step: scalar f32 (s * Delta_q); step == 0 bypasses.
    """
    safe = jnp.where(step > 0, step, 1.0)
    t = w / safe
    lower = jnp.floor(t)
    q = (lower + (u < (t - lower)).astype(w.dtype)) * safe
    # clamp to the representable range [-s, s]; s = step / Delta implied by
    # caller, so clamp against the max|w| the caller scaled with:
    return jnp.where(step > 0, q, w)


def sr_quant_pack_ref(w: jnp.ndarray, u: jnp.ndarray, step: jnp.ndarray,
                      lim: int) -> jnp.ndarray:
    """Integer codes version: clip(floor(w/step) + bern, -lim, lim) int8."""
    safe = jnp.where(step > 0, step, 1.0)
    t = w / safe
    lower = jnp.floor(t)
    codes = lower + (u < (t - lower)).astype(w.dtype)
    return jnp.clip(codes, -lim, lim).astype(jnp.int8)


def quant_matmul_ref(x: jnp.ndarray, codes: jnp.ndarray, scale: jnp.ndarray,
                     out_dtype=jnp.float32) -> jnp.ndarray:
    """x (M,K) @ dequant(codes (K,N) int8; w = codes*scale) -> (M,N)."""
    w = codes.astype(jnp.float32) * scale.astype(jnp.float32)
    return jnp.dot(x.astype(jnp.float32), w,
                   preferred_element_type=jnp.float32).astype(out_dtype)


def flash_attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        causal: bool = True) -> jnp.ndarray:
    """q,k,v: (B, H, S, D).  Full-softmax reference, fp32 accumulation."""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        S = q.shape[2]
        mask = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(q.dtype)


def flash_decode_ref(q: jnp.ndarray, k_pages: jnp.ndarray,
                     v_pages: jnp.ndarray, page_table: jnp.ndarray,
                     lengths: jnp.ndarray) -> jnp.ndarray:
    """One decode token per slot against a paged KV cache, normalized.

    q: (B, KV, G, hd); pools (N_pool, page, KV, hd); page_table (B, n_pmax)
    with -1 for unallocated pages; lengths (B,) valid tokens per slot.
    Gathers each slot's pages into a contiguous view and takes a full
    masked softmax.  Returns (B, KV, G, hd) f32.
    """
    B, KV, G, hd = q.shape
    page = k_pages.shape[1]
    n_pmax = page_table.shape[1]
    pids = jnp.maximum(page_table, 0)
    k = k_pages[pids].reshape(B, n_pmax * page, KV, hd).astype(jnp.float32)
    v = v_pages[pids].reshape(B, n_pmax * page, KV, hd).astype(jnp.float32)
    s = jnp.einsum("bhgd,bshd->bhgs", q.astype(jnp.float32), k) * hd ** -0.5
    valid = ((jnp.arange(n_pmax * page)[None, :] < lengths[:, None])
             & jnp.repeat(page_table >= 0, page, axis=1))
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhgs,bshd->bhgd", p, v)
