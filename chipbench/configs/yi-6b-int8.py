"""yi-6b int8: the plain reference and the operation and byte counts.

Sizes come from ``yi-6b-int8.json`` beside this file (or a smaller dict of
the same keys in the tests).  Nothing here imports the program: the
reference builds the weights from the seed the way the published
initialisation is documented in the program's model card (truncated-normal
fan-in matrices, N(0, 0.02) embedding, zero-initialised norm offsets),
packs them to symmetric integer codes with one scale per layer and matrix,
and runs the Llama-architecture forward pass (RMSNorm, rotary embeddings
with rotate-half, grouped-query causal attention, SwiGLU) in float32 at
``highest`` matmul precision, one layer at a time.
"""

from __future__ import annotations

import functools

import numpy as np

# ---------------------------------------------------------------------------
# operation and byte counts (the algorithm's work, from shapes)
# ---------------------------------------------------------------------------


def dims(cfg: dict) -> dict:
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    return dict(d=d, hd=hd, H=cfg["num_attention_heads"],
                KV=cfg["num_key_value_heads"], ff=cfg["intermediate_size"],
                V=cfg["vocab_size"], L=cfg["num_hidden_layers"])


def matmul_shapes(cfg: dict) -> list:
    """(K, N, calls per forward) of every packed projection."""
    m = dims(cfg)
    d, hd, H, KV, ff = m["d"], m["hd"], m["H"], m["KV"], m["ff"]
    per_layer = [(d, H * hd), (d, KV * hd), (d, KV * hd), (H * hd, d),
                 (d, ff), (d, ff), (ff, d)]
    return [(k, n, m["L"]) for k, n in per_layer] + [(d, m["V"], 1)]


def quant_matmul_calls(cfg: dict, rows: int, x_bytes: int = 2) -> list:
    """(ops, bytes, calls) of each packed projection over ``rows`` token
    rows: 2*M*K*N operations; the int8 codes, the scale, and activations in
    and out at ``x_bytes``."""
    return [(2 * rows * k * n, k * n + 4 + rows * (k + n) * x_bytes, calls)
            for k, n, calls in matmul_shapes(cfg)]


def matmul_params(cfg: dict) -> int:
    return sum(k * n * c for k, n, c in matmul_shapes(cfg))


def attention_ops(cfg: dict, ctx: int) -> int:
    """QK^T and PV operations of one token attending to ``ctx`` positions."""
    m = dims(cfg)
    return m["L"] * 4 * m["H"] * m["hd"] * ctx


def model_ops_prompt(cfg: dict, n: int) -> int:
    """Forward operations of an ``n``-token prompt (causal attention)."""
    return 2 * matmul_params(cfg) * n + attention_ops(cfg, n * (n + 1) // 2)


def model_ops_token(cfg: dict, ctx: int) -> int:
    """Forward operations of one decoded token attending to ``ctx``."""
    return 2 * matmul_params(cfg) + attention_ops(cfg, ctx)


def flash_attention_cost(cfg: dict, batch: int, seq: int,
                         x_bytes: int = 2) -> tuple:
    """(ops, bytes) of causal prefill attention over ``batch`` rows of
    ``seq`` tokens in every layer: q and output at all heads, k and v at the
    KV heads."""
    m = dims(cfg)
    ops = m["L"] * batch * 4 * m["H"] * m["hd"] * seq * (seq + 1) // 2
    byt = m["L"] * batch * seq * m["hd"] * (2 * m["H"] + 2 * m["KV"]) * x_bytes
    return ops, byt


def flash_decode_cost(cfg: dict, contexts, page: int, kv_bytes: int = 4,
                      q_bytes: int = 2) -> tuple:
    """(ops, bytes) of one decode attention in every layer: each live slot
    reads the K/V pages that hold its ``contexts`` positions."""
    m = dims(cfg)
    ops = byt = 0
    for c in contexts:
        pages = -(-int(c) // page)
        ops += 4 * m["H"] * m["hd"] * int(c)
        byt += 2 * pages * page * m["KV"] * m["hd"] * kv_bytes
        byt += 2 * m["H"] * m["hd"] * q_bytes
    return m["L"] * ops, m["L"] * byt


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------


def _key_stream(key):
    import jax

    while True:
        key, sub = jax.random.split(key)
        yield sub


def weight_keys(cfg: dict, seed: int):
    """Keys of (embedding, per-layer [wq, wk, wv, wo, w_up, w_down, w_gate],
    unembedding), in the order the initialisation draws them."""
    import jax

    ks = _key_stream(jax.random.fold_in(jax.random.PRNGKey(seed), 0))
    embed = next(ks)
    layers = [[next(ks) for _ in range(7)]
              for _ in range(cfg["num_hidden_layers"])]
    return embed, layers, next(ks)


def _dense_init(key, d_in, d_out):
    import jax

    return (jax.random.truncated_normal(key, -2.0, 2.0, (d_in, d_out))
            * (1.0 / d_in) ** 0.5)


def _pack(w, bits: int):
    """Symmetric integer codes in [-(2^bits - 1), 2^bits - 1], one scale,
    round to nearest; returns the dequantized float32 matrix."""
    import jax.numpy as jnp

    lim = 2 ** bits - 1
    scale = jnp.maximum(jnp.max(jnp.abs(w)), 1e-12) * (1.0 / lim)
    return jnp.clip(jnp.round(w / scale), -lim, lim) * scale


def _rmsnorm(x, eps):
    import jax.numpy as jnp

    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps))


def _rope(x, theta):
    """Rotate-half rotary embedding; x: (T, heads, hd)."""
    import jax.numpy as jnp

    T, _, hd = x.shape
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


@functools.lru_cache(maxsize=8)
def _layer_fn(d, hd, H, KV, ff, eps, theta, bits, dtype_name):
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(dtype_name)

    def attend(qkv):
        q, k, v = qkv
        T = q.shape[0]
        s = jnp.einsum("qhd,khd->hqk", q, k).astype(jnp.float32) * hd ** -0.5
        causal = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p.astype(dt), v)

    def layer(keys, x):
        """x: (n, T, d) float32 residual streams of n sequences."""
        ws = [_dense_init(keys[0], d, H * hd), _dense_init(keys[1], d, KV * hd),
              _dense_init(keys[2], d, KV * hd), _dense_init(keys[3], H * hd, d),
              _dense_init(keys[4], d, ff), _dense_init(keys[5], ff, d),
              _dense_init(keys[6], d, ff)]
        wq, wk, wv, wo, wu, wd, wg = (_pack(w, bits).astype(dt) for w in ws)
        n, T, _ = x.shape
        h = _rmsnorm(x, eps).astype(dt)
        q = jax.vmap(_rope, (0, None))(
            (h @ wq).astype(jnp.float32).reshape(n, T, H, hd), theta)
        k = jax.vmap(_rope, (0, None))(
            (h @ wk).astype(jnp.float32).reshape(n, T, KV, hd), theta)
        v = (h @ wv).reshape(n, T, KV, hd)
        g = H // KV
        k = jnp.repeat(k.astype(dt), g, axis=2)
        v = jnp.repeat(v.astype(dt), g, axis=2)
        a = jax.lax.map(attend, (q.astype(dt), k, v)).reshape(n, T, H * hd)
        x = x + (a @ wo).astype(jnp.float32)
        h = _rmsnorm(x, eps).astype(dt)
        m = jax.nn.silu((h @ wg).astype(jnp.float32)) * (h @ wu).astype(
            jnp.float32)
        return x + (m.astype(dt) @ wd).astype(jnp.float32)

    return jax.jit(layer)


def reference_logits(cfg: dict, seed: int, seqs: list, positions: list, *,
                     bits: int | None = None, dtype: str = "float32",
                     shape: tuple = (0, 0)):
    """Reference logits of each sequence at the given positions.

    ``seqs``: token id arrays (prompt followed by served tokens); ``positions``:
    per sequence, the positions whose next-token logits are wanted.  Returns a
    list of float32 arrays ``(len(positions[i]), vocab)``.  ``bits`` packs
    the weights at another width (the control); ``dtype`` computes in
    another precision.  Sequences are padded to one length (causality keeps
    the padding out of every earlier position) and go through
    each layer together, whose weights are drawn from the seed just before
    use; attention runs one sequence at a time.  ``shape`` (sequences,
    length) pads to at least that many, so that every run of a cell
    compiles the reference once.
    """
    import jax
    import jax.numpy as jnp

    m = dims(cfg)
    bits = cfg["weight_bits"] if bits is None else bits
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    T = max(max(len(s) for s in seqs), shape[1])
    T = -(-T // 128) * 128
    seqs = list(seqs) + [seqs[0]] * max(0, shape[0] - len(seqs))
    ekey, lkeys, ukey = weight_keys(cfg, seed)
    with jax.default_matmul_precision("highest"):
        table = _pack(jax.random.normal(ekey, (m["V"], m["d"])) * 0.02, bits)
        ids = np.zeros((len(seqs), T), np.int32)
        for i, s in enumerate(seqs):
            ids[i, :len(s)] = s
        x = jnp.take(table, jnp.asarray(ids), axis=0)
        del table
        layer = _layer_fn(m["d"], m["hd"], m["H"], m["KV"], m["ff"], eps,
                          theta, bits, dtype)
        for keys in lkeys:
            x = layer(jnp.stack(keys), x)
        w_un = _pack(_dense_init(ukey, m["d"], m["V"]), bits).astype(dtype)
        logits = np.asarray(_head_fn(eps, dtype)(x, w_un))
    return [logits[i, np.asarray(pos)] for i, pos in enumerate(positions)]


@functools.lru_cache(maxsize=8)
def _head_fn(eps, dtype_name):
    """Logits at every position: one program per cell, whatever the
    sampled requests' lengths."""
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(dtype_name)

    def head(x, w):
        return (_rmsnorm(x, eps).astype(dt) @ w).astype(jnp.float32)

    return jax.jit(head)


def served_gaps(ref_logits, tokens) -> np.ndarray:
    """How far below the reference's best logit each served token lies, in
    units of the standard deviation of that position's reference logits."""
    ref = np.asarray(ref_logits, np.float64)
    tok = np.asarray(tokens, np.int64)
    best = ref.max(axis=-1)
    got = ref[np.arange(len(tok)), tok]
    return (best - got) / np.maximum(ref.std(axis=-1), 1e-30)
