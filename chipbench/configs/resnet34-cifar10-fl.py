"""ResNet-34 FL on CIFAR-10: the plain reference and the operation counts.

Sizes come from ``resnet34-cifar10-fl.json`` beside this file (or a smaller
dict of the same keys in the tests).  Nothing here imports the program.

The reference is one FWQ round of the paper's Algorithm 1 written out
plainly: the server's float32 weights, quantized per client by stochastic
rounding onto the grid of that client's bit-width (per-tensor scale
``max|w|``, resolution ``1 / (2^q - 1)``, noise keys folded from the seed,
the round, the client's place in the cohort and the leaf's place in the
tree), each client's gradient of the mean cross-entropy over its batch taken
at its quantized weights, the plain mean over the cohort, and an SGD step.
The network is ResNet-34 with the CIFAR stem (3x3 convolution, no pooling),
post-activation basic blocks and GroupNorm of 8 groups.  It keeps weights,
activations and gradients in float32 and runs its convolutions and the
classifier at the configuration's ``matmul_precision``, the precision the
configuration states (``default``: one bfloat16 pass with float32
accumulation on TPU, exact float32 on the CPU).  ``control=True`` computes
it one step lower, in 8-bit floats as fp8 training does: every
convolution's and the classifier's inputs rounded to ``control_dtype`` and
the gradients at their outputs to ``control_grad_dtype``, each with its own
scale from the tensor's largest magnitude, the rest in bfloat16.
"""

from __future__ import annotations

import functools

import numpy as np

# ---------------------------------------------------------------------------
# shapes and operation counts
# ---------------------------------------------------------------------------


def widths(cfg: dict) -> list:
    return [cfg["width"] * 2 ** i for i in range(len(cfg["depth_blocks"]))]


def _taps(h: int, k: int, stride: int) -> int:
    """Kernel taps that land inside an ``h``-long input along one axis,
    summed over the outputs of a ``SAME``-padded convolution."""
    out = -(-h // stride)
    pad = max((out - 1) * stride + k - h, 0) // 2
    return sum(0 <= i * stride + j - pad < h
               for i in range(out) for j in range(k))


def _conv_ops(h: int, k: int, stride: int, cin: int, cout: int) -> int:
    return 2 * cin * cout * _taps(h, k, stride) ** 2


def forward_ops(cfg: dict) -> int:
    """Multiply-add operations (x2) of one image's forward pass: the
    convolutions, counting only the taps that fall inside the image (not
    the zero padding), and the classifier; normalisation and activations
    are left out."""
    hw = cfg["image_hw"]
    w = widths(cfg)
    ops = _conv_ops(hw, 3, 1, cfg["channels"], w[0])
    cin = w[0]
    for si, (blocks, cout) in enumerate(zip(cfg["depth_blocks"], w)):
        for bi in range(blocks):
            stride = 2 if (bi == 0 and si > 0) else 1
            ops += _conv_ops(hw, 3, stride, cin, cout)
            if cin != cout:
                ops += _conv_ops(hw, 1, stride, cin, cout)
            hw = -(-hw // stride)
            ops += _conv_ops(hw, 3, 1, cout, cout)
            cin = cout
    return ops + 2 * cin * cfg["n_classes"]


def train_ops_per_image(cfg: dict) -> int:
    """Forward and backward: three times the forward operations."""
    return 3 * forward_ops(cfg)


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------


def _conv_init(key, kh, cin, cout):
    import jax

    fan = kh * kh * cin
    return (jax.random.truncated_normal(key, -2, 2, (kh, kh, cin, cout))
            * (2.0 / fan) ** 0.5)


def init_params(cfg: dict, seed: int) -> dict:
    """The initial weights, drawn from the seed in the documented order."""
    import jax
    import jax.numpy as jnp

    def stream(key):
        while True:
            key, sub = jax.random.split(key)
            yield sub

    ks = stream(jax.random.PRNGKey(seed))
    w = widths(cfg)
    p = {"stem": {"w": _conv_init(next(ks), 3, cfg["channels"], w[0]),
                  "gn_s": jnp.ones((w[0],)), "gn_b": jnp.zeros((w[0],))}}
    cin = w[0]
    for si, (blocks, cout) in enumerate(zip(cfg["depth_blocks"], w)):
        for bi in range(blocks):
            blk = {"conv1": _conv_init(next(ks), 3, cin, cout),
                   "gn1_s": jnp.ones((cout,)), "gn1_b": jnp.zeros((cout,)),
                   "conv2": _conv_init(next(ks), 3, cout, cout),
                   "gn2_s": jnp.ones((cout,)), "gn2_b": jnp.zeros((cout,))}
            if cin != cout:
                blk["proj"] = _conv_init(next(ks), 1, cin, cout)
            p[f"s{si}b{bi}"] = blk
            cin = cout
    p["head"] = {"w": jax.random.normal(next(ks), (cin, cfg["n_classes"])) * 0.01,
                 "b": jnp.zeros((cfg["n_classes"],))}
    return p


def _sr(w, delta, key):
    import jax
    import jax.numpy as jnp

    s = jnp.max(jnp.abs(w))
    s = jnp.where(s > 0, s, 1.0)
    step = s * delta
    safe = jnp.where(step > 0, step, 1.0)
    t = w / safe
    lower = jnp.floor(t)
    u = jax.random.uniform(key, t.shape, dtype=t.dtype)
    q = jnp.clip((lower + (u < t - lower).astype(t.dtype)) * safe, -s, s)
    out = jnp.where(step > 0, q, w)
    return w + jax.lax.stop_gradient(out - w)


def _groupnorm(x, scale, bias, groups=8, eps=1e-5):
    import jax.numpy as jnp

    B, H, W, C = x.shape
    g = min(groups, C)
    while C % g:
        g -= 1
    xg = x.reshape(B, H, W, g, C // g)
    mu = xg.mean(axis=(1, 2, 4), keepdims=True)
    var = ((xg - mu) ** 2).mean(axis=(1, 2, 4), keepdims=True)
    xn = ((xg - mu) / jnp.sqrt(var + eps)).reshape(B, H, W, C)
    return xn * scale + bias


def _conv_plain(x, w, stride=1):
    import jax

    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _fp8(a, lo):
    """``a`` rounded to the 8-bit float ``lo`` under a per-tensor scale that
    maps its largest magnitude to the format's largest value."""
    import jax.numpy as jnp

    a32 = a.astype(jnp.float32)
    amax = jnp.max(jnp.abs(a32))
    s = jnp.where(amax > 0, float(jnp.finfo(lo).max) / amax, 1.0)
    return ((a32 * s).astype(lo).astype(jnp.float32) / s).astype(a.dtype)


def fp8_casts(fwd_dtype: str, grad_dtype: str) -> tuple:
    """(inputs, outputs) casts of the fp8 control: the first rounds a
    value (its gradient passes straight through), the second leaves a value
    alone and rounds the gradient that reaches it."""
    import jax
    import jax.numpy as jnp

    lo, glo = jnp.dtype(fwd_dtype), jnp.dtype(grad_dtype)

    def cast(a):
        return a + jax.lax.stop_gradient(_fp8(a, lo) - a)

    @jax.custom_vjp
    def grad_cast(a):
        return a

    grad_cast.defvjp(lambda a: (a, None), lambda _, g: (_fp8(g, glo),))
    return cast, grad_cast


def forward(cfg: dict, p: dict, x, cast=None, grad_cast=None):
    """Logits of images ``x``; ``cast`` rounds every convolution's and the
    classifier's inputs and ``grad_cast`` the gradients at their outputs
    (the control's lower precision)."""
    import jax

    relu = jax.nn.relu
    c = cast if cast is not None else (lambda a: a)
    gc = grad_cast if grad_cast is not None else (lambda a: a)

    def _conv(x, w, stride=1):
        return gc(_conv_plain(c(x), c(w), stride))

    h = relu(_groupnorm(_conv(x, p["stem"]["w"]), p["stem"]["gn_s"],
                        p["stem"]["gn_b"]))
    for si, blocks in enumerate(cfg["depth_blocks"]):
        for bi in range(blocks):
            b = p[f"s{si}b{bi}"]
            stride = 2 if (bi == 0 and si > 0) else 1
            y = relu(_groupnorm(_conv(h, b["conv1"], stride), b["gn1_s"],
                                b["gn1_b"]))
            y = _groupnorm(_conv(y, b["conv2"]), b["gn2_s"], b["gn2_b"])
            if "proj" in b:
                sc = _conv(h, b["proj"], stride)
            elif stride != 1:
                sc = h[:, ::stride, ::stride]
            else:
                sc = h
            h = relu(y + sc)
    h = h.mean(axis=(1, 2))
    return gc(c(h) @ c(p["head"]["w"])) + p["head"]["b"]


@functools.lru_cache(maxsize=8)
def _round_fn(cfg_key: tuple, control: tuple, n_keep: int):
    """Jitted reference round; ``control`` is ``()`` or the fp8 control's
    (input, gradient) dtypes; ``n_keep`` < batch drops the rest of every
    client's batch (the half-batch fault, read against the reference)."""
    import jax
    import jax.numpy as jnp

    cfg = dict(cfg_key)
    cfg["depth_blocks"] = list(cfg["depth_blocks"])
    dt, cast, grad_cast = jnp.dtype(jnp.float32), None, None
    if control:
        dt = jnp.dtype(jnp.bfloat16)
        cast, grad_cast = fp8_casts(*control)

    def client(params, x, y, delta, key):
        qkey, _ = jax.random.split(key)
        leaves, tdef = jax.tree_util.tree_flatten(params)
        qleaves = [_sr(l, delta, jax.random.fold_in(qkey, i)) if l.ndim > 1
                   else l for i, l in enumerate(leaves)]

        def loss(ql):
            qp = jax.tree_util.tree_unflatten(tdef, [l.astype(dt) for l in ql])
            logits = forward(cfg, qp, x[:n_keep].astype(dt), cast,
                             grad_cast).astype(jnp.float32)
            ls = jax.nn.log_softmax(logits)
            return -jnp.take_along_axis(ls, y[:n_keep, None], axis=-1).mean()

        val, g = jax.value_and_grad(loss)(qleaves)
        return val, [gi.astype(jnp.float32) for gi in g]

    def round_fn(params, x, y, bits, rng, lr):
        n = x.shape[0]
        deltas = jnp.where(bits >= 32, 0.0,
                           1.0 / (jnp.exp2(jnp.minimum(bits, 31).astype(
                               jnp.float32)) - 1.0))
        keys = jax.vmap(lambda i: jax.random.fold_in(rng, i))(jnp.arange(n))
        losses, grads = jax.lax.map(
            lambda a: client(params, *a), (x, y, deltas, keys))
        leaves, tdef = jax.tree_util.tree_flatten(params)
        new = [p - lr * jnp.mean(g, axis=0) for p, g in zip(leaves, grads)]
        return jnp.mean(losses), jax.tree_util.tree_unflatten(tdef, new)

    return jax.jit(round_fn)


def _cfg_key(cfg: dict) -> tuple:
    keep = ("depth_blocks", "width", "n_classes", "image_hw", "channels")
    return tuple((k, tuple(cfg[k]) if isinstance(cfg[k], list) else cfg[k])
                 for k in keep)


def reference_rounds(cfg: dict, seed: int, rounds: list, *,
                     control: bool = False, keep: float = 1.0) -> dict:
    """Follow the program's first rounds from the seed.

    ``rounds``: per round a dict with ``x`` (clients, batch, H, W, C), ``y``
    (clients, batch), ``bits`` (clients,) and ``round`` (the simulator's
    round index, which folds the noise key).  Returns the losses and the
    parameters as numpy leaves: ``p0`` before the first round, ``p1`` after
    it and ``pN`` after the last.  ``control`` computes in fp8 (the module
    docstring); ``keep`` keeps that share of every client's batch.
    """
    import jax
    import jax.numpy as jnp

    n_keep = max(1, int(round(cfg["local_batch"] * keep)))
    ctl = ((cfg["control_dtype"], cfg["control_grad_dtype"]) if control
           else ())
    fn = _round_fn(_cfg_key(cfg), ctl, n_keep)
    params = init_params(cfg, seed)

    def host(p):
        return [np.asarray(l, np.float64) for l in jax.tree_util.tree_leaves(p)]

    out = {"losses": [], "p0": host(params)}
    with jax.default_matmul_precision(cfg["matmul_precision"]):
        for i, r in enumerate(rounds):
            rng = jax.random.fold_in(jax.random.PRNGKey(seed), int(r["round"]))
            loss, params = fn(params, jnp.asarray(r["x"]), jnp.asarray(r["y"]),
                              jnp.asarray(r["bits"], jnp.int32), rng,
                              jnp.float32(cfg["lr"]))
            out["losses"].append(float(loss))
            if i == 0:
                out["p1"] = host(params)
    out["pN"] = host(params)
    return out


def compare(prog: dict, ref: dict, lr: float) -> dict:
    """The numbers that decide ``correct`` for a training cell.

    ``loss``: the largest relative gap of a round's mean loss.  ``grad``:
    the first gradient as the optimizer applied it, ``(p0 - p1) / lr``, by
    the worst leaf: the gap between the program's norm and the reference's,
    over the larger of the reference's norm of that leaf and of the median
    leaf.  ``change``: the same for the parameters' change after the last
    round, over the leaves whose reference gradient is at least a
    thousandth of the median leaf's (the others move by round-off alone).
    ``grad_diff``: the norm of the difference of the two first gradients,
    by the worst leaf, over the same denominator as ``grad``; a norm gap
    hides element-wise rounding, which this number sees.
    """
    lp, lr_ = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    loss = float(np.max(np.abs(lp - lr_) / np.maximum(np.abs(lr_), 1e-30)))

    def norms(a, b):
        return np.array([np.linalg.norm(x - y) for x, y in zip(a, b)])

    gp = norms(prog["p0"], prog["p1"]) / lr
    gr = norms(ref["p0"], ref["p1"]) / lr
    med = np.median(gr)
    grad = float(np.max(np.abs(gp - gr) / np.maximum(gr, med)))
    diff = np.array([np.linalg.norm((a1 - a0) - (b1 - b0)) for a0, a1, b0, b1
                     in zip(prog["p0"], prog["p1"], ref["p0"], ref["p1"])])
    grad_diff = float(np.max(diff / lr / np.maximum(gr, med)))
    moved = gr >= 1e-3 * med
    cp = norms(prog["p0"], prog["pN"])[moved]
    cr = norms(ref["p0"], ref["pN"])[moved]
    change = float(np.max(np.abs(cp - cr) / np.maximum(cr, np.median(cr))))
    return {"loss": loss, "grad": grad, "change": change,
            "grad_diff": grad_diff, "leaves_compared": int(moved.sum()),
            "leaves": len(gr)}
