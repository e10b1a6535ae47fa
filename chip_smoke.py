"""Prove the main path runs on one TPU chip, at published width.

    python chip_smoke.py                  # one chip: phases a-d
    python chip_smoke.py --four-chips     # four chips: the pod trainer only
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse   # tiny, on CPU

Phases, in order, each printing one line of its own results:

a. the device: a TPU, or exit non-zero (there is no CPU fallback);
b. the three serving kernels (``quant_matmul``, flash-attention prefill,
   paged ``flash_decode`` over f32 and bf16 pools) at yi-6b widths against
   their ``repro.kernels.ref`` oracles, each lowered to a
   ``tpu_custom_call`` (so no interpret mode ran);
c. yi-6b int8 serving through ``Session.serve`` (flash attention, paged KV
   cache, packed weights through ``quant_matmul``): every request
   completes, and the kernel path's prefill logits match the jnp path's
   (reference attention, eagerly dequantized weights) on the same weights;
d. the paper's FL round (``fl-sim``, resnet, fwq, 3 rounds): finite losses,
   and modelled joules equal to the pinned CPU values (host math).

``--four-chips`` runs only the pod trainer's SR-quantized integer gradient
all-reduce: mamba2-780m at published width on a ``4x1`` mesh, a few steps
at comm bits 8 against the same steps at comm bits 32.

The last line of a passing chip run is one JSON object naming the device.
``--rehearse`` runs the same phases on CPU at smoke sizes (interpret-mode
kernels) and prints no such line.  The compile cache lives where
``JAX_COMPILATION_CACHE_DIR`` says, else in ``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import argparse
import dataclasses
import faulthandler
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

#: Hard stop below the 1200 s the chip run is allowed: a hung collective or
#: compile dumps every thread's stack and exits non-zero instead of hanging.
WATCHDOG_S = 1140

#: Per-round modelled joules of phase d (resnet, fwq, 8 clients, seed 0),
#: from a CPU run: the energy model is host math, so the chip must agree.
FL_JOULES = [18.492357759848133, 21.08495942090827, 19.79001395929932]

#: Phase d tolerance on the joules: host float math, same code and inputs.
FL_JOULES_RTOL = 1e-9


@dataclasses.dataclass(frozen=True)
class Sizes:
    smoke: bool            # serve a smoke_variant instead of published width
    qmm: tuple             # quant_matmul (M, K, N)
    attn: tuple            # flash attention (heads, S, D)
    decode: tuple          # flash decode (B, KV, G, hd, page, n_pmax)
    slots: int
    requests: int
    prompt_len: int
    max_new: int
    s_max: int
    train_seq: int         # --four-chips: tokens per client per step
    train_steps: int


CHIP = Sizes(smoke=False, qmm=(16, 4096, 11008), attn=(32, 2048, 128),
             decode=(4, 4, 8, 128, 16, 64), slots=4, requests=8,
             prompt_len=512, max_new=16, s_max=1024, train_seq=256,
             train_steps=3)
REHEARSAL = Sizes(smoke=True, qmm=(8, 256, 384), attn=(2, 256, 128),
                  decode=(2, 2, 8, 128, 16, 4), slots=2, requests=4,
                  prompt_len=16, max_new=4, s_max=64, train_seq=32,
                  train_steps=3)

#: Stated tolerances: max |got - want| / max |want|.  A TPU f32 matmul at
#: default precision takes one bf16 pass (relative error ~4e-3), and a bf16
#: output rounds at ~4e-3, so 1e-2 / 3e-2 leave room for one of each.
KERNEL_TOL = {"float32": 1e-2, "bfloat16": 3e-2}

#: Phase c: kernel path vs jnp path prefill logits, same packed weights.
#: The jnp path rounds dequantized weights to bf16 before each matmul and
#: the kernel path does not, so the two differ by bf16 rounding compounded
#: over the layers.
LOGITS_TOL = 5e-2

#: --four-chips: comm-8 losses against comm-32 losses, per step, relative.
#: SR-quantized gradients are unbiased; over a few steps the loss moves by
#: the noise of one update, well inside a few percent.
LOSS_BAND = 0.02


def rel_err(got, want) -> float:
    import numpy as np

    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    scale = max(float(np.max(np.abs(want))), 1e-30)
    return float(np.max(np.abs(got - want))) / scale


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise AssertionError(msg)


def lowered_to_chip(fn, *args) -> bool:
    """True iff ``fn`` lowers to a Mosaic kernel (no interpret mode)."""
    import jax

    return "tpu_custom_call" in jax.jit(fn).lower(*args).as_text()


# ---------------------------------------------------------------------------
# phase a
# ---------------------------------------------------------------------------


def phase_device(rehearse: bool, need: int) -> dict:
    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    print(f"phase a device: platform={dev['platform']} kind={dev['kind']!r} "
          f"count={dev['count']}", flush=True)
    if not rehearse and dev["platform"] != "tpu":
        raise SystemExit(f"no TPU found (jax sees {dev['platform']!r} "
                         "devices); this script runs only on a TPU chip")
    if dev["count"] < need:
        raise SystemExit(f"need {need} devices, jax sees {dev['count']}")
    return dev


# ---------------------------------------------------------------------------
# phase b
# ---------------------------------------------------------------------------


def phase_kernels(sz: Sizes, on_chip: bool) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, ref

    rng = np.random.RandomState(0)
    parts = []

    def record(name, dtype, got, want, fn, *args):
        err = rel_err(got, want)
        tol = KERNEL_TOL[jnp.dtype(dtype).name]
        check(math.isfinite(err) and err <= tol,
              f"{name} {jnp.dtype(dtype).name}: error {err:.3e} > {tol:g}")
        if on_chip:
            check(lowered_to_chip(fn, *args),
                  f"{name}: no tpu_custom_call in the lowered program")
        parts.append(f"{name}[{jnp.dtype(dtype).name}] err={err:.3e}")

    highest = jax.default_matmul_precision("highest")

    M, K, N = sz.qmm
    codes = jnp.asarray(rng.randint(-127, 128, size=(K, N)).astype(np.int8))
    scale = jnp.float32(0.02)
    for dt in (jnp.float32, jnp.bfloat16):
        x = jnp.asarray(rng.randn(M, K).astype(np.float32)).astype(dt)
        got = ops.quant_matmul(x, codes, scale)
        with highest:
            want = ref.quant_matmul_ref(x, codes, scale)
        record("quant_matmul", dt, got, want, ops.quant_matmul, x, codes,
               scale)

    H, S, D = sz.attn
    for dt in (jnp.float32, jnp.bfloat16):
        q, k, v = (jnp.asarray(rng.randn(1, H, S, D).astype(np.float32))
                   .astype(dt) for _ in range(3))
        got = ops.flash_attention(q, k, v)
        with highest:
            want = ref.flash_attention_ref(q.astype(jnp.float32),
                                           k.astype(jnp.float32),
                                           v.astype(jnp.float32))
        record("flash_attention", dt, got, want, ops.flash_attention, q, k, v)

    B, KV, G, hd, page, n_pmax = sz.decode
    n_pool = B * n_pmax
    q = jnp.asarray(rng.randn(B, KV, G, hd).astype(np.float32))
    lengths = rng.randint(1, n_pmax * page + 1, size=(B,)).astype(np.int32)
    table = np.full((B, n_pmax), -1, np.int32)
    rows = rng.permutation(n_pool)
    for b in range(B):                 # pages up to each slot's length only
        n = -(-int(lengths[b]) // page)
        table[b, :n] = rows[b * n_pmax:b * n_pmax + n]
    table, lengths = jnp.asarray(table), jnp.asarray(lengths)
    for dt in (jnp.float32, jnp.bfloat16):
        kp, vp = (jnp.asarray(rng.randn(n_pool, page, KV, hd)
                              .astype(np.float32)).astype(dt)
                  for _ in range(2))
        acc, _, l = ops.flash_paged_decode(q, kp, vp, table, lengths)
        got = acc / jnp.maximum(l, 1e-30)
        with highest:
            want = ref.flash_decode_ref(q, kp, vp, table, lengths)
        record("flash_decode", dt, got, want, ops.flash_paged_decode, q, kp,
               vp, table, lengths)

    where = "tpu_custom_call in all" if on_chip else "interpret mode"
    print(f"phase b kernels ({where}): " + "; ".join(parts), flush=True)


# ---------------------------------------------------------------------------
# phase c
# ---------------------------------------------------------------------------


def phase_serve(sz: Sizes) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.api import PrecisionPolicy, RunSpec, Session
    from repro.launch.steps import build_cached_prefill, init_global_caches

    policy = PrecisionPolicy.lazy_int8(7)
    sess = Session(RunSpec(arch="yi-6b", workload="serve", mesh="1x1",
                           smoke=sz.smoke, batch=sz.slots, seq=sz.s_max,
                           precision=policy))
    t0 = time.time()
    stats = sess.serve(attn_impl="flash", kv_layout="paged",
                       requests=sz.requests, prompt_len=sz.prompt_len,
                       vary_prompt=True, max_new=sz.max_new, s_max=sz.s_max,
                       steps=sz.requests * sz.max_new, quiet=True)
    serve_s = time.time() - t0
    check(stats.completed == sz.requests and stats.admitted == sz.requests,
          f"serve completed {stats.completed}/{sz.requests} requests")
    check(stats.capacity_stops == 0,
          f"{stats.capacity_stops} requests stopped at cache capacity")

    # the same packed weights through both prefill implementations
    model, mesh, axes = sess.model, sess.mesh, sess.axes
    qparams = sess.serving_params
    ptree = jax.eval_shape(lambda: qparams)
    rng = np.random.RandomState(1)
    plens = rng.randint(sz.prompt_len // 2, sz.prompt_len + 1,
                        size=(sz.slots,)).astype(np.int32)
    toks = np.ones((sz.slots, sz.prompt_len), np.int32)
    for b, n in enumerate(plens):
        toks[b, :n] = rng.randint(2, sess.cfg.vocab_size, size=(n,))
    caches = init_global_caches(model, mesh, axes, s_max=sz.s_max,
                                batch_global=sz.slots)
    logits = {}
    for name, impl, lazy in (("kernel", "flash", True), ("jnp", "auto", False)):
        pf = build_cached_prefill(
            model, mesh, axes, params_tree=ptree, s_max=sz.s_max,
            s_prompt=sz.prompt_len, batch_global=sz.slots, attn_impl=impl,
            policy=PrecisionPolicy(weights=7, lazy=lazy),
            with_prompt_lens=True, with_logits=True)
        _, lg, _ = pf.fn(qparams, {"tokens": jnp.asarray(toks)}, caches,
                         jnp.ones((sz.slots,), jnp.bool_), jnp.asarray(plens))
        logits[name] = np.asarray(lg)[:, 0, :sess.cfg.vocab_size]
    check(np.all(np.isfinite(logits["kernel"])), "non-finite kernel logits")
    err = rel_err(logits["kernel"], logits["jnp"])
    agree = int(np.sum(np.argmax(logits["kernel"], -1)
                       == np.argmax(logits["jnp"], -1)))
    check(err <= LOGITS_TOL,
          f"prefill logits: kernel vs jnp error {err:.3e} > {LOGITS_TOL:g}")
    print(f"phase c serve {sess.cfg.name} int8: {stats.completed}/"
          f"{sz.requests} requests completed, {stats.decoded_tokens} tokens "
          f"over {stats.decode_steps} steps x {sz.slots} slots, buckets "
          f"{stats.prompt_buckets}, weights {stats.bytes_per_step_packed} B "
          f"packed ({stats.packed_vs_f32:.3f} of f32), {serve_s:.1f} s with "
          f"compiles; prefill logits kernel vs jnp err={err:.3e} "
          f"(tol {LOGITS_TOL:g}), top-1 agree {agree}/{sz.slots}", flush=True)


# ---------------------------------------------------------------------------
# phase d
# ---------------------------------------------------------------------------


def phase_fl() -> None:
    import numpy as np

    from repro.api import RunSpec, Session

    out = Session(RunSpec(arch="resnet", workload="fl-sim", rounds=3,
                          batch=32, seed=0,
                          options={"scheme": "fwq", "n_clients": 8,
                                   "quiet": True})).run()
    losses = [float(h["loss"]) for h in out["history"]]
    joules = [float(e["energy_round"]) for e in out["energy_log"]]
    check(len(losses) == 3 and all(math.isfinite(x) for x in losses),
          f"FL losses not finite: {losses}")
    check(np.allclose(joules, FL_JOULES, rtol=FL_JOULES_RTOL, atol=0),
          f"FL joules {joules!r} != CPU values {FL_JOULES!r}")
    print(f"phase d fl-sim resnet fwq: losses {losses!r}, joules per round "
          f"{joules!r} (equal to the CPU values)", flush=True)


# ---------------------------------------------------------------------------
# --four-chips
# ---------------------------------------------------------------------------


def phase_pod_trainer(sz: Sizes) -> None:
    from repro.api import PrecisionPolicy, RunSpec, Session
    from repro.configs.base import ShapeSpec
    from repro.dist.collectives import wire_dtype

    n_clients = 4
    losses, wire = {}, None
    for comm in (8, 32):
        sess = Session(RunSpec(
            arch="mamba2-780m", workload="train", mesh="4x1",
            smoke=sz.smoke, batch=1, seq=sz.train_seq, seed=0,
            rounds=sz.train_steps, precision=PrecisionPolicy(comm=comm),
            options={"quiet": True, "lr": 0.01}))
        losses[comm] = [sess.fl_round(r)["loss"]
                        for r in range(sz.train_steps)]
        if comm < 32:
            # the program asks for the narrow wire dtype; the TPU compiler
            # may widen a 16-bit integer all-reduce, so report what it ran
            compiled, lowered, _ = sess.lower(ShapeSpec(
                "pod_smoke", sz.train_seq, n_clients, "train"))
            want = jnp_hlo_name(wire_dtype(comm, n_clients))
            asked = int_all_reduces(lowered.as_text(dialect="hlo"))
            check(want in asked, f"no {want} all-reduce in the comm-{comm} "
                                 f"step as lowered (integer: {asked})")
            wire = (want, int_all_reduces(compiled.as_text()))
    for a, b in zip(losses[8], losses[32]):
        check(math.isfinite(a) and abs(a - b) <= LOSS_BAND * abs(b),
              f"comm-8 loss {a} outside {LOSS_BAND:g} of comm-32 loss {b}")
    print(f"phase pod-trainer mamba2-780m 4x1: comm-8 losses {losses[8]!r}, "
          f"comm-32 losses {losses[32]!r} (band {LOSS_BAND:g}); integer "
          f"all-reduce {wire[0]} as lowered, {sorted(set(wire[1]))} as "
          "compiled", flush=True)


def jnp_hlo_name(dt) -> str:
    import jax.numpy as jnp

    return {"int8": "s8", "int16": "s16", "int32": "s32"}[jnp.dtype(dt).name]


def int_all_reduces(hlo_text: str) -> list:
    """Signed-integer result dtypes of every all-reduce in an HLO module."""
    import re

    out = []
    for line in hlo_text.splitlines():
        m = re.search(r"=\s*(.*?)\s+all-reduce(?:-start)?\(", line)
        if m:
            out += re.findall(r"\b(s(?:8|16|32|64))\[", m.group(1))
    return out


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip pod-trainer phase")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on whatever jax finds (CPU); prints no "
                         "result line")
    args = ap.parse_args(argv)
    faulthandler.dump_traceback_later(WATCHDOG_S, exit=True)

    from repro.launch.mesh import enable_compile_cache

    cache = enable_compile_cache()
    sz = REHEARSAL if args.rehearse else CHIP
    t0 = time.time()
    dev = phase_device(args.rehearse, 4 if args.four_chips else 1)
    if args.four_chips:
        phase_pod_trainer(sz)
    else:
        phase_kernels(sz, on_chip=not args.rehearse)
        phase_serve(sz)
        phase_fl()
    print(f"all phases passed in {time.time() - t0:.1f} s "
          f"(compile cache: {cache})", flush=True)
    if not args.rehearse:
        print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
