"""Record a small profiler trace of the serving kernels on the chip.

    python -m chipbench.record_trace --out tests/chipbench/data/kernels

Two jitted programs, both named ``sm`` like the serving steps, run a few
times under ``jax.profiler.trace``: one holds ``quant_matmul`` and
``flash_decode`` (a decode-like step), the other ``quant_matmul`` and
``flash_attention`` (a prefill-like step), with a harness span around each
call.  The ``.xplane.pb`` goes to ``<out>.xplane.pb`` and a JSON summary of
its planes, lines and first events to ``<out>.summary.json``: the
trace-reduction tests read the first, and a reader of the second sees how
the device names programs and kernels.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import sys
import tempfile


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--calls", type=int, default=3)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(os.getcwd(), "src"))

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops

    if jax.devices()[0].platform != "tpu":
        print("no TPU: this records a device trace", file=sys.stderr)
        return 2
    rng = np.random.RandomState(0)
    M, K, N = 16, 1024, 2048
    codes = jnp.asarray(rng.randint(-127, 128, size=(K, N)).astype(np.int8))
    scale = jnp.float32(0.02)
    B, KV, G, hd, page, n_pmax = 4, 4, 8, 128, 16, 8
    n_pool = B * n_pmax
    q = jnp.asarray(rng.randn(B, KV, G, hd).astype(np.float32))
    kp = jnp.asarray(rng.randn(n_pool, page, KV, hd).astype(np.float32))
    vp = jnp.asarray(rng.randn(n_pool, page, KV, hd).astype(np.float32))
    table = jnp.asarray(np.arange(n_pool, dtype=np.int32).reshape(B, n_pmax))
    lengths = jnp.asarray(np.array([17, 64, 100, 128], np.int32))
    x = jnp.asarray(rng.randn(M, K).astype(np.float32))
    H, S, D = 4, 512, 128
    qa = jnp.asarray(rng.randn(1, H, S, D).astype(np.float32))

    def decode_like(x, codes, scale, q, kp, vp, table, lengths):
        y = ops.quant_matmul(x, codes, scale)
        acc, _, l = ops.flash_paged_decode(q, kp, vp, table, lengths)
        return y.sum() + (acc / jnp.maximum(l, 1e-30)).sum()

    def prefill_like(x, codes, scale, qa):
        y = ops.quant_matmul(x, codes, scale)
        return y.sum() + ops.flash_attention(qa, qa, qa).sum()

    decode_like.__name__ = prefill_like.__name__ = "sm"
    dec, pre = jax.jit(decode_like), jax.jit(prefill_like)
    dargs = (x, codes, scale, q, kp, vp, table, lengths)
    pargs = (x, codes, scale, qa)
    dec(*dargs).block_until_ready()
    pre(*pargs).block_until_ready()
    tmp = tempfile.mkdtemp(prefix="trace", dir=os.path.dirname(args.out) or ".")
    try:
        with jax.profiler.trace(tmp):
            for _ in range(args.calls):
                with jax.profiler.TraceAnnotation("chipbench.decode"):
                    dec(*dargs).block_until_ready()
                with jax.profiler.TraceAnnotation("chipbench.prefill"):
                    pre(*pargs).block_until_ready()
        path = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                         recursive=True)[0]
        shutil.copy(path, args.out + ".xplane.pb")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    pd = jax.profiler.ProfileData.from_file(args.out + ".xplane.pb")
    summary = []
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            evs = list(line.events)
            lines.append({
                "name": line.name, "n_events": len(evs),
                "first": [{"name": e.name, "start_ns": e.start_ns,
                           "duration_ns": e.duration_ns,
                           "stats": {k: str(v) for k, v in e.stats}}
                          for e in evs[:12]]})
        summary.append({"plane": plane.name, "lines": lines})
    with open(args.out + ".summary.json", "w") as f:
        json.dump(summary, f, indent=1)
    print(json.dumps({"ok": True, "kind": jax.devices()[0].device_kind,
                      "bytes": os.path.getsize(args.out + ".xplane.pb")}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
