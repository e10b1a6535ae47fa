"""Continuous-batching quantized serving CLI — a thin shim over
:class:`repro.api.Session`.

The FWQ-quantized model is packed once (:class:`QTensor` int8 codes + scale)
and — with a lazy :class:`~repro.api.PrecisionPolicy` — every decode step
streams the packed bytes straight into the ``quant_matmul`` Pallas kernel:
the weight stream stays int8 from HBM to VMEM, the serving-side realization
of the paper's storage/energy argument.  The driver itself (slot-based
continuous batching, per-sequence cache lengths, mid-flight prefill
admission) lives in :meth:`repro.api.Session.serve`.

CPU demo (interpret-mode kernels)::

    PYTHONPATH=src python -m repro.launch.serve --arch yi-6b --smoke \
        --steps 32 --batch 4 --attn-impl flash
"""

from __future__ import annotations

import argparse

from repro.api.session import BOS_ID, ServeStats  # noqa: F401  (re-export)


def run_serve(arch: str, *, smoke: bool = True, steps: int = 32, batch: int = 4,
              s_max: int = 64, prompt_len: int = 8, serve_bits: int = 7,
              attn_impl: str = "ref", mesh: str = "1x1", seed: int = 0,
              requests: int | None = None, max_new: int | None = None,
              kv_layout: str | None = None, page_size: int | None = None,
              pool_pages: int | None = None, vary_prompt: bool = False,
              precision_program=None, kv_bits: int = 32,
              quiet: bool = False) -> ServeStats:
    """Compatibility wrapper: builds a RunSpec and drives ``Session.serve``.

    ``serve_bits >= 32`` serves raw f32 weights (the baseline the packed
    ratio is measured against); ``< 32`` maps to a lazy packed
    :class:`~repro.api.PrecisionPolicy` (int8/int16 ``QTensor`` storage,
    ``quant_matmul`` decode path).  ``kv_layout="paged"`` (the default for
    attention families) serves from the paged KV cache: ``pool_pages`` pages
    of ``page_size`` tokens shared across slots, allocated per request on
    admit and reclaimed on completion.

    ``precision_program`` (a kind name or config dict, see
    :mod:`repro.api.program`) plus ``kv_bits=32`` arms the paged-KV
    watermark: an f32 cache pool is demoted to bf16 when pool pressure
    crosses the program's ``kv_watermark``.
    """
    from repro.api import PrecisionPolicy, RunSpec, Session

    precision = (PrecisionPolicy(weights=serve_bits, lazy=True,
                                 kv_cache=kv_bits)
                 if serve_bits < 32
                 else PrecisionPolicy.full_precision(kv_cache=kv_bits))
    options = {"steps": steps, "s_max": s_max, "prompt_len": prompt_len,
               "attn_impl": attn_impl, "requests": requests,
               "max_new": max_new, "quiet": quiet}
    if kv_layout is not None:
        options["kv_layout"] = kv_layout
    if page_size is not None:
        options["page_size"] = page_size
    if pool_pages is not None:
        options["pool_pages"] = pool_pages
    if vary_prompt:
        options["vary_prompt"] = True
    if precision_program is not None:
        options["precision_program"] = precision_program
    spec = RunSpec(
        arch=arch, workload="serve", mesh=mesh, smoke=smoke, seed=seed,
        batch=batch, seq=s_max, precision=precision, options=options)
    return Session(spec).serve()


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=16)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--s-max", type=int, default=64)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--serve-bits", "--bits", dest="serve_bits", type=int,
                    default=7, help="serving bit-width (<=7: int8, "
                    "8..15: int16, >=32: f32 baseline)")
    ap.add_argument("--attn-impl", choices=("ref", "flash"), default="ref",
                    help="prefill attention: jnp reference or Pallas flash kernel")
    ap.add_argument("--mesh", default="1x1")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--requests", type=int, default=None,
                    help="queue size (default 2x batch)")
    ap.add_argument("--max-new", type=int, default=None,
                    help="upper bound on per-request generation length")
    ap.add_argument("--kv-layout", choices=("paged", "contiguous"),
                    default=None, help="KV-cache layout (default: paged "
                    "where the family supports it)")
    ap.add_argument("--page-size", type=int, default=None,
                    help="tokens per KV page (paged layout)")
    ap.add_argument("--pool-pages", type=int, default=None,
                    help="shared page-pool size (default: the batch largest "
                    "queued requests)")
    ap.add_argument("--vary-prompt", action="store_true",
                    help="draw ragged prompt lengths (exercises the "
                    "prompt-length buckets)")
    ap.add_argument("--kv-bits", type=int, choices=(16, 32), default=32,
                    help="KV-cache storage: 32 = f32, 16 = bf16")
    ap.add_argument("--precision-program", default="",
                    help="adaptive precision controller (kind name or JSON "
                    "config); with --kv-bits 32 and a kv_watermark, paged "
                    "pools demote f32 -> bf16 under pool pressure, e.g. "
                    '\'{"kind": "constant", "kv_watermark": 0.9}\'')
    args = ap.parse_args(argv)
    from repro.launch.mesh import enable_compile_cache

    enable_compile_cache()

    program = None
    if args.precision_program:
        import json

        pp = args.precision_program
        program = json.loads(pp) if pp.lstrip().startswith("{") else pp
    return run_serve(
        args.arch, smoke=args.smoke, steps=args.steps, batch=args.batch,
        s_max=args.s_max, prompt_len=args.prompt_len,
        serve_bits=args.serve_bits, attn_impl=args.attn_impl, mesh=args.mesh,
        seed=args.seed, requests=args.requests, max_new=args.max_new,
        kv_layout=args.kv_layout, page_size=args.page_size,
        pool_pages=args.pool_pages, vary_prompt=args.vary_prompt,
        precision_program=program, kv_bits=args.kv_bits)


if __name__ == "__main__":
    main()
