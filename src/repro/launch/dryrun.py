"""Multi-pod dry-run CLI (deliverable e) — a thin shim over
:meth:`repro.api.Session.run_dryrun`.

For every (architecture x input shape x mesh) cell: build the sharded step,
``.lower().compile()`` it AOT (ShapeDtypeStructs only — no allocation),
print ``memory_analysis()`` / ``cost_analysis()``, and derive the roofline
terms (§Roofline).  Failures here are sharding bugs by definition.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch yi-6b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both \
        --out results/dryrun.json
"""

import argparse
import json
import os
import traceback


def run_cell(arch: str, shape_name: str, multi_pod: bool, *, verbose=True,
             variant: dict | None = None, precision=None):
    """Lower/compile/analyze one cell through the Session facade."""
    from repro.api import PrecisionPolicy, RunSpec, Session

    variant = dict(variant or {})
    if precision is None:
        # pre-facade contract: the bit knobs rode in the variant dict
        precision = PrecisionPolicy(
            weights=int(variant.get("serve_bits") or 32),
            comm=int(variant.get("grad_bits") or 32))
    spec = RunSpec(
        arch=arch, workload="dryrun",
        mesh="2x16x16" if multi_pod else "16x16", smoke=False,
        precision=precision,
        options={"shape": shape_name, "variant": variant})
    return Session(spec).run_dryrun(verbose=verbose)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--gather-bf16", action="store_true")
    ap.add_argument("--grad-bits", type=int, default=0)
    ap.add_argument("--capacity", type=float, default=0.0)
    ap.add_argument("--serve-bits", type=int, default=0)
    ap.add_argument("--no-remat", action="store_true")
    args = ap.parse_args(argv)
    from repro.launch.mesh import enable_compile_cache

    enable_compile_cache()
    # the multi-pod mesh needs 512 fake host devices; XLA reads the flag when
    # the backend starts, so add it (to whatever the caller set) before that
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count=512").strip()

    from repro.api import PrecisionPolicy
    from repro.configs import ARCH_NAMES, get_config, shapes_for

    # CLI shim: the bit knobs fold into one PrecisionPolicy; the cfg knobs
    # stay a variant dict (recorded in the output rows).  lazy stays off:
    # the AOT roofline measures the packed-storage gathers; the interpret-
    # mode Pallas body would skew the CPU cost model.
    precision = PrecisionPolicy(
        weights=args.serve_bits if args.serve_bits else 32,
        comm=args.grad_bits or 32)
    variant = {k: v for k, v in dict(
        gather_bf16=args.gather_bf16, capacity=args.capacity,
        no_remat=args.no_remat, grad_bits=args.grad_bits,
        serve_bits=args.serve_bits).items() if v}

    archs = list(ARCH_NAMES) if (args.all or not args.arch) else [args.arch]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    results = []
    for arch in archs:
        cfg = get_config(arch)
        shapes = [s.name for s in shapes_for(cfg)]
        if args.shape:
            shapes = [s for s in shapes if s == args.shape]
        for shape in shapes:
            for mp in meshes:
                try:
                    results.append(run_cell(arch, shape, mp, variant=variant,
                                            precision=precision))
                except Exception as e:
                    traceback.print_exc()
                    results.append(dict(arch=arch, shape=shape,
                                        mesh="2x16x16" if mp else "16x16",
                                        status="FAIL", error=str(e)[-2000:]))
                if args.out:
                    with open(args.out, "w") as f:
                        json.dump(results, f, indent=1)
    n_ok = sum(r.get("status") == "ok" for r in results)
    print(f"\n{n_ok}/{len(results)} cells OK")
    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)


if __name__ == "__main__":
    main()
