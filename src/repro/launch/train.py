"""End-to-end FWQ-FL training CLI — a thin shim over :class:`repro.api.Session`.

Maps the paper's loop onto the mesh: each data-parallel group is an FL
client; every round the GBD co-design picks per-client bit-widths from the
simulated 5G channel + device fleet (``--scheme fixed`` skips the co-design
and trains at the spec's fixed PrecisionPolicy); one jitted shard_map step
trains at the quantized weights; energy/latency are accounted; checkpoints
land every k rounds and resume bit-identically.

On the CPU container run the smoke configs::

    PYTHONPATH=src python -m repro.launch.train --arch yi-6b --smoke \
        --rounds 20 --mesh 1x1
"""

from __future__ import annotations

import argparse
import logging


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--mesh", default="1x1", help="DATAxMODEL, e.g. 16x16")
    ap.add_argument("--batch", type=int, default=4, help="per-client batch")
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--scheme", default="fwq",
                    choices=["fwq", "full_precision", "unified_q", "rand_q",
                             "fixed"])
    ap.add_argument("--bits", type=int, default=32,
                    help="fixed weight bit-width (--scheme fixed only)")
    ap.add_argument("--grad-compression-bits", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    from repro.launch.mesh import enable_compile_cache

    enable_compile_cache()

    from repro.api import PrecisionPolicy, RunSpec, Session

    logging.basicConfig(level=logging.INFO)
    comm = args.grad_compression_bits or 32
    if args.scheme == "fixed":
        workload = "train"
        precision = PrecisionPolicy.uniform(args.bits, comm=comm)
    else:
        workload = "fl-orchestrate"
        precision = PrecisionPolicy(comm=comm)
    spec = RunSpec(
        arch=args.arch, workload=workload, mesh=args.mesh, smoke=args.smoke,
        seed=args.seed, batch=args.batch, seq=args.seq, rounds=args.rounds,
        precision=precision,
        options={"scheme": args.scheme, "lr": args.lr,
                 "ckpt_dir": args.ckpt_dir, "out": args.out})
    return Session(spec).run()


if __name__ == "__main__":
    main()
