"""Read the numbers that set a cell's limits, on the chip, in one process.

    python -m chipbench.calibrate --workload <cell> --seconds <s> \\
        --seeds 11,12,... --control-seeds 11,12,13 [--out <file.jsonl>]

Every seed runs as the benchmark runs it (``chipbench.run.execute``:
set-up, the window at the cell's own load, the checks), and its line holds
the program's readings: the numbers ``correct`` compares.  For each control
seed it also reads

* the control: the plain reference computed one precision below the one the
  configuration states, put in the program's place (serving: int4 weights,
  at each position the reference gap of the token the control puts first;
  training: fp8, the configuration's ``control_dtype`` forward and
  ``control_grad_dtype`` gradients);
* the faults the cell can have, planted in the reference put in the
  program's place (training: half of every client's batch left out, the
  mean taken over the rest; serving: one served token of every request
  altered where it is produced).  A state left unchanged reads 1 on the
  change and needs no run.

One JSON line per seed; the benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys

import numpy as np

from chipbench import registry, run


def serve_readings(drv) -> dict:
    mod, ref, picked = drv.mod, drv.ref, drv.picked
    ctl = drv.reference(picked, bits=drv.cfg["control_weight_bits"])
    cg = [mod.served_gaps(r, np.argmax(c, axis=-1)) for r, c in zip(ref, ctl)]
    rng = np.random.RandomState(drv.seed % 2 ** 32)
    alt = []
    for lg, s in zip(ref, picked):
        toks = np.array(s.served)
        i = rng.randint(len(toks))
        toks[i] = rng.randint(2, drv.cfg["vocab_size"])
        alt.append(mod.served_gaps(lg, toks))
    return {"control_gap": float(np.concatenate(cg).max()),
            "altered_token_gap": float(np.concatenate(alt).max())}


def fl_readings(drv) -> dict:
    mod, lr = drv.mod, drv.cfg["lr"]
    out = {}
    for name, kw in (("control", {"control": True}),
                     ("half_batch", {"keep": 0.5})):
        got = mod.compare(drv.reference(**kw), drv.ref, lr)
        out.update({f"{name}_{k}": got[k]
                    for k in ("loss", "grad", "change", "grad_diff")})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(registry.ROOT / "src"))
    wl = registry.workload(args.workload)
    cfg = registry.config(wl["config"])
    mod = registry.config_module(wl["config"])

    from repro.launch.mesh import enable_compile_cache

    enable_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    device = run.device_info(wl["chips"])
    peak = registry.peaks(device["kind"])
    ctl = {int(s) for s in args.control_seeds.split(",") if s}
    read = serve_readings if cfg["kind"] == "serve" else fl_readings
    for seed in (int(s) for s in args.seeds.split(",")):
        result, lines = run.execute(wl, cfg, mod, seed, args.seconds, False,
                                    peak, device,
                                    readings=read if seed in ctl else None)
        row = {"seed": seed, "correct": result["correct"],
               **{k: v["value"] for k, v in result["metrics"].items()},
               **{k: c["value"] for k, c in result["checks"].items()},
               **result.get("readings", {}), "lines": lines}
        line = json.dumps(row)
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
