"""Run one cell traced, as the benchmark does, and name its idle gaps by the
program's own spans as well as the harness's.

    python -m chipbench.span_breakdown --workload <cell> --seed <n> \\
        --seconds <s> [--keep <file.xplane.pb>]

The run is ``chipbench.run.execute`` with ``--trace 1``, and its result line
is printed first, as ``chipbench.run`` prints it.  The profiler trace it
took is then read again, with the program's ``repro.*`` spans from the
trace's host planes (:func:`chipbench.spans.from_profile`).  The second
line holds the device's longest idle gaps named by the innermost harness or
program span, each program span's summed seconds and count, and the same
two read from the program's in-process log on the trace's clock
(:func:`chipbench.spans.of`, what the per-layer readers use), so that the
two sources can be compared.  ``--keep`` keeps the trace file.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import types

from chipbench import registry, run


def breakdown(wl: dict, cfg: dict, mod, seed: int, seconds: float,
              peak: dict, device: dict, keep: str) -> tuple:
    """One traced run; returns ``(result, check_lines, spans_line)``, the
    last ``None`` where the run took no trace."""
    import jax

    from chipbench import spans, trace

    class KeepingCapture(trace.Capture):
        """Copies the trace out before the run deletes its directory."""

        def __exit__(self, *exc):
            out = super().__exit__(*exc)
            if self.xplane:
                os.makedirs(os.path.dirname(os.path.abspath(keep)),
                            exist_ok=True)
                shutil.copy(self.xplane, keep)
            return out

    plain, trace.Capture = trace.Capture, KeepingCapture
    try:
        result, lines = run.execute(wl, cfg, mod, seed, seconds, True, peak,
                                    device)
    finally:
        trace.Capture = plain
    if not os.path.isfile(keep):
        return result, lines, None
    pd = jax.profiler.ProfileData.from_file(keep)
    tr = trace.Trace.from_profile(pd)
    read = spans.Spans(tr, spans.from_profile(pd))
    logged = spans.of(types.SimpleNamespace(trace=tr))
    return result, lines, {
        "idle_gaps": read.idle_gaps(),
        "spans": read.totals(),
        "log_idle_gaps": logged.idle_gaps() if logged else None,
        "log_spans": logged.totals() if logged else None}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", default=None)
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
    keep = args.keep or str(run.TRACE_DIR / f"{wl['name']}.xplane.pb")
    result, lines, found = breakdown(wl, cfg, mod, args.seed, args.seconds,
                                     registry.peaks(device["kind"]), device,
                                     keep)
    for line in lines:
        print(line, file=sys.stderr)
    print(json.dumps(result), flush=True)
    print(json.dumps(found), flush=True)
    if args.keep is None and os.path.isfile(keep):
        os.remove(keep)
    return 0 if found is not None else 1


if __name__ == "__main__":
    sys.exit(main())
