"""Run one benchmark cell on the chip and print its result line.

    python -m chipbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (process start to the start of the window, compilation included)
builds the cell's driver from its configuration and workload files and
warms every program the window will run.  The window is measured for about
``--seconds``.  Then the peak device memory is read, the program's state
is freed, and the plain reference checks what the window's programs
produced; every number compared is printed beside its limit, as the last
lines of standard error and under ``checks`` in the result line.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` traces
the window with the profiler and reports its per-layer metrics, the
device's busy time and a breakdown.  A run that finds no TPU, or fewer
chips than the cell asks for, exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

from chipbench import registry  # noqa: E402

TRACE_DIR = registry.ROOT / ".chipbench_trace"


class Context:
    """What a per-layer metric reader sees."""

    def __init__(self, trace, counters, cfg, wl, mod, peak):
        self.trace, self.counters = trace, counters
        self.cfg, self.wl, self.mod, self.peak = cfg, wl, mod, peak


class CompileCounter:
    """Counts compilations that missed the persistent compilation cache (so
    ran the compiler) while ``armed``; a program loaded from the cache is
    not counted."""

    def __init__(self):
        from jax._src import monitoring

        self.armed, self.n = False, 0

        def on_event(event, **kw):
            if self.armed and event == "/jax/compilation_cache/cache_misses":
                self.n += 1

        monitoring.register_event_listener(on_event)


def device_info(chips: int) -> dict:
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"no TPU found (jax sees {devs[0].platform!r} "
                         "devices); this benchmark runs only on the chip")
    if len(devs) < chips:
        raise SystemExit(f"the cell asks for {chips} chips, jax sees "
                         f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak(chips: int) -> int:
    import jax

    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def finite(x) -> bool:
    return isinstance(x, (int, float)) and math.isfinite(x)


def execute(wl: dict, cfg: dict, mod, seed: int, seconds: float,
            trace: bool, peak: dict, device: dict, readings=None) -> tuple:
    """Set-up, window, checks and metrics of one run on the devices jax
    holds; returns ``(result, check_lines)``.  ``readings(driver)``, where
    given, runs after the checks and its dict goes under ``readings``."""
    import jax

    counter = CompileCounter()
    if trace:
        # a cell may trace a shorter window than it measures: the per-layer
        # metrics are rates and shares, and a long trace outgrows the run
        seconds = min(seconds, wl.get("trace_seconds", seconds))
    drv = registry.driver(cfg["kind"]).build(cfg, wl, mod, seed, seconds)
    drv.setup()
    gc.collect()                   # set-up's garbage goes before the window
    setup_s = time.perf_counter() - T_START

    counter.armed = True
    cap = None
    if trace:
        from chipbench.trace import Capture

        cap = Capture(str(TRACE_DIR / wl["name"]))
        with cap, jax.profiler.TraceAnnotation("chipbench.window"):
            e2e = drv.window()
    else:
        e2e = drv.window()
    counter.armed = False
    window_s = time.perf_counter() - T_START - setup_s
    device = dict(device, memory_peak_bytes=memory_peak(wl["chips"]))

    result = {"correct": False, "attempted": int(drv.attempted),
              "failed": int(drv.failed)}
    counters, problem = None, None
    try:
        counters = drv.counters()
    except ValueError as e:        # the traffic moved under the program
        problem = str(e)
    drv.release()
    gc.collect()

    checks = []
    t_ref = time.perf_counter()
    if problem is None:
        try:
            checks = drv.check()
        except Exception:          # a check that cannot finish is a failure
            problem = traceback.format_exc(limit=3)
    result["correct"] = (problem is None and bool(checks)
                         and all(finite(v) and v <= lim
                                 for _, v, lim in checks))

    names = registry.metrics_for(wl["name"],
                                 "per_layer" if trace else "end_to_end")
    metrics = {}
    if trace:
        from chipbench.trace import Trace

        tr = Trace.from_file(cap.xplane) if cap.xplane else None
        shutil.rmtree(cap.dir, ignore_errors=True)
        ctx = Context(tr, counters or {}, cfg, wl, mod, peak)
        for m in names:
            v = registry.metric_reader(m["name"])(ctx)
            if v is not None and finite(v):
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if tr is not None:
            device["busy_s"] = tr.busy_s()
            device["window_s"] = tr.window_s
            result["breakdown"] = {"device_ops": tr.top_ops(),
                                   "idle_gaps": tr.idle_gaps()}
    else:
        e2e["setup_s"] = setup_s
        for m in names:
            if m["name"] in e2e:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = device
    result["compiles_in_window"] = counter.n
    if readings is not None and problem is None:
        result["readings"] = readings(drv)
    result["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in checks}

    lines = [f"set-up {setup_s:.2f} s {drv.phases}, window {window_s:.2f} s "
             f"{drv.notes}, reference {time.perf_counter() - t_ref:.2f} s"]
    if problem is not None:
        lines.append(f"not correct: {problem}")
    lines.append(f"compiles in window: {counter.n}")
    for n, v, lim in checks:
        ok = "ok" if finite(v) and v <= lim else "FAIL"
        lines.append(f"check {n} {v!r} limit {lim!r} {ok}")
    return result, lines


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(registry.ROOT / "src"))
    wl = registry.workload(args.workload)
    cfg = registry.config(wl["config"])
    mod = registry.config_module(wl["config"])

    from repro.launch.mesh import enable_compile_cache

    enable_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    device = device_info(wl["chips"])
    peak = registry.peaks(device["kind"])
    result, lines = execute(wl, cfg, mod, args.seed, args.seconds,
                            bool(args.trace), peak, device)
    for line in lines:
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SystemExit as e:
        if isinstance(e.code, str):
            print(e.code, file=sys.stderr)
            sys.exit(3)
        raise
    except KeyError as e:
        print(f"error: {e}", file=sys.stderr)
        sys.exit(3)
