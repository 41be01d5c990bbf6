#!/usr/bin/env python3
"""Run one benchmark cell once on the chip.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up builds the cell from its files and the seed and runs it once, which
compiles every program the window uses; that is ``setup_s``. The window
then runs whole units back to back for ``--seconds``. ``--trace 0``
reports the cell's end-to-end metrics, ``--trace 1`` records a profiler
trace of a window of at most ``TRACE_SECONDS`` and reports its per-layer
metrics. After the window
every unit's answers are compared with the plain reference's.

Earlier lines say what was compiled inside the window; the last lines of
standard error give each compared number beside its limit, and the last
line of standard output is one JSON object. Without a TPU, or with fewer
chips than the cell asks for, the run exits non-zero before any work.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import compare, harness, trace  # noqa: E402

# A traced window: the profiler records every device op of every slot,
# and reading the trace back takes about twice the window on a TPU v5e.
# The window still runs at least one whole unit.
TRACE_SECONDS = 5.0


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def main(argv=None, require_chip: bool = True, spec: dict = None) -> dict:
    """Run the cell; returns the result that the last line prints.
    ``require_chip=False`` and ``spec`` exist for the tests, which drive
    a run at a small size on the CPU."""
    args = parse(argv)
    spec = spec or harness.load_json(ROOT / "BENCHMARK.json")
    cell = harness.resolve(spec, args.workload)
    if require_chip:
        harness.require_chips(cell.chips)
    harness.import_program(use_compile_cache=require_chip)
    import jax

    counter = harness.CompileCounter()
    annotate = jax.profiler.TraceAnnotation

    t0 = time.perf_counter()
    with annotate("bench.build"):
        drv = cell.driver.Cell(cell.config, cell.traffic, args.seed)
    with annotate("bench.warm"):
        drv.warm()
    setup_s = time.perf_counter() - t0
    log(f"set-up {setup_s:.3f} s: {counter.compiles} compiles "
        f"({counter.compile_s:.3f} s)")

    c0, tr0 = counter.compiles, counter.traces
    logdir = None
    if args.trace:
        logdir = tempfile.mkdtemp(prefix="bench_trace_")
        drv.reset_counts()
        # JAX's host events only: tracing every Python call slows the
        # host-driven cells several times over
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(logdir, profiler_options=opts)
        seconds = min(args.seconds, TRACE_SECONDS)
    else:
        seconds = args.seconds
    work, units, elapsed = harness.window(drv, seconds, annotate)
    if args.trace:
        jax.profiler.stop_trace()
    log(f"window {elapsed:.3f} s, {units} units: "
        f"{counter.compiles - c0} compiles and {counter.traces - tr0} "
        f"traces inside it")
    device = harness.device_info(cell.chips)
    counts = drv.counts()

    if args.trace:
        red = trace.reduce_xplane(trace.find_xplane(logdir))
        shutil.rmtree(logdir, ignore_errors=True)
        peak = harness.peaks(device["kind"])
        metrics = {}
        for m in (m for m in spec["per_layer"] if m["name"] in cell.per_layer):
            value = cell.per_layer[m["name"]].read(red, counts, peak)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        breakdown = red.breakdown(10)
    else:
        units_of = {m["name"]: m["unit"] for m in cell.end_to_end}
        metrics = {drv.work_metric: {"value": work / elapsed,
                                     "unit": units_of[drv.work_metric]},
                   harness.SETUP_METRIC: {
                       "value": setup_s,
                       "unit": units_of[harness.SETUP_METRIC]}}
        breakdown = None

    # the reference runs after the program's state is freed
    drv.free()
    t_ref = time.perf_counter()
    ref = drv.reference()
    per_unit = [drv.numbers(ans, ref) for ans in drv.runs]
    worst = {k: max(n[k] for n in per_unit) for k in per_unit[0]}
    limits = cell.config["limits"]
    failed = sum(not all(ok for *_, ok in compare.verdict(n, limits))
                 for n in per_unit)
    rows = compare.verdict(worst, limits)
    log(f"reference {time.perf_counter() - t_ref:.3f} s")
    for name, value, limit, ok in rows:
        log(f"check {name}: {value!r} (limit {limit!r}) "
            f"{'ok' if ok else 'FAILED'}")

    result = {"correct": failed == 0 and units > 0, "attempted": units,
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {name: {"value": value, "limit": limit}
                        for name, value, limit, _ in rows}
    print(json.dumps(result), flush=True)
    return result


if __name__ == "__main__":
    main()
