#!/usr/bin/env python3
"""Readings that the limits of a cell are set from, in one process.

    python3 bench/readings.py --workload <name> --seeds 1 2 3 [--control]

For each seed: the cell's set-up and one unit of its window (the timed
path), then the compared numbers of that unit against the plain
reference; with ``--control``, also the numbers of the control, which is
the reference itself computed one precision lower (``Cell.control_dtype``:
bfloat16 for the float32 configurations), against the reference. Prints
one JSON line per reading. The benchmark's own runs never run the
control. Runs on the chip; it checks for one as ``bench/run.py`` does.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402


def main(argv=None, require_chip: bool = True, spec: dict = None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    spec = spec or harness.load_json(ROOT / "BENCHMARK.json")
    cell = harness.resolve(spec, args.workload)
    if require_chip:
        harness.require_chips(cell.chips)
    harness.import_program(use_compile_cache=require_chip)
    out = []
    for seed in args.seeds:
        t0 = time.perf_counter()
        drv = cell.driver.Cell(cell.config, cell.traffic, seed)
        drv.unit()
        t1 = time.perf_counter()
        drv.free()
        ref = drv.reference()
        t2 = time.perf_counter()
        rows = [("program", drv.numbers(drv.runs[-1], ref), t1 - t0,
                 t2 - t1)]
        if args.control:
            ctl = drv.reference(drv.control_dtype)
            rows.append(("control", drv.numbers(ctl, ref),
                         time.perf_counter() - t2, t2 - t1))
        for who, numbers, run_s, ref_s in rows:
            line = {"workload": args.workload, "seed": seed, "who": who,
                    "numbers": numbers, "run_s": run_s, "reference_s": ref_s}
            print(json.dumps(line), flush=True)
            out.append(line)
    return out


if __name__ == "__main__":
    main()
