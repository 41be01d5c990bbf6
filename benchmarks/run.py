"""Paper results driver: one module per table or figure of the paper.

They reproduce the paper's energy, Q, H and accuracy results and Table
III's per-decision overhead. The system's speed is measured only by
``bench/`` on the chip.

    PYTHONPATH=src python -m benchmarks.run            # fast settings
    PYTHONPATH=src python -m benchmarks.run --full     # paper horizons
    PYTHONPATH=src python -m benchmarks.run --only fig4
"""
from __future__ import annotations

import argparse
import sys
import time

from benchmarks import (bench_fig4_tradeoff, bench_fig5_convergence,
                        bench_fig6_arrival, bench_table2_energy,
                        bench_table3_overhead)
from benchmarks.common import emit
from repro.launch.cache import enable_compile_cache

BENCHES = [
    ("table2", bench_table2_energy),
    ("table3", bench_table3_overhead),
    ("fig4", bench_fig4_tradeoff),
    ("fig6", bench_fig6_arrival),
    ("fig5", bench_fig5_convergence),
]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale horizons (3 h sim, 25 users)")
    ap.add_argument("--only", default=None,
                    help="comma-separated bench names")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None
    enable_compile_cache()

    failures = 0
    for name, mod in BENCHES:
        if only and name not in only:
            continue
        t0 = time.time()
        print(f"# === {name} ===", flush=True)
        try:
            emit(mod.run(fast=not args.full))
        except Exception as e:  # keep the suite going; report at the end
            failures += 1
            print(f"# {name} FAILED: {type(e).__name__}: {e}", flush=True)
        print(f"# {name} done in {time.time() - t0:.1f}s", flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
