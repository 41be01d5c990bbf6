"""Shared benchmark plumbing: CSV emission + default horizons.

Every bench_* module exposes ``run(fast: bool) -> list[dict]`` rows; the
``benchmarks.run`` driver aggregates them into one CSV stream. fast=True
(default in CI) shrinks horizons; pass --full for the paper's 3-hour
settings.
"""
from __future__ import annotations

import csv
import io
import json
import platform
import sys
import time
from typing import Iterable, Optional


def emit(rows: Iterable[dict], header_done=set()) -> None:
    rows = list(rows)
    if not rows:
        return
    w = csv.DictWriter(sys.stdout, fieldnames=list(rows[0].keys()))
    w.writeheader()
    for r in rows:
        w.writerow(r)
    sys.stdout.flush()


def write_json(rows: Iterable[dict], path: str,
               meta: Optional[dict] = None) -> str:
    """Persist bench rows as a machine-readable artifact.

    The schema stays flat (e.g. ``BENCH_fig4_tradeoff.json``): a ``meta``
    header (timestamp, host) plus the same row dicts the CSV stream
    carries."""
    doc = {
        "meta": {
            "generated_unix": int(time.time()),
            "generated": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
            "host": platform.node(),
            "python": platform.python_version(),
            **(meta or {}),
        },
        "rows": list(rows),
    }
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)
        f.write("\n")
    # stderr: stdout is a pure CSV stream consumers may redirect
    print(f"# wrote {path}", file=sys.stderr, flush=True)
    return path
