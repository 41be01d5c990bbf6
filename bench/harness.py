"""What every cell shares: finding its files by name, the chip, the clock.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix. The
configuration's file names the driver (``bench/drivers/<driver>.py``) that
the window loops over; the traffic mix is ``bench/traffic/<mix>.json``;
each per-layer metric is read by ``bench/metrics/<metric>.py``. Adding a
cell, mix, configuration or metric adds files and entries; no file here
names one.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
import time
from pathlib import Path
from types import ModuleType
from typing import Dict, List

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
SETUP_METRIC = "setup_s"


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path) -> ModuleType:
    name = "bench_file_" + "_".join(path.relative_to(BENCH).with_suffix("")
                                    .parts).replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    driver: ModuleType
    end_to_end: List[dict]
    per_layer: Dict[str, ModuleType]


def _in_cell(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(spec: dict, workload: str, root: Path = ROOT) -> Cell:
    """The files of one cell, found by the names in ``spec``."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; expected one of "
                       f"{sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    config = load_json(root / configs[w["config"]]["file"])
    traffic = load_json(BENCH / "traffic" / f"{w['traffic']}.json")
    driver = load_module(BENCH / "drivers" / f"{config['driver']}.py")
    e2e = [m for m in spec["end_to_end"] if _in_cell(m, workload)]
    names = {m["name"] for m in e2e}
    if SETUP_METRIC not in names or driver.Cell.work_metric not in names:
        raise ValueError(f"{workload}: end-to-end metrics {sorted(names)} "
                         f"lack {SETUP_METRIC!r} or the driver's "
                         f"{driver.Cell.work_metric!r}")
    per_layer = {m["name"]: load_module(BENCH / "metrics" /
                                        f"{m['name']}.py")
                 for m in spec["per_layer"] if _in_cell(m, workload)}
    return Cell(workload, int(w["chips"]), config, traffic, driver, e2e,
                per_layer)


def require_chips(n: int):
    """Exit non-zero before any work unless JAX holds ``n`` TPU chips."""
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        msg = f"JAX backend is {backend!r}, not a TPU"
    elif len(jax.devices()) < n:
        msg = f"{len(jax.devices())} TPU chip(s), the cell needs {n}"
    else:
        return
    print(f"bench: {msg}; nothing was run", file=sys.stderr)
    raise SystemExit(3)


def import_program(use_compile_cache: bool):
    """Put the program (``<checkout>/src``) on the path and, on the chip,
    keep JAX's compile cache where the program keeps it: the fixed
    ``<checkout>/.jax_cache``, or ``$JAX_COMPILATION_CACHE_DIR``."""
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        raise SystemExit(f"bench: the program is not at {src}")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    if use_compile_cache:
        import jax
        from repro.launch.cache import enable_compile_cache

        enable_compile_cache()
        # every program of the cell comes from the cache after the first
        # run, however short its compile
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


class CompileCounter:
    """Counts XLA compiles (one event each) and jaxpr traces, from the
    moment it is registered."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    TRACE = "/jax/core/compile/jaxpr_trace_duration"

    def __init__(self):
        import jax

        self.compiles = self.traces = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.COMPILE:
            self.compiles += 1
            self.compile_s += duration
        elif event == self.TRACE:
            self.traces += 1


def device_info(n_used: int) -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": max(
                (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                for d in devs[:max(n_used, 1)])}


def peaks(kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def window(drv, seconds: float, annotate) -> tuple:
    """Whole units back to back until ``seconds`` have passed; returns
    (work, units, wall seconds from the first start to the last end)."""
    work, units = 0.0, 0
    t0 = time.perf_counter()
    while True:
        with annotate("bench.unit"):
            work += drv.unit()
        units += 1
        if time.perf_counter() - t0 >= seconds:
            break
    return work, units, time.perf_counter() - t0
