"""Memory-aware auto-tuning of the jax chunked scan's shape knobs.

The scan (core/vector_engine.py) has two knobs that used to be global
heuristics and stop scaling past ~1M users:

- ``SimConfig.jax_chunk`` — slots per compiled ``lax.scan`` chunk. The
  chunk-proportional device cost is the ``(chunk, n/D)`` arrival slice
  (plus the stacked per-slot trace outputs); too big a chunk at a 10M-row
  shard blows device memory, too small a chunk pays dispatch overhead per
  chunk. ``jax_chunk=0`` resolves here against the per-device budget.
- push-buffer capacity — the legacy ``max(1024, 2 * n_users)`` guess
  allocates a ~960 MB replicated buffer at n=10M. The training pipeline
  bounds pushes per chunk by ``n * chunk / cycle_slots`` (a user must
  train ``min t_train`` seconds and sit out ``ready_delay`` + 1 slots
  between pushes), which is orders of magnitude tighter at fleet scale.
  Under-estimates stay safe: the driver detects buffer overflow by count
  and re-runs the chunk doubled.

Budgets come from the accelerator's ``memory_stats()`` (GPU/TPU
``bytes_limit``; an accelerator without one is an error) and, on the CPU
backend only, from system RAM split over the (possibly forced-host)
device count — so the same tuner sizes a CPU smoke test and a TPU run.
"""
from __future__ import annotations

import os
from types import SimpleNamespace

import numpy as np

from .simulator import n_slots

__all__ = ["autotune_scan_params", "device_memory_budget",
           "estimate_device_bytes"]

# Modeled per-user resident bytes of one sharded scan row (x64): 11
# EngineState SoA leaves + 8 catalog gathers + up to 7 dynamics leaves at
# 8 B each — bools/int8 leaves round UP toward safety.
_STATE_BYTES_PER_USER = 26 * 8
# Arrival operands are resident for the whole horizon: 1 B bool schedule
# + 4 B int32 app choice per user per slot.
_ARRIVAL_BYTES_PER_SLOT = 5
_PUSH_ROW_BYTES = 6 * 8           # (t, user, lag, gap, corun, weight) f64


def _next_pow2(k: int) -> int:
    c = 1
    while c < k:
        c <<= 1
    return c


def _prev_pow2(k: int) -> int:
    return _next_pow2(max(int(k), 1) + 1) >> 1 if k >= 1 else 1


def device_memory_budget(n_devices: int = 1, fraction: float = 0.25) -> int:
    """Usable bytes per device for the scan's operands: the device's
    reported ``bytes_limit`` on an accelerator, system RAM split over the
    ``n_devices`` host devices on the CPU backend. An accelerator that
    reports no limit is an error, never sized from host RAM.
    ``fraction`` leaves headroom for XLA temporaries, the replicated
    scalars and the rest of the process."""
    import jax

    dev = jax.local_devices()[0]
    if dev.platform == "cpu":
        limit = (os.sysconf("SC_PAGE_SIZE")
                 * os.sysconf("SC_PHYS_PAGES")) // max(int(n_devices), 1)
    else:
        limit = (dev.memory_stats() or {}).get("bytes_limit")
        if not limit:
            raise RuntimeError(
                f"{dev.platform} device {dev.device_kind!r} reports no "
                "memory_stats()['bytes_limit']; pass mem_bytes or set "
                "jax_chunk explicitly")
    return int(limit * fraction)


def estimate_device_bytes(n: int, T: int, chunk: int, capacity: int,
                          n_devices: int = 1, dyn_active: bool = False,
                          collect: bool = False) -> int:
    """Modeled peak per-device bytes of a sharded run: resident state
    rows + whole-horizon arrival columns for this device's shard, the
    in-flight ``(chunk, rows)`` arrival slice, and the replicated push
    buffer."""
    rows = -(-int(n) // max(int(n_devices), 1))
    per_user = _STATE_BYTES_PER_USER if dyn_active else 19 * 8
    per_slot = rows * _ARRIVAL_BYTES_PER_SLOT
    return int(rows * per_user + T * per_slot + chunk * per_slot
               + (capacity * _PUSH_ROW_BYTES if collect else 0))


def autotune_scan_params(sim, n_devices: int = 1, mem_bytes=None):
    """Pick ``(jax_chunk, push_capacity)`` for a built ``FederatedSim``
    from the per-device memory budget (``mem_bytes`` overrides the probed
    budget — tests pin it). Returns a namespace with the chosen knobs,
    the budget, and the modeled per-device footprint at those knobs."""
    cfg = sim.cfg
    n, T = cfg.n_users, n_slots(cfg)
    D = max(int(n_devices), 1)
    rows = -(-n // D)
    budget = device_memory_budget(D) if mem_bytes is None else int(mem_bytes)
    # chunk: cap the in-flight (chunk, rows) arrival slice at 1/8 of the
    # budget; floor 64 slots (dispatch amortization), ceiling 16384 (trace
    # time and program size grow with the unrolled chunk graph), never
    # past the horizon
    per_slot = max(rows * _ARRIVAL_BYTES_PER_SLOT, 1)
    chunk = max(64, budget // (8 * per_slot))
    chunk = _prev_pow2(min(chunk, 16384))
    if T:
        chunk = min(chunk, T)
    # push capacity: pushes per chunk are bounded by the training cycle —
    # min t_train slots of training + ready_delay cooldown + 1 waiting
    # slot between consecutive pushes of one user; 2x safety, pow2.
    # An overflowing chunk is re-run doubled, so a tight guess costs a
    # (rare) recompile, never correctness.
    tt = np.asarray(sim.fleet_spec.tables.t_train, dtype=float)
    cycle = max(float(tt.min()) / cfg.t_d + cfg.ready_delay + 1.0, 1.0) \
        if tt.size else 1.0
    per_chunk = n * min(chunk, T or chunk) / cycle
    cap = _next_pow2(max(int(2.0 * per_chunk) + 64, 1024))
    # never let the buffer itself dominate the budget
    cap = min(cap, _next_pow2(max(budget // (2 * _PUSH_ROW_BYTES), 1024)))
    est = estimate_device_bytes(
        n, T, chunk, cap if cfg.collect_push_log else 0, D,
        dyn_active=sim.dynamics.active, collect=cfg.collect_push_log)
    return SimpleNamespace(jax_chunk=int(chunk), push_capacity=int(cap),
                           device_budget=int(budget),
                           est_bytes_per_device=int(est))
