"""The comparison that decides ``correct``: numbers of a run's answers
against the plain reference's, each held to its limit.

An answer is a dict of host arrays, as the references return them: the
push log columns (``t``, ``user``, ``lag``, ``gap``, ``corun``,
``weight``), the per-user ``energy`` and ``updates``, and the traced
queues ``trace_Q`` and ``trace_H``.
"""
from __future__ import annotations

import numpy as np


def _rel(a, b, floor):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    if a.shape != b.shape:
        return float("inf")
    if not a.size:
        return 0.0
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), floor)))


def push_log_numbers(run: dict, ref: dict) -> dict:
    """A push is keyed by its slot and user (a user pushes at most once a
    slot).

    - ``pushes_differ``: share of the reference's pushes that the run
      lacks, has extra, or has with another ``corun`` or ``weight``;
    - ``lag_err``: the largest difference of a push's lag (in pushes),
      over the pushes both have;
    - ``gap_rel_err``: the largest relative error of a push's Eq. 4 gap,
      over the pushes both have with the same lag."""
    span = 1 + int(max(np.max(run["user"], initial=0),
                       np.max(ref["user"], initial=0)))

    def keys(a):
        return (np.asarray(a["t"], np.int64) * span
                + np.asarray(a["user"], np.int64))

    k_run, k_ref = keys(run), keys(ref)
    both, a, b = np.intersect1d(k_run, k_ref, return_indices=True)
    col = {k: (np.asarray(run[k])[a], np.asarray(ref[k])[b])
           for k in ("lag", "gap", "corun", "weight")}
    same_kind = ((col["corun"][0] == col["corun"][1])
                 & (col["weight"][0] == col["weight"][1]))
    missing = len(k_run) + len(k_ref) - 2 * len(both)
    lag_d = np.abs(col["lag"][0] - col["lag"][1])
    same_lag = lag_d == 0
    return {
        "pushes_differ": (missing + int(np.count_nonzero(~same_kind)))
        / max(len(k_ref), 1),
        "lag_err": float(lag_d.max()) if len(both) else 0.0,
        "gap_rel_err": _rel(col["gap"][0][same_lag], col["gap"][1][same_lag],
                            1e-30),
    }


def fleet_numbers(run: dict, ref: dict, L_b: float, with_log: bool) -> dict:
    """The compared numbers of one fleet run (all 0 for a run equal to the
    reference): those of ``push_log_numbers`` where the log is on, and

    - ``updates_differ``: share of users whose update count differs;
    - ``energy_rel_err``: the largest relative error of a user's energy;
    - ``Q_err``: the largest error of the traced request queue, relative
      to it and at least 1 (it counts requests);
    - ``H_err``: the largest error of the traced staleness queue,
      relative to it and at least L_b (each slot adds the gap sum less
      L_b, so that is the scale of its rounding)."""
    out = push_log_numbers(run, ref) if with_log else {}
    n = len(ref["updates"])
    if len(run["updates"]) != n:
        out["updates_differ"] = 1.0
    else:
        out["updates_differ"] = float(
            np.count_nonzero(np.asarray(run["updates"]) != ref["updates"])
            / max(n, 1))
    out["energy_rel_err"] = _rel(run["energy"], ref["energy"], 1e-30)
    out["Q_err"] = _rel(run["trace_Q"], ref["trace_Q"], 1.0)
    out["H_err"] = _rel(run["trace_H"], ref["trace_H"], float(L_b))
    return out


def verdict(numbers: dict, limits: dict) -> list:
    """``[(name, value, limit, ok)]`` for every compared number; a number
    without a limit is a fault of the configuration file."""
    rows = []
    for name, value in numbers.items():
        if name not in limits:
            raise KeyError(f"no limit for the compared number {name!r}")
        limit = float(limits[name])
        rows.append((name, float(value), limit,
                     bool(np.isfinite(value) and value <= limit)))
    return rows
