"""The comparison that decides ``correct`` in the cells that train a real
model: the schedule as ``compare.fleet_numbers`` holds it, and training
where its rounding has not yet compounded.

Training rounds differently in the program and in the reference (fused
and batched programs, other products on the chip), and each push carries
the difference into the next one's starting point, so late in a run the
two models part however sound both are. The training numbers therefore
look at the first pushes only. The schedule's are taken over the whole
run against a reference whose Alg. 2 read the run's own momentum norms
(``lenet_fl.simulate(decision_norms=...)``): given its norms, the
schedule, the logged gaps and the queues are exact.

Where the products round their operands to bfloat16 (a TPU at its
default precision), a local epoch's 20 steps already part by a percent:
a rounding that tips a max-pool winner or a ReLU sign moves the next
step, and so on. The training numbers are then held to what a model that
trains as the reference does reads, well below what a model left as it
was reads (1), and not to the rounding of float32.

An answer is a dict of host arrays, as ``bench/reference/lenet_fl.py``
returns it: the push log columns, ``energy``, ``updates``, ``trace_Q``,
``trace_H``, ``v_norms`` (the server momentum norm after each push),
``params_first`` (the global model after the first slot that applied a
push, flattened) and ``params`` (at the end); the reference's also holds
``params0``, the initial model.
"""
from __future__ import annotations

import numpy as np

from bench import compare

LOG = ("t", "user", "lag", "gap", "corun", "weight")


def _rel_l2(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    if a.shape != b.shape:
        return float("inf")
    if not a.size:
        return 0.0
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _change_rel_l2(a, b, start) -> float:
    """``_rel_l2`` of ``a`` and ``b`` taken as changes from ``start``."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    if a.shape != b.shape:
        return float("inf")
    if not b.size:
        return 0.0
    start = np.asarray(start, np.float64)
    return _rel_l2(a - start, b - start)


def lenet_numbers(run: dict, ref: dict, L_b: float, first: int) -> dict:
    """The compared numbers of one run (all 0 for a run equal to the
    reference):

    - ``pushes_differ``, ``lag_err``, ``gap_rel_err``,
      ``updates_differ``, ``energy_rel_err``, ``Q_err``, ``H_err``: as in
      ``compare.fleet_numbers``, over the whole run;
    - ``early_v_norm_rel_err``: the largest relative error of the server
      momentum norm after each of the first ``first`` pushes (the norm the
      next gap is computed from); a model that does not train reads 1;
    - ``cohort1_change_rel_err``: the L2 error of the global model after
      the first slot that applied pushes, relative to the reference's
      change of the model from its initial one over that slot; a model
      left as it was reads 1."""
    out = compare.fleet_numbers(run, ref, L_b, with_log=True)
    out["early_v_norm_rel_err"] = compare._rel(
        np.asarray(run["v_norms"])[:first],
        np.asarray(ref["v_norms"])[:first], 1e-30)
    out["cohort1_change_rel_err"] = _change_rel_l2(
        run["params_first"], ref["params_first"], ref["params0"])
    return out
