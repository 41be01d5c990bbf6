"""The compared numbers on small hand-made answers."""
from __future__ import annotations

import numpy as np
import pytest

from bench import compare


def log(rows):
    t, user, lag, gap, corun, weight = (np.asarray(c) for c in zip(*rows))
    return {"t": t, "user": user, "lag": lag, "gap": gap.astype(float),
            "corun": corun.astype(bool), "weight": weight.astype(float)}


REF = [(0, 3, 0, 0.0, 0, 1.0), (0, 7, 1, 0.5, 1, 1.0),
       (2, 3, 4, 1.0, 0, 1.0), (5, 1, 2, 0.25, 0, 1.0)]


def test_equal_logs_read_zero():
    assert compare.push_log_numbers(log(REF), log(REF)) == {
        "pushes_differ": 0.0, "lag_err": 0.0, "gap_rel_err": 0.0}


@pytest.mark.parametrize("run, differ, lag_err", [
    (REF[:3], 0.25, 0.0),                                 # one missing
    (REF + [(6, 2, 0, 0.0, 0, 1.0)], 0.25, 0.0),          # one extra
    (REF[:1] + [(0, 7, 1, 0.5, 0, 1.0)] + REF[2:], 0.25, 0.0),   # corun
    (REF[:2] + [(2, 3, 7, 1.0, 0, 1.0)] + REF[3:], 0.0, 3.0),    # lag
    (REF[:3] + [(5, 1, 2, 0.25, 0, 0.5)], 0.25, 0.0),     # weight
])
def test_push_differences(run, differ, lag_err):
    got = compare.push_log_numbers(log(run), log(REF))
    assert got["pushes_differ"] == pytest.approx(differ)
    assert got["lag_err"] == lag_err
    assert got["gap_rel_err"] == 0.0


def test_gap_error_only_where_lags_agree():
    run = [r[:3] + (r[3] * 1.01,) + r[4:] for r in REF]
    assert compare.push_log_numbers(log(run), log(REF))["gap_rel_err"] == \
        pytest.approx(0.01)
    run[2] = (2, 3, 5, 9.0, 0, 1.0)
    assert compare.push_log_numbers(log(run), log(REF))["gap_rel_err"] == \
        pytest.approx(0.01)


def test_fleet_numbers_scale_Q_and_H():
    ref = {"updates": np.array([1, 2, 3]), "energy": np.array([10., 20, 40]),
           "trace_Q": np.array([0., 4, 100]),
           "trace_H": np.array([0., 10, 5000])}
    run = dict(ref, updates=np.array([1, 2, 4]),
               energy=np.array([10., 20, 40.4]),
               trace_Q=np.array([1., 4, 100]),
               trace_H=np.array([2., 10, 5000]))
    got = compare.fleet_numbers(run, ref, L_b=1000.0, with_log=False)
    assert got == pytest.approx({"updates_differ": 1 / 3,
                                 "energy_rel_err": 0.01, "Q_err": 1.0,
                                 "H_err": 0.002})


def test_verdict_needs_a_limit_for_every_number():
    rows = compare.verdict({"a": 0.5, "b": float("nan")}, {"a": 1, "b": 1})
    assert [ok for *_, ok in rows] == [True, False]
    with pytest.raises(KeyError):
        compare.verdict({"c": 0.0}, {"a": 1})
