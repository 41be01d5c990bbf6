"""The real-ML path (``Scenario(ml="lenet")`` on the vectorized engine)
against the plain LeNet-5 fleet reference, ``bench/reference/lenet_fl.py``,
at a small size: 4 clients on 160 samples over 900 slots from seeded
random weights. ``L_b`` is lowered to 1 so that the staleness queue rises
within the horizon and Alg. 2's decisions read the real momentum norm.

Under float32 and under ``jax_enable_x64`` (the model is float32 in both):
the schedule is exact, the momentum norm after each push and the model
after the first cohort agree to stated tolerances, and a second run of one
simulator repeats the first bit for bit without compiling anything.
"""
from __future__ import annotations

import os
import sys

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from bench import compare_ml, harness  # noqa: E402
from bench.reference import lenet_fl  # noqa: E402

CONFIG = harness.load_json(harness.BENCH / "configs" / "lenet25.json")
SMALL = dict(CONFIG, n_users=4, horizon_s=900, experiments_per_window=1,
             scenario=dict(CONFIG["scenario"], L_b=1.0),
             ml=dict(CONFIG["ml"], n_train=160, n_test=64, eval_every=300))
TRAFFIC = harness.load_json(harness.BENCH / "traffic" / "online-real.json")
DRIVER = harness.load_module(harness.BENCH / "drivers" / "lenet_fleet.py")
SEED = 2**31 + 1501

# float32 training in two programs: XLA's fused, batched epoch and the
# reference's step by step. Measured at this size: 1e-7 on the norms and
# 1e-8 on the first cohort's model; the tolerances leave 100x.
NORM_RTOL = 1e-5
PARAMS_RTOL = 1e-6
H_RTOL = 1e-9


def reference(cell):
    """The reference on the inputs of the cell's one experiment, its
    Alg. 2 reading its own momentum norms."""
    from repro.data.synthetic import cifarlike_dataset
    from repro.models.lenet import init_lenet

    ml, sc = SMALL["ml"], SMALL["scenario"]
    (exp,) = cell.experiments
    images, labels = cifarlike_dataset(ml["n_train"], seed=exp.seed,
                                       noise=ml["noise"])
    return lenet_fl.simulate(
        exp.inputs["device"], exp.inputs["app_sched"],
        exp.inputs["app_choice"], images, labels,
        init_lenet(jax.random.PRNGKey(exp.seed)), V=sc["V"], L_b=sc["L_b"],
        epsilon=sc["epsilon"], eta=sc["eta"], beta=sc["beta"],
        t_d=SMALL["t_d"], ready_delay=sc["ready_delay"],
        trace_every=sc["trace_every"], batch_size=ml["batch_size"])


@pytest.fixture(scope="module", params=["f32", "x64"])
def runs(request):
    prev = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", request.param == "x64")
    try:
        counter = harness.CompileCounter()
        cell = DRIVER.Cell(SMALL, TRAFFIC, SEED)
        cell.unit()
        before = counter.compiles
        cell.unit()
        second_run_compiles = counter.compiles - before
        cell.free()
        ref = reference(cell)
    finally:
        jax.config.update("jax_enable_x64", prev)
    return cell.runs, ref, second_run_compiles


def test_the_schedule_is_exact(runs):
    (run, _), ref, _ = runs
    assert len(ref["t"]) >= 8, "too few pushes to test anything"
    assert ref["trace_H"].max() > 0, "H never rose: Alg. 2 never read the norm"
    for k in ("t", "user", "lag", "corun", "weight", "updates", "energy",
              "trace_Q"):
        assert np.array_equal(run[k], ref[k]), k
    np.testing.assert_allclose(run["trace_H"], ref["trace_H"], rtol=H_RTOL)


def test_training_matches_the_reference(runs):
    (run, _), ref, _ = runs
    assert len(run["v_norms"]) == len(ref["t"]) == len(ref["v_norms"])
    np.testing.assert_allclose(run["v_norms"], ref["v_norms"],
                               rtol=NORM_RTOL)
    assert compare_ml._rel_l2(run["params_first"],
                              ref["params_first"]) < PARAMS_RTOL
    # the logged gaps are Eq. 4 of the norm before each push
    np.testing.assert_allclose(run["gap"], ref["gap"], rtol=NORM_RTOL)


def test_a_repeated_run_is_identical(runs):
    (first, second), _, _ = runs
    for k in compare_ml.LOG + ("accuracy", "v_norms", "params",
                               "params_first", "energy", "trace_H"):
        assert np.array_equal(first[k], second[k]), k
    assert len(first["accuracy"]) == 3      # slots 300, 600 and the end


def test_the_second_run_compiles_nothing(runs):
    assert runs[2] == 0
