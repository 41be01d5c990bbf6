"""The fleet cells at a small size on the CPU: the plain reference against
the program, the control and planted faults against the comparison, and a
run without a chip."""
from __future__ import annotations

import json

import ml_dtypes
import numpy as np
import pytest

from bench import compare, harness, readings, run, traffic as gen
from bench.reference.fleet_online import simulate

SPEC = harness.load_json(harness.ROOT / "BENCHMARK.json")
CONFIG = harness.load_json(harness.BENCH / "configs" / "fleet100k.json")
LIMITS = CONFIG["limits"]
LOG, NOLOG = "fleet100k-online-log", "fleet100k-online-nolog"
N_SMALL = 1500


@pytest.fixture(scope="module")
def small_spec(tmp_path_factory):
    cfg = dict(CONFIG, n_users=N_SMALL)
    path = tmp_path_factory.mktemp("fleet") / "fleet_small.json"
    path.write_text(json.dumps(cfg))
    spec = json.loads(json.dumps(SPEC))
    for c in spec["configs"]:
        if c["name"] == "fleet100k":
            c["file"] = str(path)
    return spec


def traffic(name):
    return harness.load_json(harness.BENCH / "traffic" / f"{name}.json")


def reference(inputs, dtype=np.float64):
    sc = CONFIG["scenario"]
    return simulate(inputs["device"], inputs["app_sched"],
                    inputs["app_choice"], V=sc["V"], L_b=sc["L_b"],
                    epsilon=sc["epsilon"], eta=sc["eta"], beta=sc["beta"],
                    t_d=CONFIG["t_d"], ready_delay=sc["ready_delay"],
                    v_norm0=sc["v_norm0"], trace_every=sc["trace_every"],
                    dtype=dtype)


@pytest.mark.parametrize("seed", [0, 2**31 + 5])
def test_inputs_are_the_papers_default_draws(seed):
    from repro.core import Scenario

    cfg = dict(CONFIG, n_users=300, horizon_s=200)
    inputs = gen.fleet_inputs(cfg, traffic("online-log"), seed)
    sim = Scenario(policy="online", n_users=300, horizon_s=200,
                   seed=seed).build()
    assert np.array_equal(inputs["device"], sim.fleet_spec.device_ids)
    assert np.array_equal(inputs["app_sched"], sim.app_sched)
    assert np.array_equal(inputs["app_choice"], sim.app_choice)


@pytest.mark.parametrize("seed", [3, 2**31 + 11])
def test_reference_equals_the_programs_float64_engine(seed):
    """The reference and the program's NumPy engine (float64 on the host)
    agree exactly, push log and all."""
    from repro.core import Scenario
    from repro.core.arrivals import TraceArrivals

    cfg = dict(CONFIG, n_users=N_SMALL)
    inputs = gen.fleet_inputs(cfg, traffic("online-log"), seed)
    sim = Scenario(policy="online", engine="vectorized", n_users=N_SMALL,
                   horizon_s=cfg["horizon_s"], seed=seed,
                   arrivals=TraceArrivals(inputs["app_sched"],
                                          inputs["app_choice"]),
                   fleet=gen.program_fleet(inputs["device"])).build()
    r = sim.run()
    ref = reference(inputs)
    for name, col in zip(("t", "user", "lag", "gap", "corun", "weight"),
                         r.push_log.arrays()):
        assert np.array_equal(col, ref[name]), name
    assert np.array_equal(sim.state.energy, ref["energy"])
    assert np.array_equal(sim.state.updates, ref["updates"])
    assert np.array_equal(r.trace_Q, ref["trace_Q"])
    assert np.array_equal(r.trace_H, ref["trace_H"])
    assert r.mean_H > 0, "the staleness queue never rose: H > 0 untested"


def test_the_control_fails_the_comparison():
    """The reference computed in bfloat16, in the program's place."""
    cfg = dict(CONFIG, n_users=N_SMALL)
    inputs = gen.fleet_inputs(cfg, traffic("online-log"), 7)
    ref = reference(inputs)
    ctl = reference(inputs, ml_dtypes.bfloat16)
    rows = compare.verdict(compare.fleet_numbers(ctl, ref, 1000.0, True),
                           LIMITS)
    assert not all(ok for *_, ok in rows)
    assert all(compare.fleet_numbers(ref, ref, 1000.0, True)[k] == 0
               for k in LIMITS)


def run_cell(spec, cell, seed=2**31 + 99):
    return run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                     "0.5", "--trace", "0"], require_chip=False, spec=spec)


@pytest.mark.parametrize("cell", [LOG, NOLOG])
def test_a_sound_run_is_correct(small_spec, cell):
    res = run_cell(small_spec, cell)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] >= 1
    assert set(res["metrics"]) == {"sim_user_slots_per_s", "setup_s"}
    assert list(res)[-1] == "checks"


def _frozen_chunks(monkeypatch):
    """A scan chunk that hands back the state it was given."""
    from repro.core import vector_engine

    orig = vector_engine._jax_chunk_fn

    def frozen(*a, **k):
        fn = orig(*a, **k)

        def chunk(*args):
            _, traces = fn(*args)
            return args[-1], traces
        return chunk

    monkeypatch.setattr(vector_engine, "_jax_chunk_fn", frozen)


def _altered_energy(monkeypatch):
    """One user's energy off by ten times its limit, where the run's final
    state is produced."""
    from repro.core import vector_engine

    orig = vector_engine._state_to_host

    def altered(state, jax):
        host = orig(state, jax)
        energy = np.array(host.energy)
        energy[0] *= 1 + 10 * LIMITS["energy_rel_err"]
        return host.replace(energy=energy)

    monkeypatch.setattr(vector_engine, "_state_to_host", altered)


def _altered_push(monkeypatch):
    """One push's lag off by more than its limit, where the log is
    produced."""
    from repro.core.engine_state import PushLog

    orig = PushLog.extend_rows

    def altered(self, rows):
        rows = np.array(rows)
        rows[0, 2] += LIMITS["lag_err"] + 1
        return orig(self, rows)

    monkeypatch.setattr(PushLog, "extend_rows", altered)


@pytest.mark.parametrize("cell, fault", [
    (LOG, _frozen_chunks), (NOLOG, _frozen_chunks),
    (LOG, _altered_energy), (NOLOG, _altered_energy),
    (LOG, _altered_push)])
def test_a_planted_fault_is_not_correct(small_spec, monkeypatch, cell,
                                        fault):
    fault(monkeypatch)
    res = run_cell(small_spec, cell)
    assert not res["correct"] and res["failed"] == res["attempted"]


def test_a_traced_run_records_a_window_of_at_most_trace_seconds(
        small_spec, monkeypatch):
    monkeypatch.setattr(run, "TRACE_SECONDS", 0.2)
    # the CPU has no entry in the table of peaks and no TPU device plane
    monkeypatch.setattr(harness, "peaks", lambda kind: {})
    res = run.main(["--workload", NOLOG, "--seed", "17", "--seconds", "600",
                    "--trace", "1"], require_chip=False, spec=small_spec)
    assert res["correct"] and res["attempted"] >= 1
    assert 0 < res["device"]["window_s"] < 60
    assert res["metrics"] == {} and res["device"]["busy_s"] == 0.0
    assert "breakdown" in res


def test_without_a_chip_the_run_exits_before_any_work(capsys):
    with pytest.raises(SystemExit) as exc:
        run.main(["--workload", LOG, "--seed", "1", "--seconds", "1",
                  "--trace", "0"])
    assert exc.value.code not in (0, None)
    assert capsys.readouterr().out == ""


def test_readings_give_the_program_and_the_control(small_spec):
    lines = readings.main(["--workload", LOG, "--seeds", "5", "--control"],
                          require_chip=False, spec=small_spec)
    prog, ctl = lines
    assert prog["who"] == "program" and ctl["who"] == "control"
    assert all(ok for *_, ok in compare.verdict(prog["numbers"], LIMITS))
    assert not all(ok for *_, ok in compare.verdict(ctl["numbers"], LIMITS))
