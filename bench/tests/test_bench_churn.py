"""The churning fleet cell at a small size on the CPU: its files by name,
the churn reference against the program, the control and planted faults
against the comparison, and the reader of the churn phase's device time."""
from __future__ import annotations

import json

import ml_dtypes
import numpy as np
import pytest

from bench import compare, harness, readings, run, trace
from bench import traffic as gen
from bench.reference.fleet_churn import simulate

SPEC = harness.load_json(harness.ROOT / "BENCHMARK.json")
CELL = "fleet100k-online-dyn"
CONFIG = harness.load_json(harness.BENCH / "configs" / "fleet100k_churn.json")
LIMITS = CONFIG["limits"]
QUANTUM = CONFIG["dynamics"]["battery_quantum"]
N, HORIZON = 256, 300
MS = 1e6


def small_config(crossing=False):
    """The cell's calibration cut to 256 users x 300 slots: V and L_b
    scaled with the fleet so that Alg. 2 schedules at this size, the
    chains ten times as fast so that churn fires in half the horizon. With
    ``crossing`` the starting levels straddle ``battery_min`` and the
    battery moves five times as fast, so the gate turns users down and
    lets them up again mid-run."""
    cfg = json.loads(json.dumps(CONFIG))
    cfg.update(n_users=N, horizon_s=HORIZON)
    cfg["scenario"].update(V=10.0, L_b=2.5)
    dyn = cfg["dynamics"]
    dyn["p_off"] = [10 * p for p in dyn["p_off"]]
    dyn["p_on"] = 10 * dyn["p_on"]
    if crossing:
        for k in ("drain_train", "drain_corun", "charge_rate"):
            dyn[k] = 5 * dyn[k]
        cfg["battery_init"] = {"flat_share": 0.5, "flat": [0.1, 0.2],
                               "charged": [0.2, 0.4]}
    return cfg


@pytest.fixture(scope="module")
def small_spec(tmp_path_factory):
    path = tmp_path_factory.mktemp("churn") / "churn_small.json"
    path.write_text(json.dumps(small_config()))
    spec = json.loads(json.dumps(SPEC))
    for c in spec["configs"]:
        if c["name"] == "fleet100k-churn":
            c["file"] = str(path)
    return spec


def driver():
    return harness.resolve(SPEC, CELL).driver


def traffic():
    return harness.load_json(harness.BENCH / "traffic" / "online-log.json")


@pytest.fixture
def x64():
    import jax

    prev = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", True)
    yield
    jax.config.update("jax_enable_x64", prev)


def test_the_cell_resolves_to_its_files():
    from bench.drivers import fleet_scan

    c = harness.resolve(SPEC, CELL)
    assert c.chips == 1 and c.traffic == traffic()
    assert issubclass(c.driver.Cell, fleet_scan.Cell)
    assert {m["name"] for m in c.end_to_end} == {"setup_s",
                                                 "sim_user_slots_per_s"}
    assert set(c.per_layer) == {
        "sim.device_idle_share", "sim.device_ms_per_slot",
        "sim.host_prologue_ms", "sim.host_epilogue_ms",
        "sim.dynamics_ms_per_slot"}
    fleet = harness.load_json(harness.BENCH / "configs" / "fleet100k.json")
    assert set(LIMITS) == set(fleet["limits"]) | {
        "drops_differ", "battery_err", "counters_differ"}
    same = ("n_users", "horizon_s", "t_d", "fleet", "dtype")
    assert all(CONFIG[k] == fleet[k] for k in same)
    assert {k: v for k, v in CONFIG["scenario"].items() if k != "dynamics"} \
        == {k: v for k, v in fleet["scenario"].items() if k != "dynamics"}


@pytest.mark.parametrize("seed", [0, 2**31 + 7])
def test_the_battery_draw_leaves_the_other_inputs_alone(seed):
    cfg = small_config()
    a = gen.fleet_inputs(cfg, traffic(), seed)
    levels = driver().battery_draw(cfg, seed)
    b = gen.fleet_inputs(cfg, traffic(), seed)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert levels.shape == (N,) and np.all((levels >= 0) & (levels <= 1))
    assert np.array_equal(levels, driver().battery_draw(cfg, seed))
    assert not np.array_equal(levels, driver().battery_draw(cfg, seed + 1))


def run_engine(cfg, seed, engine):
    """One run of the cell's inputs at ``cfg`` on ``engine``: the
    simulator, its result, and the reference's answers."""
    from repro.core import MarkovChurnDynamics, Scenario
    from repro.core.arrivals import TraceArrivals

    d = driver()
    inputs = gen.fleet_inputs(cfg, traffic(), seed)
    levels = d.battery_draw(cfg, seed)
    sc = dict(cfg["scenario"], engine=engine, dynamics=MarkovChurnDynamics(
        battery_init=levels, **cfg["dynamics"]))
    sim = Scenario(policy="online",
                   arrivals=TraceArrivals(inputs["app_sched"],
                                          inputs["app_choice"]),
                   fleet=gen.program_fleet(inputs["device"]), seed=seed,
                   n_users=N, horizon_s=HORIZON, t_d=cfg["t_d"],
                   **sc).build()
    assert sim.resolve_engine() == engine
    r = sim.run()
    ref = simulate(inputs["device"], inputs["app_sched"],
                   inputs["app_choice"], levels, seed=seed, V=sc["V"],
                   L_b=sc["L_b"], epsilon=sc["epsilon"], eta=sc["eta"],
                   beta=sc["beta"], t_d=cfg["t_d"],
                   ready_delay=sc["ready_delay"], v_norm0=sc["v_norm0"],
                   trace_every=sc["trace_every"], **cfg["dynamics"])
    return sim, r, ref


@pytest.mark.parametrize("engine", ["vectorized", "jax"])
@pytest.mark.parametrize("crossing", [False, True],
                         ids=["cell", "crossing"])
def test_reference_equals_the_programs_float64_engines(x64, engine,
                                                       crossing):
    """Under float64 the program's schedule, drops, battery levels and
    churn counters are the reference's exactly; its reals are too on the
    NumPy engine, and within a last-place rounding on the scan."""
    cfg = small_config(crossing)
    sim, r, ref = run_engine(cfg, 2**31 + 3, engine)
    cols = dict(zip(("t", "user", "lag", "gap", "corun", "weight"),
                    r.push_log.arrays()))
    for name in ("t", "user", "lag", "corun", "weight"):
        assert np.array_equal(cols[name], ref[name]), name
    assert np.array_equal(sim.state.updates, ref["updates"])
    assert np.array_equal(sim.state.dyn["drops"], ref["drops"])
    assert np.array_equal(sim.state.dyn["battery"], ref["battery"])
    assert np.array_equal(r.trace_Q, ref["trace_Q"])
    assert (r.departures, r.recoveries, r.lost) == \
        (ref["departures"], ref["recoveries"], ref["lost"])
    exact = engine == "vectorized"
    for got, want in ((cols["gap"], ref["gap"]),
                      (sim.state.energy, ref["energy"]),
                      (r.trace_H, ref["trace_H"])):
        if exact:
            assert np.array_equal(got, want)
        else:
            np.testing.assert_allclose(got, want, rtol=1e-12, atol=1e-9)
    # the churn is not vacuous at this size
    assert r.lost > 0 and r.recoveries > 0 and r.departures >= 0
    assert len(cols["t"]) > 0 and r.mean_H > 0
    assert ref["battery_down_peak"] > 0 and ref["net_bad_peak"] > 0
    if crossing:
        assert ref["battery_down_ever"] > ref["battery_down_peak"]
        assert ref["battery_falls"] > 0 and ref["battery_rises"] > 0


@pytest.mark.parametrize("seed", [2**31 + 3, 17])
def test_float32_gate_crossings_land_in_the_references_slots(seed):
    """The battery is a whole count of quanta, so the float32 scan's
    levels are the float64 reference's exactly and every crossing of the
    gate happens in the same slot: the same pushes, drops and counters."""
    sim, r, ref = run_engine(small_config(crossing=True), seed, "jax")
    assert sim.state.energy.dtype == np.float32
    t, user, lag, _, corun, _ = r.push_log.arrays()
    for name, col in (("t", t), ("user", user), ("lag", lag),
                      ("corun", corun)):
        assert np.array_equal(col, ref[name]), name
    assert np.array_equal(sim.state.dyn["drops"], ref["drops"])
    assert np.array_equal(np.asarray(sim.state.dyn["battery"], np.float64),
                          ref["battery"])
    assert (r.departures, r.recoveries, r.lost) == \
        (ref["departures"], ref["recoveries"], ref["lost"])
    assert ref["battery_falls"] > 0 and ref["battery_rises"] > 0


def test_the_control_fails_the_comparison():
    """The reference computed in bfloat16, in the program's place."""
    cfg = small_config()
    d = driver()
    cell = d.Cell(cfg, traffic(), 11)
    ref = cell.reference()
    ctl = cell.reference(ml_dtypes.bfloat16)
    rows = compare.verdict(cell.numbers(ctl, ref), LIMITS)
    assert not all(ok for *_, ok in rows)
    assert all(v == 0 for v in cell.numbers(ref, ref).values())


def run_cell(spec, seed=2**31 + 99, trace_on=0):
    return run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                     "0.5", "--trace", str(trace_on)], require_chip=False,
                    spec=spec)


def test_a_sound_float32_run_is_correct(small_spec):
    res = run_cell(small_spec)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] >= 1
    assert set(res["metrics"]) == {"sim_user_slots_per_s", "setup_s"}
    assert set(res["checks"]) == set(LIMITS)
    assert all(c["value"] <= c["limit"] for c in res["checks"].values())


def _altered_dyn(name, by):
    """One user's final ``name`` off by ``by``, where the run's final
    state is produced."""
    def plant(monkeypatch):
        from repro.core import vector_engine

        orig = vector_engine._state_to_host

        def altered(state, jax):
            host = orig(state, jax)
            dyn = dict(host.dyn)
            leaf = np.array(dyn[name])
            leaf.flat[0] += by
            dyn[name] = leaf
            return host.replace(dyn=dyn)

        monkeypatch.setattr(vector_engine, "_state_to_host", altered)
    return plant


@pytest.mark.parametrize("fault", [
    _altered_dyn("battery", 10 * LIMITS["battery_err"] / QUANTUM),
    _altered_dyn("drops", 1), _altered_dyn("recoveries", 1)],
    ids=["battery", "drops", "recoveries"])
def test_a_planted_churn_fault_is_not_correct(small_spec, monkeypatch,
                                              fault):
    fault(monkeypatch)
    res = run_cell(small_spec)
    assert not res["correct"] and res["failed"] == res["attempted"]


def test_readings_give_the_program_and_the_control(small_spec):
    prog, ctl = readings.main(["--workload", CELL, "--seeds", "5",
                               "--control"], require_chip=False,
                              spec=small_spec)
    assert all(ok for *_, ok in compare.verdict(prog["numbers"], LIMITS))
    assert not all(ok for *_, ok in compare.verdict(ctl["numbers"], LIMITS))


def test_a_traced_window_hands_the_readers_the_chunks_scopes(small_spec,
                                                             monkeypatch):
    """On the CPU there is no device plane to read, but the driver hands
    the map from the instructions of the chunk the last run dispatched
    (the engine's record of it) to the slot step's scopes, the churn
    phase's among them."""
    from repro.core import vector_engine

    c = harness.resolve(small_spec, CELL)
    cell = c.driver.Cell(c.config, c.traffic, 21)
    cell.warm()
    assert "op_scope" not in cell.counts()
    cell.reset_counts()
    cell.unit()
    seen = []
    orig = vector_engine.ScanChunk.hlo_text

    def hlo_text(chunk):
        seen.append(chunk)
        return orig(chunk)

    monkeypatch.setattr(vector_engine.ScanChunk, "hlo_text", hlo_text)
    counts = cell.counts()
    assert seen == [cell.sim.scan_chunk]
    assert (seen[0].chunk, seen[0].cap) == (
        cell.T, vector_engine._next_pow2(max(1024, 2 * N)))
    scopes = {p for s in counts["op_scope"].values() for p in s.split("/")}
    assert {"slot.dynamics", "slot.policy"} <= scopes
    assert counts["slots"] == HORIZON
    monkeypatch.setattr(run, "TRACE_SECONDS", 0.2)
    monkeypatch.setattr(harness, "peaks", lambda kind: {})
    res = run_cell(small_spec, trace_on=1)
    assert res["correct"] and res["metrics"] == {}


def reader():
    return harness.load_module(harness.BENCH / "metrics" /
                               "sim.dynamics_ms_per_slot.py")


def test_the_reader_sums_the_churn_phase_per_slot():
    """Ops under ``slot.dynamics`` that ran while a chunk was dispatched
    and awaited count; an op of another program after the wait that
    reuses a chunk instruction's name does not."""
    ops = [("%fusion.1 = f32[100000] fusion(...)", 0, 4),
           ("fusion.2", 3, 6), ("fusion.3", 10, 30), ("copy.4", 40, 41),
           ("fusion.1", 60, 70)]
    spans = [("scan.chunk", -1 * MS, 1 * MS), ("scan.wait", 2 * MS, 50 * MS),
             ("scan.drain", 55 * MS, 80 * MS)]
    red = trace.Reduced(window=(0, 100 * MS),
                        ops={"/device:TPU:0": [(n, s * MS, e * MS)
                                               for n, s, e in ops]},
                        spans=spans)
    scopes = {"fusion.1": "jit(simulate)/slot.dynamics/add",
              "fusion.2": "jit(simulate)/while/body/slot.dynamics/rng",
              "fusion.3": "jit(simulate)/slot.policy/sort",
              "copy.4": ""}
    got = reader().read(red, {"slots": 3, "op_scope": scopes}, {})
    assert got == pytest.approx(6 / 3)       # union 0-6 ms over 3 slots
    assert reader().read(red, {"slots": 3}, {}) is None
    assert reader().read(red, {"slots": 3, "op_scope": {
        "fusion.3": "slot.policy"}}, {}) is None
    no_chunk = trace.Reduced(window=red.window, ops=red.ops, spans=[])
    assert reader().read(no_chunk, {"slots": 3, "op_scope": scopes}, {}) \
        is None
    empty = trace.Reduced(window=(0, MS), ops={}, spans=spans)
    assert reader().read(empty, {"slots": 3, "op_scope": scopes}, {}) \
        is None
