"""Fault-injection harness for the device-dynamics layer (core/dynamics.py).

Each named scenario is a churn regime — steady availability churn, a mass
dropout spike, a battery blackout, flapping availability, a bad-network
regime — run through all three engines. The suite pins:

- loop / vectorized / jax push-log DIGEST parity under every scenario and
  both dropout rules (the acceptance criterion: churn must not break the
  engines' bit-parity contract);
- queue-invariant preservation under churn (``in_flight`` never negative
  and always equal to the number of training users; Q/H never negative),
  checked every slot by an instrumented policy;
- the robustness headline: a started user goes down mid-training
  (``result.drops > 0``) and the run stays consistent;
- PushBuffer overflow round-trips losslessly when churn recovery floods a
  slot with pushes (satellite: the jax event buffer's doubling retry);
- the fault monitors (repro.fault) wired to the simulator's slot clock:
  replaying a churned run's push stream evicts exactly the users that
  went silent, and evicted users re-enter on their next push.
"""
import hashlib

import numpy as np
import pytest

from repro.core import (FederatedSim, ImmediatePolicy, MarkovChurnDynamics,
                        Scenario, SimConfig)
from repro.core.dynamics import (NoDynamics, dynamics_support,
                                 registered_dynamics, resolve_dynamics)
from repro.core.engine_state import MODE_TRAIN
from repro.fault import FleetMonitor

# ---------------------------------------------------------------------------
# Fault scenarios: name -> (sim kwargs, dynamics kwargs)
# ---------------------------------------------------------------------------
BASE = dict(n_users=16, horizon_s=1200, seed=7, app_arrival_p=0.01,
            policy="immediate")

SCENARIOS = {
    # steady background churn: the common case
    "churn": dict(p_off=0.01, p_on=0.05),
    # mass dropout spike: a fifth of the fleet drops every slot
    "mass_dropout": dict(p_off=0.2, p_on=0.05),
    # battery blackout: thin batteries + heavy train drain collapse
    # participation until chargers catch up (DEAL-style gating)
    "battery_blackout": dict(p_off=0.0, p_on=1.0, battery_init=0.35,
                             drain_train=5e-3, drain_corun=8e-3,
                             charge_rate=2e-4, battery_min=0.2),
    # flapping availability: rapid off/on cycling
    "flapping": dict(p_off=0.3, p_on=0.5),
    # bad network regime: churn plus long re-arrival delays
    "net_degraded": dict(p_off=0.02, p_on=0.1, p_net_bad=0.1,
                         p_net_recover=0.05, net_delay_slots=40),
}


def _dyn(scenario: str, dropout: str = "lose") -> MarkovChurnDynamics:
    return MarkovChurnDynamics(dropout=dropout, resume_penalty_s=20.0,
                               **SCENARIOS[scenario])


def _digest(log) -> str:
    h = hashlib.sha256()
    for e in log:
        h.update(f'{e["t"]},{e["user"]},{e["lag"]},{e["gap"]!r},'
                 f'{int(e["corun"])};'.encode())
    return h.hexdigest()


def _run(engine, dynamics, **over):
    kw = dict(BASE, **over)
    return Scenario(engine=engine, dynamics=dynamics, **kw).run()


@pytest.fixture(scope="module", autouse=True)
def _x64():
    """f64 scan parity with the host engines (same contract as the
    golden jax tests)."""
    import jax
    prev = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", True)
    yield
    jax.config.update("jax_enable_x64", prev)


# ---------------------------------------------------------------------------
# Three-engine parity under every fault scenario
# ---------------------------------------------------------------------------
class TestEngineParityUnderChurn:
    @pytest.mark.parametrize("scenario", list(SCENARIOS))
    @pytest.mark.parametrize("dropout", ["lose", "resume"])
    def test_push_log_digests_identical(self, scenario, dropout):
        dyn = _dyn(scenario, dropout)
        rl = _run("loop", dyn)
        rv = _run("vectorized", dyn)
        rj = _run("jax", dyn)
        d = _digest(rl.push_log)
        assert _digest(rv.push_log) == d
        assert _digest(rj.push_log) == d
        assert rl.updates == rv.updates == rj.updates
        assert rl.drops == rv.drops == rj.drops
        assert rv.energy_j == pytest.approx(rl.energy_j, rel=1e-9)
        assert rj.energy_j == pytest.approx(rl.energy_j, rel=1e-9)
        assert rv.mean_Q == pytest.approx(rl.mean_Q, rel=1e-9, abs=1e-12)
        assert rj.mean_Q == pytest.approx(rl.mean_Q, rel=1e-9, abs=1e-12)
        assert rv.mean_H == pytest.approx(rl.mean_H, rel=1e-6, abs=1e-9)
        assert rj.mean_H == pytest.approx(rl.mean_H, rel=1e-6, abs=1e-9)

    @pytest.mark.parametrize("policy", ["online", "offline", "sync",
                                        "eps_greedy"])
    def test_parity_holds_for_other_policies(self, policy):
        dyn = _dyn("churn")
        kw = {} if policy != "eps_greedy" else dict(seed=11)
        rl = _run("loop", dyn, policy=policy, **kw)
        rv = _run("vectorized", dyn, policy=policy, **kw)
        rj = _run("jax", dyn, policy=policy, **kw)
        assert _digest(rl.push_log) == _digest(rv.push_log) == \
            _digest(rj.push_log)
        assert rl.drops == rv.drops == rj.drops

    def test_churn_actually_bites(self):
        """The scenarios are not vacuous: churn drops trainers and costs
        updates relative to the always-on fleet."""
        r0 = _run("vectorized", "none")
        rc = _run("vectorized", _dyn("mass_dropout"))
        assert r0.drops == 0
        assert rc.drops > 0
        assert rc.updates < r0.updates
        assert rc.energy_j < r0.energy_j      # down devices draw nothing


# ---------------------------------------------------------------------------
# The robustness headline: mid-training dropout with consistent queues
# ---------------------------------------------------------------------------
class _InvariantPolicy(ImmediatePolicy):
    """Immediate scheduling plus a per-slot audit of the scheduler's
    bookkeeping: ``in_flight`` must equal the number of training users
    (a mid-training dropout must decrement it exactly once) and never go
    negative; the Lyapunov queues must stay non-negative."""

    name = "invariant-audit"

    def __init__(self):
        self.violations = []

    def decide_loop(self, sim, t, waiting, carry):
        n_train = sum(1 for u in sim.users if u.mode == "training")
        self._audit(t, sim.in_flight, n_train, sim.sched.Q, sim.sched.H)
        return super().decide_loop(sim, t, waiting, carry)

    def decide_vectorized(self, eng, t, carry):
        n_train = int(np.count_nonzero(eng.s.mode == MODE_TRAIN))
        self._audit(t, int(eng.s.in_flight), n_train,
                    eng.sched.Q, eng.sched.H)
        return super().decide_vectorized(eng, t, carry)

    def _audit(self, t, in_flight, n_train, Q, H):
        if in_flight < 0:
            self.violations.append((t, "in_flight negative", in_flight))
        if in_flight != n_train:
            self.violations.append(
                (t, "in_flight != #training", in_flight, n_train))
        if Q < 0 or H < 0:
            self.violations.append((t, "negative queue", Q, H))


class TestMidTrainingDropout:
    @pytest.mark.parametrize("engine", ["loop", "vectorized"])
    @pytest.mark.parametrize("dropout", ["lose", "resume"])
    def test_started_user_drops_and_queues_stay_consistent(self, engine,
                                                           dropout):
        pol = _InvariantPolicy()
        r = _run(engine, _dyn("mass_dropout", dropout), policy=pol)
        assert r.drops > 0            # started users went down mid-run
        assert pol.violations == []
        assert r.mean_Q >= 0.0 and r.mean_H >= 0.0

    @pytest.mark.parametrize("dropout", ["lose", "resume"])
    def test_jax_final_state_queue_consistent(self, dropout):
        """The scan cannot host a per-slot Python audit; pin the final
        carry instead — in_flight == #training and non-negative — plus
        digest parity with the audited numpy run."""
        dyn = _dyn("mass_dropout", dropout)
        cfg = SimConfig(engine="jax", dynamics=dyn, **BASE)
        sim = FederatedSim(cfg)
        rj = sim.run()
        es = sim.state
        assert int(es.in_flight) >= 0
        assert int(es.in_flight) == int(np.count_nonzero(
            es.mode == MODE_TRAIN))
        pol = _InvariantPolicy()
        rv = _run("vectorized", dyn, policy=pol)
        assert pol.violations == []
        assert _digest(rj.push_log) == _digest(rv.push_log)

    def test_resume_rule_pays_extra_lag(self):
        """A resumed dropout finishes late: with everything else equal,
        the resume fleet's pushes land with at least the lose fleet's
        total delay, and paused slots make no progress (fewer or equal
        updates than an un-churned run)."""
        r_none = _run("vectorized", "none")
        r_resume = _run("vectorized", _dyn("churn", "resume"))
        assert r_resume.drops > 0
        assert r_resume.updates <= r_none.updates

    def test_drops_counts_down_edges_of_trainers_only(self):
        """No training => no mid-training drops, however hard the
        availability churn."""
        r = _run("vectorized", _dyn("flapping"),
                 app_arrival_p=0.0, policy="online", V=1e9)
        assert r.updates == 0
        assert r.drops == 0


# ---------------------------------------------------------------------------
# PushBuffer overflow under a churn-inflated push burst (satellite)
# ---------------------------------------------------------------------------
class TestPushBufferChurnBurst:
    def test_mass_recovery_burst_round_trips_losslessly(self):
        """Flapping availability synchronizes re-entries, so single slots
        flood the jax event buffer; a capacity-1 buffer must still
        produce the exact log of an amply-sized one (doubling retry).
        Resume dropout: under "lose" this churn rate never lets a
        training run complete, so there would be no pushes to buffer."""
        dyn = MarkovChurnDynamics(p_off=0.3, p_on=0.9, dropout="resume")
        kw = dict(BASE, n_users=32, horizon_s=600)
        tiny = Scenario(engine="jax", dynamics=dyn,
                        push_log_capacity=1, **kw).run()
        ample = Scenario(engine="jax", dynamics=dyn,
                         push_log_capacity=4096, **kw).run()
        assert len(tiny.push_log) > 0
        assert _digest(tiny.push_log) == _digest(ample.push_log)
        assert [e["weight"] for e in tiny.push_log] == \
            [e["weight"] for e in ample.push_log]
        # and the host engine agrees
        host = Scenario(engine="vectorized", dynamics=dyn, **kw).run()
        assert _digest(host.push_log) == _digest(tiny.push_log)


# ---------------------------------------------------------------------------
# Fault monitors on the simulator's slot clock
# ---------------------------------------------------------------------------
class TestMonitorIntegration:
    def test_replay_evicts_churned_users_and_readmits_them(self):
        r = _run("vectorized", _dyn("churn"), horizon_s=2400)
        T = 2400
        log = list(r.push_log)
        assert len(log) > 0
        mon = FleetMonitor(timeout_slots=400)
        evictions = mon.replay(r.push_log, T)
        # churn silences users long enough to trip the heartbeat timeout
        assert evictions
        # every eviction is justified: no push from that user inside the
        # timeout window before the eviction slot
        for slot, uid in evictions:
            recent = [e for e in log
                      if e["user"] == uid and slot - 400 <= e["t"] <= slot]
            assert not recent, (slot, uid, recent)
        # eviction is non-final: at least one evicted user pushes again
        # (the simulator's recovery path re-enters the arrival process)
        readmitted = [uid for slot, uid in evictions
                      if any(e["user"] == uid and e["t"] > slot
                             for e in log)]
        assert readmitted

    def test_no_evictions_without_churn(self):
        """Timeout above the fleet's natural worst-case push interval:
        an always-on fleet must never trip the heartbeat."""
        r = _run("vectorized", "none")
        mon = FleetMonitor(timeout_slots=800)
        assert mon.replay(r.push_log, 1200) == []


# ---------------------------------------------------------------------------
# Registry / construction-time validation
# ---------------------------------------------------------------------------
class TestDynamicsConfigValidation:
    def test_registry_contains_shipped_dynamics(self):
        assert {"none", "markov"} <= set(registered_dynamics())
        assert isinstance(resolve_dynamics("none"), NoDynamics)
        assert resolve_dynamics("none") is resolve_dynamics("none")

    def test_unknown_name_rejected_at_config_time(self):
        with pytest.raises(ValueError, match="unknown dynamics"):
            SimConfig(dynamics="nope")

    def test_bad_knobs_rejected(self):
        with pytest.raises(ValueError, match="p_off"):
            MarkovChurnDynamics(p_off=1.5)
        with pytest.raises(ValueError, match="battery_min"):
            MarkovChurnDynamics(battery_min=2.0)
        with pytest.raises(ValueError, match="dropout"):
            MarkovChurnDynamics(dropout="retry")
        with pytest.raises(ValueError, match="net_delay_slots"):
            MarkovChurnDynamics(net_delay_slots=-1)

    def test_supports_jax_flag_without_hook_rejected(self):
        from repro.core.dynamics import DeviceDynamics

        class _Lying(MarkovChurnDynamics):
            name = "lying-test"
            # flag stays True but the hook is the base stub again
            scan_step = DeviceDynamics.scan_step

        lie = _Lying()
        assert not dynamics_support(lie)["jax"]
        with pytest.raises(ValueError, match="supports_jax"):
            SimConfig(dynamics=lie)

    def test_dynamics_without_jax_hook_degrades_engine(self):
        class _HostOnly(MarkovChurnDynamics):
            name = "host-only-test"
            supports_jax = False

        sim = Scenario(engine="jax", dynamics=_HostOnly(),
                       **BASE).build()
        assert sim.resolve_engine() == "vectorized"
        sim0 = Scenario(engine="jax", dynamics="none", **BASE).build()
        assert sim0.resolve_engine() == "jax"

    def test_per_device_class_probabilities_gather_per_user(self):
        # one p_off per catalog row of the paper fleet (4 device classes)
        sim = Scenario(dynamics=MarkovChurnDynamics(
            p_off=[0.1, 0.2, 0.3, 0.4],
            p_on=0.5), **BASE).build()
        dev = sim.fleet_spec.device_ids
        expected = np.asarray([0.1, 0.2, 0.3, 0.4])[dev]
        np.testing.assert_array_equal(sim.state.dyn["p_off"], expected)
        assert sim.state.dyn["p_off"].shape == (BASE["n_users"],)

    def test_wrong_length_class_vector_rejected(self):
        with pytest.raises(ValueError, match="per-device-class"):
            Scenario(dynamics=MarkovChurnDynamics(p_off=[0.1, 0.2]),
                     **BASE).build()

    def test_none_is_inactive_and_stateless(self):
        sim = Scenario(dynamics="none", **BASE).build()
        assert sim.state.dyn is None
        assert not sim.dynamics.active


# ---------------------------------------------------------------------------
# battery_init forms and the churn counters
# ---------------------------------------------------------------------------
# a regime where every rule fires: availability and network churn, and
# batteries that cross battery_min both ways
MIXED = dict(p_off=0.005, p_on=0.1, drain_train=1e-3, drain_corun=2e-3,
             charge_rate=5e-4, battery_min=0.2, p_net_bad=0.05)


def _outcome(sim, r):
    """What must not differ between runs that should be the same: the
    push keys, the per-user drops and battery levels, the counters."""
    t, u, lag, _, corun, _ = r.push_log.arrays()
    return ([np.asarray(a).tolist() for a in (t, u, lag, corun)],
            np.asarray(sim.state.dyn["drops"]).tolist(),
            np.asarray(sim.state.dyn["battery"], np.float64).tolist(),
            (r.drops, r.departures, r.recoveries, r.lost))


class TestBatteryInitForms:
    N = BASE["n_users"]

    def _run(self, engine, battery_init, dropout="lose"):
        sim = Scenario(engine=engine, dynamics=MarkovChurnDynamics(
            battery_init=battery_init, dropout=dropout, **MIXED),
            **BASE).build()
        return sim, sim.run()

    @pytest.mark.parametrize("engine", ["loop", "vectorized", "jax"])
    def test_scalar_class_vector_and_per_user_array_agree(self, engine):
        """One level given three ways is one run."""
        runs = [_outcome(*self._run(engine, b)) for b in
                (0.5, [0.5] * 4, np.full(self.N, 0.5))]
        assert runs[0] == runs[1] == runs[2]
        assert runs[0][3][0] > 0 and runs[0][3][2] > 0

    @pytest.mark.parametrize("dropout", ["lose", "resume"])
    def test_per_user_levels_give_identical_runs_on_every_engine(
            self, dropout):
        levels = np.linspace(0.15, 0.95, self.N)
        loop, vec, jx = (_outcome(*self._run(e, levels, dropout))
                         for e in ("loop", "vectorized", "jax"))
        assert loop == vec == jx
        drops, departures, recoveries, lost = loop[3]
        assert drops > 0 and recoveries > 0
        assert lost == (drops if dropout == "lose" else 0)

    def test_class_vector_gathers_by_device(self):
        sim = Scenario(dynamics=MarkovChurnDynamics(
            battery_init=[0.1, 0.2, 0.3, 0.4], battery_capacity=2.0),
            **BASE).build()
        want = 2.0 * np.array([0.1, 0.2, 0.3, 0.4])[
            sim.fleet_spec.device_ids]
        np.testing.assert_array_equal(sim.state.dyn["battery"], want)

    # the parent's push keys, drops and final battery levels, from runs of
    # the scalar form before battery_init took arrays (sha256 of the int64
    # columns t, user, lag, corun, drops, then the float64 levels)
    PINNED = {
        "net_degraded": ("1ddfdb8bd15c8c62d593ff3f25d5b799b5bcfadf9808f9d"
                         "37d30ad3606ac5a6c", 197, 3),
        "mixed": ("758583561d006f84f4a8e3d1fec37bc883449a3a3bdd99fa7f4045"
                  "a76af16c3b", 843, 6),
    }

    @pytest.mark.parametrize("engine", ["loop", "vectorized", "jax"])
    @pytest.mark.parametrize("scenario", ["net_degraded", "mixed"])
    def test_scalar_form_is_bit_identical_to_before(self, engine, scenario):
        kw = SCENARIOS[scenario] if scenario != "mixed" \
            else dict(MIXED, battery_init=0.5)
        sim = Scenario(engine=engine, dynamics=MarkovChurnDynamics(
            dropout="lose", resume_penalty_s=20.0, **kw), **BASE).build()
        r = sim.run()
        h = hashlib.sha256()
        t, u, lag, _, corun, _ = r.push_log.arrays()
        for a in (t, u, lag, corun, sim.state.dyn["drops"]):
            h.update(np.ascontiguousarray(a, np.int64).tobytes())
        h.update(np.asarray(sim.state.dyn["battery"], np.float64).tobytes())
        assert (h.hexdigest(), r.drops, len(r.push_log)) == \
            self.PINNED[scenario]

    @pytest.mark.parametrize("bad, match", [
        (1.5, "battery_init"), (-0.1, "battery_init"),
        ([0.2, 1.2], "battery_init"), ([], "battery_init"),
        ([[0.5, 0.5]], "battery_init"), ([0.5, float("nan")], "battery_init"),
    ])
    def test_bad_values_raise_at_construction(self, bad, match):
        with pytest.raises(ValueError, match=match):
            MarkovChurnDynamics(battery_init=bad)

    @pytest.mark.parametrize("bad, match", [
        ([0.5, 0.5], "per-user array"),       # neither 4 classes nor n
        (np.full(BASE["n_users"] + 1, 0.5), "per-user array"),
    ])
    def test_bad_shapes_raise_at_build(self, bad, match):
        with pytest.raises(ValueError, match=match):
            Scenario(dynamics=MarkovChurnDynamics(battery_init=bad),
                     **BASE).build()

    def test_n_equal_to_the_class_count_is_ambiguous(self):
        with pytest.raises(ValueError, match="ambiguous"):
            Scenario(dynamics=MarkovChurnDynamics(
                battery_init=[0.2, 0.4, 0.6, 0.8]),
                **dict(BASE, n_users=4)).build()


class TestChurnCounters:
    @pytest.mark.parametrize("scenario", list(SCENARIOS))
    def test_counters_agree_on_every_engine(self, scenario):
        got = []
        for engine in ("loop", "vectorized", "jax"):
            r = _run(engine, _dyn(scenario))
            got.append((r.drops, r.departures, r.recoveries, r.lost))
        assert got[0] == got[1] == got[2]

    def test_counters_count_what_they_name(self):
        """Lost trainings are the drops under "lose" and none under
        "resume"; a fleet that only waits loses nothing, and each of its
        down-edges is a departure; no churn counts nothing."""
        r = _run("vectorized", _dyn("mass_dropout"))
        assert r.lost == r.drops > 0 and r.recoveries > 0
        r = _run("vectorized", _dyn("mass_dropout", "resume"))
        assert r.lost == 0 and r.drops > 0
        r = _run("vectorized", _dyn("flapping"), app_arrival_p=0.0,
                 policy="online", V=1e9)
        assert r.updates == 0 and r.drops == r.lost == 0
        assert r.departures > 0 and r.recoveries > 0
        r = _run("vectorized", "none")
        assert (r.drops, r.departures, r.recoveries, r.lost) == (0, 0, 0, 0)

    def test_counters_are_0d_int32_leaves_of_the_state(self):
        sim = Scenario(dynamics=_dyn("churn"), **BASE).build()
        for name in ("departures", "recoveries"):
            leaf = sim.state.dyn[name]
            assert np.shape(leaf) == () and leaf.dtype == np.int32


# ---------------------------------------------------------------------------
# the battery gate: resume level (hysteresis) and whole-quantum levels
# ---------------------------------------------------------------------------
GATE = dict(MIXED, battery_min=0.15, battery_resume=0.2,
            battery_quantum=1e-4)


class TestBatteryGate:
    N = BASE["n_users"]

    def _run(self, engine, **kw):
        sim = Scenario(engine=engine, dynamics=MarkovChurnDynamics(
            battery_init=np.linspace(0.12, 0.3, self.N), **kw),
            **BASE).build()
        return sim, sim.run()

    @pytest.mark.parametrize("engine", ["loop", "vectorized", "jax"])
    def test_resume_at_battery_min_is_the_plain_gate(self, engine):
        plain = _outcome(*self._run(engine, **MIXED))
        same = _outcome(*self._run(engine, battery_resume=0.2, **MIXED))
        assert plain == same

    @pytest.mark.parametrize("dropout", ["lose", "resume"])
    def test_every_engine_runs_the_gate_alike(self, dropout):
        loop, vec, jx = (_outcome(*self._run(e, dropout=dropout, **GATE))
                         for e in ("loop", "vectorized", "jax"))
        assert loop == vec == jx
        levels = np.asarray(loop[2])
        assert np.array_equal(levels, np.rint(levels))   # whole quanta
        assert loop[3][0] > 0 and loop[3][2] > 0

    def test_a_held_user_waits_for_the_resume_level(self):
        """One user on a charger below the resume level stays down until
        its level passes it, then comes up once."""
        d = MarkovChurnDynamics(p_off=0.0, p_on=1.0, battery_init=0.14,
                                battery_min=0.15, battery_resume=0.2,
                                battery_quantum=1e-4, charge_rate=1e-3)
        dyn = d.init_state(1)
        assert dyn["battery"][0] == 1400.0
        key = np.array([0, 1], np.uint32)
        mode, corun = np.full(1, 2, np.int8), np.zeros(1, bool)
        ups = []
        for _ in range(70):
            dyn, key, eff = d.host_step(dyn, key, mode, corun, 1.0)
            ups.append(bool(eff.up[0]))
        # 10 quanta a slot: 1410, ..., 2000 held, 2010 (slot 61) up
        assert ups == [False] * 60 + [True] * 10
        assert int(dyn["recoveries"]) == 1

    def test_the_plain_gate_without_resume_flaps(self):
        """Without a resume level the same user comes up as soon as it
        passes battery_min."""
        d = MarkovChurnDynamics(p_off=0.0, p_on=1.0, battery_init=0.14,
                                battery_min=0.15, charge_rate=1e-3)
        dyn, key = d.init_state(1), np.array([0, 1], np.uint32)
        mode, corun = np.full(1, 2, np.int8), np.zeros(1, bool)
        ups = []
        for _ in range(20):
            dyn, key, eff = d.host_step(dyn, key, mode, corun, 1.0)
            ups.append(bool(eff.up[0]))
        assert ups.index(True) < 15

    def test_float32_levels_are_the_float64_levels(self):
        import jax

        f64 = _outcome(*self._run("jax", **GATE))
        jax.config.update("jax_enable_x64", False)
        try:
            sim, r = self._run("jax", **GATE)
            assert sim.state.dyn["battery"].dtype == np.float32
            f32 = _outcome(sim, r)
        finally:
            jax.config.update("jax_enable_x64", True)
        assert f32[2] == f64[2]

    @pytest.mark.parametrize("kw, match", [
        (dict(battery_min=0.2, battery_resume=0.1), "battery_resume"),
        (dict(battery_resume=1.0), "battery_resume"),
        (dict(battery_quantum=0.0), "battery_quantum"),
        (dict(battery_quantum=3e-4), "whole number"),
        (dict(battery_quantum=1e-4, battery_min=0.15005), "battery_min"),
        (dict(battery_quantum=1e-9), "2\\*\\*24"),
    ])
    def test_bad_gate_knobs_raise_at_construction(self, kw, match):
        with pytest.raises(ValueError, match=match):
            MarkovChurnDynamics(**kw)

    def test_a_step_of_part_of_a_quantum_raises_at_build(self):
        with pytest.raises(ValueError, match="whole number"):
            Scenario(dynamics=MarkovChurnDynamics(battery_quantum=1e-4),
                     **dict(BASE, t_d=0.3)).build()
