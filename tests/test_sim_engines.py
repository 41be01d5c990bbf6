"""Vectorized SoA engine vs. the reference loop engine (the oracle).

The loop engine (``FederatedSim._run_loop``) is the ground truth for the
Sec. VII.B evaluation; these tests pin the batched engines to it: identical
decision sequences / update counts / push logs, energies within float-sum
reordering, plus scalar-vs-batch property checks for the primitives the
vectorized engine leans on (Lemma 1 bounds, Eq. 4 gaps, the batched
Lyapunov argmin)."""
import numpy as np
import pytest

from repro.core.lyapunov import OnlineScheduler, UserSlotState
from repro.core.offline import lemma1_lag_bounds, lemma1_lag_bounds_loop
from repro.core.simulator import POLICIES, FederatedSim, SimConfig
from repro.core.staleness import gradient_gap, momentum_scale


def run(policy, engine, **kw):
    kw.setdefault("horizon_s", 2000)
    kw.setdefault("n_users", 12)
    kw.setdefault("seed", 2)
    return FederatedSim(SimConfig(policy=policy, engine=engine, **kw)).run()


def assert_equivalent(a, b, energy_rtol=1e-9, push_log=True):
    assert a.updates == b.updates
    assert b.energy_j == pytest.approx(a.energy_j, rel=energy_rtol)
    assert b.mean_Q == pytest.approx(a.mean_Q, rel=1e-9, abs=1e-12)
    assert b.mean_H == pytest.approx(a.mean_H, rel=1e-6, abs=1e-9)
    assert b.corun_fraction == pytest.approx(a.corun_fraction)
    np.testing.assert_array_equal(a.trace_t, b.trace_t)
    np.testing.assert_allclose(b.trace_energy, a.trace_energy,
                               rtol=energy_rtol)
    np.testing.assert_allclose(b.trace_Q, a.trace_Q, rtol=1e-9, atol=1e-12)
    np.testing.assert_allclose(b.trace_H, a.trace_H, rtol=1e-6, atol=1e-9)
    if push_log:
        assert [(e["t"], e["user"], e["lag"], e["corun"])
                for e in a.push_log] == \
               [(e["t"], e["user"], e["lag"], e["corun"])
                for e in b.push_log]
        np.testing.assert_allclose([e["gap"] for e in b.push_log],
                                   [e["gap"] for e in a.push_log],
                                   rtol=1e-9, atol=1e-15)


class TestLoopVsVectorized:
    @pytest.mark.parametrize("policy", POLICIES)
    def test_seeded_parity(self, policy):
        a = run(policy, "loop")
        b = run(policy, "vectorized")
        assert_equivalent(a, b)

    @pytest.mark.parametrize("policy", POLICIES)
    def test_parity_other_seed_and_arrivals(self, policy):
        kw = dict(seed=7, app_arrival_p=0.01, horizon_s=1500, n_users=16)
        assert_equivalent(run(policy, "loop", **kw),
                          run(policy, "vectorized", **kw))

    def test_parity_with_staleness_pressure(self):
        """Tight L_b keeps H > 0, exercising the sequential in-slot lag
        coupling path of decide_batch."""
        kw = dict(L_b=2.0, V=2000.0, app_arrival_p=0.01, horizon_s=3000,
                  n_users=16)
        a = run("online", "loop", **kw)
        b = run("online", "vectorized", **kw)
        assert a.mean_H > 0          # the test must actually hit that path
        assert_equivalent(a, b)

    def test_parity_with_scheduler_overhead(self):
        kw = dict(include_scheduler_overhead=True)
        assert_equivalent(run("online", "loop", **kw),
                          run("online", "vectorized", **kw))

    @pytest.mark.parametrize("policy", POLICIES)
    def test_parity_with_subsecond_slots(self, policy):
        """t_d < 1 means more slots than seconds; the arrival schedule
        must cover all T slots on every engine."""
        kw = dict(t_d=0.5, horizon_s=600, n_users=8, app_arrival_p=0.01)
        assert_equivalent(run(policy, "loop", **kw),
                          run(policy, "vectorized", **kw))

    def test_parity_at_scale(self):
        """Acceptance shape: n_users=400, online, trace mode."""
        kw = dict(n_users=400, horizon_s=400, seed=0)
        assert_equivalent(run("online", "loop", **kw),
                          run("online", "vectorized", **kw))

    def test_auto_selects_vectorized_for_trace(self):
        sim = FederatedSim(SimConfig(policy="online"))
        assert sim.resolve_engine() == "vectorized"
        sim = FederatedSim(SimConfig(policy="online", ml_mode="real"))
        assert sim.resolve_engine() == "loop"

    def test_vectorized_rejects_real_ml(self):
        cfg = SimConfig(policy="online", ml_mode="real", engine="vectorized")
        with pytest.raises(ValueError):
            FederatedSim(cfg).run()

    def test_push_log_opt_out(self):
        r = run("online", "vectorized", collect_push_log=False)
        assert r.push_log == [] and r.updates > 0


class TestJaxBackend:
    @pytest.fixture(autouse=True)
    def _x64(self):
        """f64 matches the loop engine's float semantics; f32 is a
        documented approximation."""
        import jax
        prev = jax.config.jax_enable_x64
        jax.config.update("jax_enable_x64", True)
        yield
        jax.config.update("jax_enable_x64", prev)

    @pytest.mark.parametrize("policy",
                             ("sync", "immediate", "online", "offline",
                              "greedy"))
    def test_seeded_parity(self, policy):
        a = run(policy, "loop", collect_push_log=False)
        b = run(policy, "jax", collect_push_log=False)
        # energies via jnp pairwise sums
        assert_equivalent(a, b, energy_rtol=1e-9, push_log=False)
        assert b.push_log == []

    @pytest.mark.parametrize("policy",
                             ("sync", "immediate", "online", "offline",
                              "greedy"))
    def test_push_log_streams_out_of_scan(self, policy):
        """collect_push_log=True on engine='jax' (regression: it used to
        warn and return an empty log): the streamed event buffer must
        reproduce the loop oracle's push log exactly — every engine, same
        events."""
        import warnings
        a = run(policy, "loop")
        with warnings.catch_warnings():
            warnings.simplefilter("error")        # the old path warned
            b = run(policy, "jax")
        assert len(b.push_log) == len(a.push_log) > 0
        assert_equivalent(a, b)

    def test_push_log_identical_across_all_three_engines(self):
        """The satellite regression pin: one seeded online run, three
        engines, byte-identical push events."""
        a, b, c = (run("online", e, app_arrival_p=0.01, horizon_s=1500,
                       n_users=16, seed=7, V=2000.0, L_b=2.0) for e in
                   ("loop", "vectorized", "jax"))
        key = [(e["t"], e["user"], e["lag"], e["corun"]) for e in a.push_log]
        assert len(key) > 0
        assert [(e["t"], e["user"], e["lag"], e["corun"])
                for e in b.push_log] == key
        assert [(e["t"], e["user"], e["lag"], e["corun"])
                for e in c.push_log] == key
        np.testing.assert_allclose([e["gap"] for e in c.push_log],
                                   [e["gap"] for e in a.push_log],
                                   rtol=1e-9, atol=1e-15)

    def test_push_log_chunk_and_overflow_invariance(self):
        """The streamed log must not depend on scan chunking or on the
        initial event-buffer capacity (overflow doubles + retries)."""
        base = run("immediate", "loop")
        tiny = run("immediate", "jax", jax_chunk=64, push_log_capacity=2)
        assert_equivalent(base, tiny)

    def test_parity_with_staleness_pressure(self):
        kw = dict(L_b=2.0, V=2000.0, app_arrival_p=0.01, horizon_s=2000,
                  n_users=16)
        a = run("online", "loop", **kw)
        b = run("online", "jax", **kw)
        assert a.mean_H > 0
        assert_equivalent(a, b)

    def test_staleness_pressure_order_free_branch(self):
        """With H > 0 the online hook decides order-free users without the
        per-user replay and sums Eq. 16's gaps as a tree, not the replay's
        left fold; decisions stay the oracle's, H within rounding."""
        # a few slots hold users whose decision depends on their in-slot
        # lag, so all three branches of the hook run
        kw = dict(L_b=10.0, V=2000.0, app_arrival_p=0.002, horizon_s=2000,
                  n_users=64)
        a = run("online", "loop", **kw)
        b = run("online", "jax", **kw)
        assert a.mean_H > 0
        assert_equivalent(a, b)

    def test_offline_runs_on_jax(self):
        """The offline knapsack plans through a host callback at window
        slots: engine='jax' resolves to jax (it used to degrade to the
        numpy engine) and matches the oracle, push log included."""
        sim = FederatedSim(SimConfig(policy="offline", engine="jax",
                                     horizon_s=2000, n_users=12, seed=2))
        assert sim.resolve_engine() == "jax"
        a = run("offline", "loop")
        b = run("offline", "jax")
        assert a.updates > 0
        assert_equivalent(a, b)

    def test_single_core_hosts_get_second_callback_device(self):
        """On one-core hosts jax's pure_callback deadlocks: its operand
        device_put waits on the CPU device whose only thread is parked in
        the custom call waiting for the callback (hangs the offline
        policy's plan_window callback from n_users~100 up). policies.py
        must pre-set --xla_force_host_platform_device_count=2 there, and
        must leave XLA_FLAGS alone on multi-core hosts."""
        import os
        import subprocess
        import sys

        import repro.core.policies as pol
        src = os.path.dirname(os.path.dirname(os.path.dirname(pol.__file__)))
        code = ("import os; os.cpu_count = lambda: {n}; "
                "os.environ.pop('XLA_FLAGS', None); "
                "import repro.core.policies; "
                "print(os.environ.get('XLA_FLAGS', ''))")

        def probe(n):
            out = subprocess.run(
                [sys.executable, "-c", code.format(n=n)],
                env={**os.environ, "PYTHONPATH": src},
                capture_output=True, text=True, timeout=120)
            assert out.returncode == 0, out.stderr
            return out.stdout

        assert "xla_force_host_platform_device_count=2" in probe(1)
        assert "xla_force_host_platform_device_count" not in probe(4)

    def test_v_norm_hook_falls_back_to_numpy(self):
        """A Python v_norm callback can't run inside lax.scan; jax must
        degrade to the numpy engine (which honors it), not silently
        ignore the hook."""
        hooks = {"v_norm": lambda: 5.0}
        kw = dict(policy="online", L_b=2.0, V=2000.0, horizon_s=800,
                  n_users=12, seed=2, app_arrival_p=0.01)
        a = FederatedSim(SimConfig(engine="loop", **kw), ml_hooks=hooks)
        b = FederatedSim(SimConfig(engine="jax", **kw), ml_hooks=hooks)
        assert b.resolve_engine() == "vectorized"
        assert_equivalent(a.run(), b.run())


class TestBatchedPrimitives:
    def test_lemma1_matches_loop_form(self, rng):
        for n in (1, 2, 7, 40, 200):
            t = rng.uniform(0, 1000, n)
            ta = t + rng.uniform(0, 300, n)
            d = rng.uniform(1, 400, n)
            np.testing.assert_array_equal(
                lemma1_lag_bounds(t, ta, d),
                lemma1_lag_bounds_loop(t, ta, d))

    def test_lemma1_blocked_matches_unblocked(self, rng):
        n = 97
        t = rng.uniform(0, 500, n)
        ta = t + rng.uniform(0, 100, n)
        d = rng.uniform(1, 300, n)
        np.testing.assert_array_equal(
            lemma1_lag_bounds(t, ta, d, block=16),
            lemma1_lag_bounds(t, ta, d))

    def test_gradient_gap_batched_matches_scalar(self, rng):
        lags = rng.integers(0, 50, 64)
        for beta in (0.0, 0.5, 0.9):
            batch = gradient_gap(1.7, lags, 0.01, beta)
            scal = [gradient_gap(1.7, int(l), 0.01, beta) for l in lags]
            np.testing.assert_array_equal(batch, scal)
        # array v_norm broadcasting
        vns = rng.uniform(0, 2, 64)
        np.testing.assert_array_equal(
            gradient_gap(vns, lags, 0.01, 0.9),
            [gradient_gap(v, int(l), 0.01, 0.9)
             for v, l in zip(vns, lags)])

    def test_momentum_scale_scalar_type(self):
        assert isinstance(momentum_scale(3, 0.01, 0.9), float)
        assert isinstance(momentum_scale(3, 0.01, 0.0), float)

    def test_momentum_scale_stays_jit_traceable(self):
        """Eq. (3)/(4) are used inside jitted train steps; the scalar path
        must not force a traced lag to a concrete numpy value."""
        import jax
        out = jax.jit(lambda l: momentum_scale(l, 0.01, 0.9))(3)
        assert float(out) == pytest.approx(momentum_scale(3, 0.01, 0.9))

    def test_catalog_tables_are_immutable(self):
        from repro.core.energy import catalog_tables
        tab = catalog_tables()
        with pytest.raises(ValueError):
            tab.p_train[0] = 999.0
        # gathers used by the engines still produce writable copies
        assert tab.p_train[np.array([0, 1])].flags.writeable

    @pytest.mark.parametrize("Q,H", [(0.0, 0.0), (50.0, 0.0),
                                     (3.0, 40.0), (200.0, 1e4)])
    def test_decide_batch_replays_sequential_decide(self, rng, Q, H):
        """decide_batch == repeated decide() with the in-flight lag estimate
        incremented after every scheduled user (the loop engine's exact
        in-slot semantics)."""
        k = 37
        s1 = OnlineScheduler(V=1000.0, L_b=10.0, eta=0.01, beta=0.9)
        s2 = OnlineScheduler(V=1000.0, L_b=10.0, eta=0.01, beta=0.9)
        s1.Q = s2.Q = Q
        s1.H = s2.H = H
        p_train, p_idle = 1.35, 0.689
        has_app = rng.random(k) < 0.4
        p_cor = rng.uniform(1.5, 3.0, k)
        p_app = rng.uniform(0.5, 2.0, k)
        idle_gap = rng.uniform(0, 2.0, k)
        p_s = np.where(has_app, p_cor, p_train)
        p_i = np.where(has_app, p_app, p_idle)
        lag_base, vn = 3, 0.8

        in_flight = lag_base
        seq = []
        gaps = []
        for i in range(k):
            st = UserSlotState(p_corun=p_cor[i], p_app=p_app[i],
                               p_train=p_train, p_idle=p_idle,
                               app_running=bool(has_app[i]),
                               lag_estimate=in_flight,
                               idle_gap=idle_gap[i])
            d = s1.decide(st, vn)
            seq.append(d.schedule)
            gaps.append(d.gap)
            in_flight += d.schedule

        b = s2.decide_batch(p_s, p_i, idle_gap, lag_base, vn)
        np.testing.assert_array_equal(b.schedule, seq)
        np.testing.assert_allclose(b.gaps, gaps, rtol=1e-12, atol=1e-15)
        assert b.n_served == sum(seq)
        assert b.gap_sum == pytest.approx(sum(gaps), rel=1e-9)

    def test_decide_batch_survives_inverted_gap_ordering(self, rng):
        """Negative eta inverts gap monotonicity; decide_batch must fall
        back to the literal sequential replay, not the threshold trick."""
        k = 25
        s1 = OnlineScheduler(V=1000.0, L_b=10.0, eta=-0.05, beta=0.9)
        s2 = OnlineScheduler(V=1000.0, L_b=10.0, eta=-0.05, beta=0.9)
        s1.Q = s2.Q = 3.0
        s1.H = s2.H = 40.0
        p_cor = rng.uniform(1.5, 3.0, k)
        p_app = rng.uniform(0.5, 2.0, k)
        idle_gap = rng.uniform(0, 2.0, k)
        in_flight = 2
        seq = []
        for i in range(k):
            st = UserSlotState(p_corun=p_cor[i], p_app=p_app[i],
                               p_train=1.35, p_idle=0.689,
                               app_running=True, lag_estimate=in_flight,
                               idle_gap=idle_gap[i])
            d = s1.decide(st, 0.8)
            seq.append(d.schedule)
            in_flight += d.schedule
        b = s2.decide_batch(p_cor, p_app, idle_gap, 2, 0.8)
        np.testing.assert_array_equal(b.schedule, seq)


class TestConfigValidation:
    def test_rejects_unknown_policy_at_construction(self):
        with pytest.raises(ValueError, match="policy"):
            SimConfig(policy="bogus")

    def test_rejects_unknown_engine(self):
        with pytest.raises(ValueError, match="engine"):
            SimConfig(engine="cuda")

    @pytest.mark.parametrize("kw", [dict(t_d=0.0), dict(t_d=-1.0),
                                    dict(horizon_s=0), dict(horizon_s=-5),
                                    dict(n_users=0), dict(beta=1.0),
                                    dict(app_arrival_p=1.5),
                                    dict(trace_every=0),
                                    dict(offline_window=0.0),
                                    dict(eta=-0.01), dict(v_norm0=-1.0),
                                    dict(ml_mode="dream")])
    def test_rejects_bad_numerics(self, kw):
        with pytest.raises(ValueError):
            SimConfig(**kw)

    def test_zero_slot_horizon_guarded(self):
        """horizon < t_d -> T == 0; means must not divide by zero."""
        for engine in ("loop", "vectorized"):
            r = FederatedSim(SimConfig(policy="online", horizon_s=1,
                                       t_d=2.0, engine=engine)).run()
            assert r.updates == 0
            assert r.mean_Q == 0.0 and r.mean_H == 0.0
            assert r.corun_fraction == 0.0
