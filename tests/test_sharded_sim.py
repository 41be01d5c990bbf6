"""Sharded user-axis simulation (SimConfig.n_devices): the chunked jax
scan partitioned over a 1-D ``("users",)`` device mesh must be an exact
twin of the single-device scan.

The contract under test (the tentpole acceptance criterion):

* push logs, queue traces (Q/H), update counts and per-user state are
  BIT-IDENTICAL to the plain jax engine across policies x aggregation
  rules x dynamics — scheduler scalars replicate and the policy hook
  computes fully replicated, so Alg. 2 decisions cannot drift across
  shards;
* scalar energy totals agree to float-sum reordering only (the per-user
  energy vector itself is exact);
* when ``n_users`` is not a multiple of the mesh size, the user axis
  pads to ``n_arr`` INERT rows — pad users never wait, never train,
  never push, never draw energy, and never touch the queues;
* sharded sims never alias the batched-sweep path or the unsharded
  executable cache (mesh signature + padded length key the memo).

Runs under however many devices the host exposes (2 forced host devices
on single-core boxes, 8 under the CI job's
``XLA_FLAGS=--xla_force_host_platform_device_count=8``).
"""
from __future__ import annotations

import numpy as np
import pytest
from hypothesis import HealthCheck, given, settings
from hypothesis import strategies as st

from repro.core import vector_engine as ve
from repro.core.dynamics import MarkovChurnDynamics, resolve_dynamics
from repro.core.engine_state import (MODE_OFF, pad_state_per_user,
                                     pad_to_devices, unpad_state_per_user)
from repro.core.simulator import FederatedSim, SimConfig, n_slots
from repro.launch.mesh import make_sim_mesh

COMMON = dict(deadline=None,
              suppress_health_check=[HealthCheck.too_slow])


def _n_devices():
    import jax
    return len(jax.devices())


def _run(n_devices, n, policy="online", dynamics="none", agg="replace",
         seed=7, horizon=240, jax_chunk=64, collect=True):
    cfg = SimConfig(n_users=n, horizon_s=horizon, policy=policy,
                    engine="jax", collect_push_log=collect,
                    n_devices=n_devices, seed=seed, dynamics=dynamics,
                    aggregation=agg, jax_chunk=jax_chunk)
    sim = FederatedSim(cfg)
    return sim, sim.run()


def _log_cols(log):
    return np.stack([np.asarray(c, np.float64) for c in log.arrays()]) \
        if len(log) else np.zeros((6, 0))


def _assert_twin(s0, r0, s1, r1):
    """Sharded run (s1, r1) must be the plain jax run's exact twin."""
    a, b = _log_cols(r0.push_log), _log_cols(r1.push_log)
    assert a.shape == b.shape
    assert np.array_equal(a, b)
    assert np.array_equal(r0.trace_Q, r1.trace_Q)
    assert np.array_equal(r0.trace_H, r1.trace_H)
    assert r0.updates == r1.updates
    assert r0.mean_Q == r1.mean_Q
    assert (r0.drops, r0.departures, r0.recoveries, r0.lost) == \
        (r1.drops, r1.departures, r1.recoveries, r1.lost)
    # per-user state: exact, field by field (energy included — the lanes
    # never cross shards, only the scalar TOTAL re-associates)
    for f in ("mode", "cooldown", "app", "train_rem", "energy", "updates",
              "pulled_at", "idle_gap"):
        assert np.array_equal(np.asarray(getattr(s0.state, f)),
                              np.asarray(getattr(s1.state, f))), f
    np.testing.assert_allclose(r0.energy_j, r1.energy_j, rtol=1e-6)
    np.testing.assert_allclose(r0.trace_energy, r1.trace_energy,
                               rtol=1e-6)


# =====================================================================
# digest parity: the acceptance matrix
# =====================================================================
class TestShardedParity:
    @pytest.mark.parametrize("policy", ["online", "eps_greedy"])
    @pytest.mark.parametrize("agg", ["replace", "fedasync_poly"])
    @pytest.mark.parametrize("dynamics", ["none", "markov"])
    @pytest.mark.parametrize("n", [23, 24])
    def test_matrix(self, policy, agg, dynamics, n):
        """{policies} x {rules} x {dynamics} at a non-divisible and a
        divisible n: push logs / traces / per-user state bit-identical."""
        s0, r0 = _run(0, n, policy, dynamics, agg)
        s1, r1 = _run(_n_devices(), n, policy, dynamics, agg)
        _assert_twin(s0, r0, s1, r1)

    def test_x64_twin(self):
        """The f64 contract holds sharded too (one spot-check cell; the
        matrix above runs the default f32)."""
        import jax
        prev = jax.config.jax_enable_x64
        jax.config.update("jax_enable_x64", True)
        try:
            s0, r0 = _run(0, 23, dynamics="markov")
            s1, r1 = _run(_n_devices(), 23, dynamics="markov")
            _assert_twin(s0, r0, s1, r1)
        finally:
            jax.config.update("jax_enable_x64", prev)

    def test_autotuned_chunk_same_history(self):
        """jax_chunk=0 (auto-tune) must only change chunking, never the
        slot histories — sharded auto-tuned vs plain default-chunk."""
        _, r0 = _run(0, 23, jax_chunk=64)
        _, r1 = _run(_n_devices(), 23, jax_chunk=0)
        assert np.array_equal(r0.trace_Q, r1.trace_Q)
        assert np.array_equal(r0.trace_H, r1.trace_H)
        assert r0.updates == r1.updates

    def test_uneven_chunk_tail(self):
        """horizon not a multiple of jax_chunk: the padded tail chunk
        skips dead slots identically under the mesh."""
        _, r0 = _run(0, 23, horizon=250, jax_chunk=64)
        _, r1 = _run(_n_devices(), 23, horizon=250, jax_chunk=64)
        assert np.array_equal(r0.trace_Q, r1.trace_Q)
        assert np.array_equal(r0.trace_H, r1.trace_H)

    def test_reports_placement(self):
        """The result names the devices the scan's carry and arrival
        operands sat on, the user axis split evenly between them."""
        d = _n_devices()
        _, r0 = _run(0, 23)
        _, r1 = _run(d, 23)
        for r, n_dev in ((r0, 1), (r1, d)):
            assert set(r.placement) == {"state.mode", "state.energy",
                                        "app_sched"}
            for ids, shard_shape in r.placement.values():
                assert len(ids) == n_dev
                assert shard_shape[-1] == -(-23 // n_dev)

    def test_single_device_mesh_degenerates(self):
        """n_devices=1 runs the plain path (no constraint ops) and still
        matches."""
        _, r0 = _run(0, 10)
        _, r1 = _run(1, 10)
        assert np.array_equal(r0.trace_Q, r1.trace_Q)
        assert r0.updates == r1.updates


# =====================================================================
# padding inertness (property tests; hypothesis or the conftest stub)
# =====================================================================
class TestPaddingInert:
    @settings(max_examples=6, **COMMON)
    @given(n=st.integers(3, 29), seed=st.integers(0, 2 ** 16),
           policy=st.sampled_from(["online", "eps_greedy"]),
           dynamics=st.sampled_from(["none", "markov"]))
    def test_pad_users_never_act(self, n, seed, policy, dynamics):
        """Whatever (n, seed, policy, dynamics): pad users must push
        nothing, draw no energy, enter no queue — equivalently, the
        sharded run IS the unsharded run after unpadding."""
        D = _n_devices()
        s0, r0 = _run(0, n, policy, dynamics, seed=seed, horizon=120)
        s1, r1 = _run(D, n, policy, dynamics, seed=seed, horizon=120)
        # unpadded state already sliced back to n by the driver
        assert np.shape(s1.state.mode)[0] == n
        users = np.asarray(r1.push_log.arrays()[1])
        assert users.size == 0 or users.max() < n
        assert np.array_equal(r0.trace_Q, r1.trace_Q)
        assert np.array_equal(r0.trace_H, r1.trace_H)
        assert np.array_equal(np.asarray(s0.state.energy),
                              np.asarray(s1.state.energy))

    @settings(max_examples=12, **COMMON)
    @given(n=st.integers(1, 10 ** 6), d=st.integers(1, 64))
    def test_pad_to_devices(self, n, d):
        n_arr = pad_to_devices(n, d)
        assert n_arr % d == 0 and n_arr >= n and n_arr - n < d

    def test_pad_state_fills(self):
        st0 = FederatedSim(SimConfig(n_users=5, horizon_s=60)).state
        padded = pad_state_per_user(st0, 8)
        assert np.shape(padded.mode)[0] == 8
        assert (np.asarray(padded.mode)[5:] == MODE_OFF).all()
        assert (np.asarray(padded.app)[5:] == -1).all()
        assert (np.asarray(padded.energy)[5:] == 0.0).all()
        back = unpad_state_per_user(padded, 5)
        for f in ("mode", "app", "energy", "cooldown"):
            assert np.array_equal(np.asarray(getattr(back, f)),
                                  np.asarray(getattr(st0, f))), f

    def test_pad_state_requires_dyn_rows(self):
        cfg = SimConfig(n_users=4, horizon_s=60, dynamics="markov")
        sim = FederatedSim(cfg)
        with pytest.raises(ValueError, match="pad_state"):
            pad_state_per_user(sim.state, 8)

    def test_markov_pad_rows_pinned_up(self):
        """The markov pad recipe: up/on forever, full battery, zero
        transition probabilities — with fill-1.0 uniform draws the chain
        can never edge, so pad users never ret/depart."""
        dyn = MarkovChurnDynamics(p_off=0.3, p_on=0.3)
        rows = dyn.pad_state(3)
        assert rows["on"].all() and rows["up"].all()
        assert (rows["battery"] == dyn.capacity).all()
        assert (rows["p_off"] == 0).all() and (rows["p_on"] == 0).all()
        assert not rows["net_bad"].any() and (rows["drops"] == 0).all()

    def test_base_dynamics_has_no_recipe(self):
        assert resolve_dynamics("none").pad_state(3) is None


# =====================================================================
# mesh construction + config validation
# =====================================================================
class TestMeshAndConfig:
    def test_make_sim_mesh_all_devices(self):
        mesh = make_sim_mesh(0)
        assert mesh.axis_names == ("users",)
        assert mesh.devices.size == _n_devices()

    def test_make_sim_mesh_clamps(self):
        assert make_sim_mesh(10 ** 6).devices.size == _n_devices()
        assert make_sim_mesh(1).devices.size == 1

    def test_make_sim_mesh_rejects_negative(self):
        with pytest.raises(ValueError):
            make_sim_mesh(-1)

    def test_offline_policy_rejected(self):
        with pytest.raises(ValueError, match="supports_shard"):
            SimConfig(n_users=8, horizon_s=60, policy="offline",
                      n_devices=2)

    def test_loop_engine_rejected(self):
        with pytest.raises(ValueError, match="n_devices"):
            SimConfig(n_users=8, horizon_s=60, engine="loop", n_devices=2)

    def test_negative_n_devices_rejected(self):
        with pytest.raises(ValueError):
            SimConfig(n_users=8, horizon_s=60, n_devices=-1)

    def test_sharded_sim_resolves_jax(self):
        sim = FederatedSim(SimConfig(n_users=8, horizon_s=60, n_devices=2))
        assert sim.resolve_engine() == "jax"

    def test_sweep_bucket_key_none_for_sharded(self):
        sim = FederatedSim(SimConfig(n_users=8, horizon_s=60, n_devices=2))
        assert ve.sweep_bucket_key(sim) is None
        sim2 = FederatedSim(SimConfig(n_users=8, horizon_s=60, jax_chunk=0))
        assert ve.sweep_bucket_key(sim2) is None


# =====================================================================
# the memory auto-tuner
# =====================================================================
class TestAutotune:
    def _sim(self, n=1000, horizon=600, collect=False):
        return FederatedSim(SimConfig(n_users=n, horizon_s=horizon,
                                      collect_push_log=collect))

    def test_chunk_bounds(self):
        from repro.core.autotune import autotune_scan_params
        tune = autotune_scan_params(self._sim(), n_devices=2)
        T = n_slots(self._sim().cfg)
        assert 1 <= tune.jax_chunk <= min(16384, T)
        # pow2, unless clamped to the horizon
        assert (tune.jax_chunk & (tune.jax_chunk - 1) == 0
                or tune.jax_chunk == T)

    def test_capacity_scales_with_budget(self):
        from repro.core.autotune import autotune_scan_params
        small = autotune_scan_params(self._sim(collect=True), n_devices=1,
                                     mem_bytes=64 << 20)
        big = autotune_scan_params(self._sim(collect=True), n_devices=1,
                                   mem_bytes=8 << 30)
        assert small.jax_chunk <= big.jax_chunk
        assert small.device_budget == 64 << 20
        for t in (small, big):
            assert t.push_capacity >= 1024
            assert t.push_capacity & (t.push_capacity - 1) == 0

    def test_estimate_monotonic(self):
        from repro.core.autotune import estimate_device_bytes
        lo = estimate_device_bytes(10 ** 5, 600, 256, 4096, n_devices=8)
        hi = estimate_device_bytes(10 ** 6, 600, 256, 4096, n_devices=8)
        assert hi > lo > 0
        # more devices -> smaller per-device footprint
        one = estimate_device_bytes(10 ** 6, 600, 256, 0, n_devices=1)
        eight = estimate_device_bytes(10 ** 6, 600, 256, 0, n_devices=8)
        assert eight < one

    def test_budget_positive(self):
        from repro.core.autotune import device_memory_budget
        assert device_memory_budget(1) > 0
        assert device_memory_budget(8) > 0


# =====================================================================
# executable cache: sharded and unsharded never alias
# =====================================================================
class TestShardedCache:
    def test_mesh_key_distinguishes(self):
        assert ve._mesh_key(None) is None
        k1 = ve._mesh_key(make_sim_mesh(1))
        kd = ve._mesh_key(make_sim_mesh(0))
        assert k1[0] == ("users",)
        if _n_devices() > 1:
            assert k1 != kd

    def test_no_alias_with_unsharded(self):
        from repro.core.policies import resolve_policy
        pol = resolve_policy("online")
        s0 = ve.jax_cache_stats()
        f_plain = ve._jax_chunk_fn(8, 16, 32, pol, False, False, 0)
        f_mesh = ve._jax_chunk_fn(8, 16, 32, pol, False, False, 0,
                                  mesh=make_sim_mesh(1), n_arr=8)
        assert f_plain is not f_mesh
        assert ve._jax_chunk_fn(8, 16, 32, pol, False, False, 0) is f_plain
        s1 = ve.jax_cache_stats()
        assert s1["misses"] - s0["misses"] == 2
        assert s1["hits"] - s0["hits"] >= 1

    def test_sharded_batch_rejected(self):
        from repro.core.policies import resolve_policy
        with pytest.raises(ValueError, match="never batch"):
            ve._build_jax_chunk_fn(8, 16, 32, resolve_policy("online"),
                                   False, False, 0, batch=4,
                                   mesh=make_sim_mesh(1), n_arr=8)
