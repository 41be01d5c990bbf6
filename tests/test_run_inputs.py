"""What a run rebuilds, and what it takes as built.

A simulator's arrivals are fixed at construction: ``app_choice`` is stored
int32 there (the dtype the jax scan reads), after the range check, so no
run converts them: the scan's host operands are views of them (copies
only where an uneven horizon pads them to whole chunks). Pins: repeated
runs are bit-for-bit a freshly built twin's; an uneven horizon, alone and
in a batched sweep, matches an even one; reassigned arrivals are read by
the next run; a choice too wide for int32 fails the range check instead
of wrapping into range; a sharded run matches the unsharded one. ``sim.users`` is built on first access: the batched engines never
build it, the loop engine does, and a loop run after a reset starts from
fresh objects."""
from __future__ import annotations

import hashlib
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Scenario
from repro.core import vector_engine as ve
from repro.core.arrivals import ArrivalProcess, TraceArrivals
from repro.core.simulator import UserState

BASE = dict(n_users=48, horizon_s=400, seed=5, app_arrival_p=0.02,
            engine="jax", jax_chunk=128, policy="online", V=500.0,
            L_b=50.0)
LOG = pytest.mark.parametrize("log", [True, False], ids=["log", "nolog"])


def build(log, **kw):
    return Scenario(**{**BASE, "collect_push_log": log, **kw}).build()


def answer(sim, result):
    """Everything a run returns that must not move, as exact arrays."""
    cols = [np.asarray(c, np.float64) for c in result.push_log.arrays()]
    digest = hashlib.sha256(
        np.stack(cols).tobytes() if cols else b"").hexdigest()
    return {"energy": np.asarray(sim.state.energy),
            "updates": np.asarray(sim.state.updates),
            "trace_Q": result.trace_Q, "trace_H": result.trace_H,
            "trace_energy": result.trace_energy,
            "energy_j": np.asarray(result.energy_j),
            "push_log": digest}


def assert_same(a, b):
    assert a.keys() == b.keys()
    for k in a:
        if k == "push_log":
            assert a[k] == b[k]
        else:
            np.testing.assert_array_equal(a[k], b[k], err_msg=k)


class TestRunInputs:
    @LOG
    def test_repeated_runs_match_a_fresh_twin(self, log):
        sim = build(log)
        first = answer(sim, sim.run())
        second = answer(sim, sim.run())
        twin = build(log)
        assert_same(first, answer(twin, twin.run()))
        assert_same(first, second)
        if log:
            assert len(sim.run().push_log) > 0

    @LOG
    def test_an_uneven_horizon_matches_an_even_chunking(self, log):
        """400 slots in chunks of 128 pad 112 slots; in chunks of 100
        none. Both give one answer, run by run and in a
        two-config sweep."""
        padded, even = build(log), build(log, jax_chunk=100)
        assert_same(answer(padded, padded.run()), answer(even, even.run()))
        swept = {}
        for chunk in (128, 100):
            sims = [build(log, jax_chunk=chunk, seed=s) for s in (5, 6)]
            results = ve.run_jax_sweep(sims)
            swept[chunk] = [answer(s, r) for s, r in zip(sims, results)]
        for a, b in zip(swept[128], swept[100]):
            assert_same(a, b)

    def test_the_run_converts_no_arrivals(self):
        """The scan's host operands are views of the simulator's arrays
        where the horizon is whole chunks; an uneven one pads a copy."""
        sim = build(True, jax_chunk=100)
        assert sim.app_choice.dtype == np.int32
        rs = ve._jax_run_setup(sim, jax, jnp)
        assert rs.app_sched.base is sim.app_sched
        assert rs.app_choice.base is sim.app_choice
        rs = ve._jax_run_setup(build(True), jax, jnp)
        assert rs.app_sched.shape == rs.app_choice.shape == (512, 48)
        assert rs.app_choice.dtype == np.int32

    @LOG
    def test_reassigned_arrivals_are_read_by_the_next_run(self, log):
        sim = build(log)
        before = answer(sim, sim.run())
        sched = np.roll(sim.app_sched, 37, axis=0)
        choice = np.roll(sim.app_choice, 37, axis=0).astype(np.int64)
        sim.app_sched, sim.app_choice = sched, choice
        after = answer(sim, sim.run())
        twin = build(log, arrivals=TraceArrivals(sched, choice))
        assert_same(after, answer(twin, twin.run()))
        # the new arrivals do move the answer, so stale ones would show
        assert not np.array_equal(after["energy"], before["energy"])

    @pytest.mark.parametrize("bad", [2**32 + 1, -1], ids=["wide", "neg"])
    def test_a_choice_out_of_range_fails_before_narrowing(self, bad):
        """2**32 + 1 would read as app 1 once narrowed to int32."""
        sim = build(True)
        choice = sim.app_choice.astype(np.int64)
        choice[3, 4] = bad

        class Planted(ArrivalProcess):
            name = "planted"

            def sample(self, rng, T, n_users, n_apps, t_d=1.0):
                return sim.app_sched, choice

        with pytest.raises(ValueError, match="outside"):
            build(True, arrivals=Planted())

    def test_a_sharded_run_matches_the_unsharded_one(self):
        """Two forced host devices in a fresh process: the int32 arrivals
        padded to 48 user columns and to whole chunks, put sharded."""
        code = r"""
import json, numpy as np
from repro.core import Scenario
out = []
for n_devices in (2, 0):
    sim = Scenario(n_users=47, horizon_s=300, seed=5, app_arrival_p=0.02,
                   engine="jax", jax_chunk=128, policy="online", V=500.0,
                   L_b=50.0, collect_push_log=True,
                   n_devices=n_devices).build()
    r = sim.run()
    cols = np.stack([np.asarray(c, np.float64) for c in r.push_log.arrays()])
    out.append({"devices": len(r.placement["app_sched"][0]),
                "log": cols.tolist(), "Q": r.trace_Q.tolist(),
                "H": r.trace_H.tolist(),
                "energy": np.asarray(sim.state.energy).tolist(),
                "updates": np.asarray(sim.state.updates).tolist()})
print(json.dumps(out))
"""
        import repro.core
        src = os.path.dirname(os.path.dirname(os.path.dirname(
            repro.core.__file__)))
        env = {**os.environ, "PYTHONPATH": src, "JAX_PLATFORMS": "cpu",
               "XLA_FLAGS": "--xla_force_host_platform_device_count=2"}
        proc = subprocess.run([sys.executable, "-c", code], env=env,
                              capture_output=True, text=True, timeout=300)
        assert proc.returncode == 0, proc.stderr[-3000:]
        sharded, plain = json.loads(proc.stdout.strip().splitlines()[-1])
        assert (sharded["devices"], plain["devices"]) == (2, 1)
        assert sharded["log"]
        for k in ("log", "Q", "H", "energy", "updates"):
            assert sharded[k] == plain[k], k


class TestLazyUsers:
    @pytest.mark.parametrize("engine", ["jax", "vectorized"])
    def test_the_batched_engines_leave_it_unbuilt(self, engine):
        sim = build(True, engine=engine)
        assert sim.resolve_engine() == engine
        sim.run()
        sim.run()
        assert sim._users is None

    def test_the_loop_engine_builds_one_user_per_fleet_device(self):
        sim = build(True, engine="loop")
        assert sim._users is None
        result = sim.run()
        users = sim.users
        assert len(users) == BASE["n_users"]
        assert all(isinstance(u, UserState) and u.device is d
                   for u, d in zip(users, sim.fleet_spec.devices))
        assert sum(u.updates for u in users) == result.updates

    def test_attribute_access_builds_it_once(self):
        sim = build(True)
        users = sim.users
        assert sim._users is users and sim.users is users
        assert [u.device for u in users] == list(sim.fleet_spec.devices)
        assert all(u.mode == "cooldown" and u.updates == 0 for u in users)

    def test_a_loop_run_after_a_reset_starts_from_fresh_users(self):
        sim = build(True, engine="loop")
        r1 = sim.run()
        first = sim.users
        r2 = sim.run()
        second = sim.users
        assert second is not first
        assert not {id(u) for u in first} & {id(u) for u in second}
        assert r2.updates == r1.updates > 0
        assert r2.energy_j == r1.energy_j
        assert sum(u.updates for u in second) == r2.updates
