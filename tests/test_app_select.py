"""The jax slot step reads each user's per-user table entry for its
current app by a select over the table's app columns, not a gather.

Two pins: the lowered online chunk has no ``gather`` op at all, with the
push log on and off (the online hook computes its Eq. 4 gap elementwise
too), and in the vmapped sweep build none that reads a value per user;
and on a fleet whose app columns all hold distinct values the jax engine
reproduces the NumPy engine in float64: the per-user state the
selected entries feed (app and training time left, energy) and the
schedule bit for bit, the cross-user sums and Eq. 4's gap to rounding."""
from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Scenario
from repro.core import vector_engine as ve
from repro.core.energy import APPS, AppProfile, DeviceProfile
from repro.core.fleet import CustomCatalogFleet


def _chunk_and_operands(sc, batch=0):
    """The jitted chunk of ``sc``'s run and its operands (stacked
    ``batch`` times along a leading config axis when ``batch`` > 0)."""
    sim = sc.build()
    rs = ve._ops_to_device(ve._jax_run_setup(sim, jax, jnp), jax, jnp)
    fn = ve._jax_chunk_fn(rs.n, rs.chunk, rs.T, sim.policy, rs.overhead,
                          rs.collect, rs.cap, rs.statics, sim.agg,
                          sim.dynamics, batch=batch)
    state = rs.state
    if rs.collect:
        state = state.replace(events=ve.PushBuffer(
            jnp.zeros((rs.cap, 6), rs.f), jnp.asarray(0, rs.i)))
    ops = [rs.tables, rs.app_sched, rs.app_choice, rs.scalars, rs.pol_ops,
           rs.agg_ops, rs.dyn_ops, state]
    if batch:
        ops = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct((batch,) + x.shape, x.dtype), ops)
    *head, state = ops
    return fn, (*head, jnp.asarray(0, rs.i), state)


def gather_scopes(text):
    """The location names of every ``stablehlo.gather`` in a lowered
    module's debug text, with ``#loc`` aliases followed to their
    definitions (a name location carries the op's full scope path)."""
    defs = dict(re.findall(r"^(#loc\d+) = (.*)$", text, re.M))

    def names(ref, seen=()):
        if ref in seen or ref not in defs:
            return []
        body = defs[ref]
        return re.findall(r'"([^"]*)"', body) + [
            n for r in re.findall(r"#loc\d+", body)
            for n in names(r, seen + (ref,))]

    out = []
    for line in text.splitlines():
        if re.search(r"stablehlo\.gather\b", line):
            ref = re.search(r"loc\((#loc\d+)\)\s*$", line)
            out.append(" ".join(names(ref.group(1))) if ref else line)
    return out


BASE = dict(n_users=48, horizon_s=600, seed=5, app_arrival_p=0.02,
            engine="jax", jax_chunk=320, policy="online")


@pytest.mark.parametrize("log, batch", [(True, 0), (False, 0), (True, 3)],
                         ids=["log", "nolog", "sweep"])
def test_the_online_chunk_has_no_gather(log, batch):
    fn, ops = _chunk_and_operands(
        Scenario(**BASE, collect_push_log=log), batch=batch)
    scopes = gather_scopes(fn.lower(*ops).as_text(debug_info=True))
    if not batch:
        assert scopes == [], scopes
    else:
        # vmap turns a dynamic slice at a per-config index into a gather:
        # the chunk's slices outside the slot step, the push log's block
        # windows and the slow replay's scalar lag lookup. None reads a
        # value per user.
        assert all("slot." not in s or "slot.push_log" in s
                   or "slow.<locals>.body" in s for s in scopes), scopes


def test_the_parser_sees_a_gather():
    """An array indexed by an array lowers to a gather, and the parser
    names it with its scope."""
    def lookup(table, idx):
        with jax.named_scope("lookup"):
            return table[idx]

    text = jax.jit(lookup).lower(
        jnp.arange(8.0), jnp.array([3, 1, 4])).as_text(debug_info=True)
    scopes = gather_scopes(text)
    assert len(scopes) == 1 and "lookup" in scopes[0], scopes


def distinct_catalog(n_devices=3):
    """Devices whose eight app columns hold pairwise distinct values in
    every table (P^a, P^{a'}, co-run time), with positive co-run savings
    and apps short enough to end and re-arrive inside the horizon."""
    devices = []
    for d in range(n_devices):
        p_train = 1.1 + 0.17 * d
        apps = {}
        for k, name in enumerate(APPS):
            p_app = 0.6 + 0.13 * k + 0.011 * d
            apps[name] = AppProfile(
                p_app=p_app, p_corun=p_train + p_app - (0.2 + 0.037 * k),
                t_corun=31.0 + 6.0 * k + 2.0 * d)
        devices.append(DeviceProfile(
            name=f"distinct{d}", p_train=p_train, t_train=57.0 + 5.0 * d,
            p_idle=0.21 + 0.01 * d, p_sched=0.25 + 0.01 * d, apps=apps))
    return devices


@pytest.fixture
def _x64():
    prev = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", True)
    yield
    jax.config.update("jax_enable_x64", prev)


def run(engine, policy, log):
    sc = Scenario(n_users=40, horizon_s=900, seed=17, app_arrival_p=0.03,
                  engine=engine, jax_chunk=256, policy=policy, L_b=5.0,
                  collect_push_log=log,
                  fleet=CustomCatalogFleet(distinct_catalog(), "random"))
    sim = sc.build()
    return sim, sim.run()


@pytest.mark.parametrize("log", [True, False], ids=["log", "nolog"])
@pytest.mark.parametrize("policy", ["online", "immediate"])
def test_jax_engine_matches_numpy_on_distinct_app_columns(_x64, policy, log):
    ref_sim, ref = run("vectorized", policy, log)
    sim, res = run("jax", policy, log)

    # the traffic reaches every app column and the idle id
    tab = ref_sim.fleet_spec.tables
    for t in (tab.p_app, tab.p_corun, tab.t_corun):
        assert len(np.unique(t)) == t.size
    arrived = np.unique(ref_sim.app_choice[ref_sim.app_sched])
    assert set(arrived.tolist()) == set(range(len(APPS)))
    assert (sim.state.app == -1).any() and (sim.state.app >= 0).any()
    assert res.updates > 0

    assert np.array_equal(sim.state.energy, ref_sim.state.energy)
    assert np.array_equal(sim.state.updates, ref_sim.state.updates)
    assert np.array_equal(sim.state.app_rem, ref_sim.state.app_rem)
    assert np.array_equal(sim.state.train_rem, ref_sim.state.train_rem)
    assert res.updates == ref.updates
    assert res.corun_fraction == ref.corun_fraction
    for name in ("trace_t", "trace_Q"):
        assert np.array_equal(getattr(res, name), getattr(ref, name)), name
    # sums over users (Eq. 16's gap sum in H, the fleet's energy) are
    # reduced in another order by XLA than by NumPy: equal to rounding
    for name in ("trace_H", "trace_energy", "energy_j"):
        np.testing.assert_allclose(getattr(res, name), getattr(ref, name),
                                   rtol=1e-13, err_msg=name)
    if log:
        assert len(res.push_log) == len(ref.push_log) > 0
        for a, b in zip(res.push_log, ref.push_log):
            # Eq. 4's gap is a power of the momentum-norm model: XLA's
            # and NumPy's pow round the last bit differently
            gap_a, gap_b = a.pop("gap"), b.pop("gap")
            assert a == b
            assert gap_a == pytest.approx(gap_b, rel=1e-13)
    else:
        assert len(res.push_log) == len(ref.push_log) == 0
