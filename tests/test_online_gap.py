"""The jax online hook computes each scheduled user's Eq. 4 gap
elementwise, at lag ``in_flight + before`` (``before`` = the number of
users scheduled ahead of it in the slot), instead of gathering it from
the ``(n + 1,)`` vector of gaps at every reachable lag.

Three pins: under ``jax.jit`` the elementwise gap equals the gathered one
bit for bit (float32 and x64, random masks and lags); inside the engine's
scan the hook's ``(start, gap_sum, idle_gap)`` equals the gather
formula's in each branch of its switch (``fast``, ``order_free`` and the
sequential ``slow`` replay); and the jax engine's online outputs at 2,000
users x 600 slots match digests recorded before the change."""
from __future__ import annotations

import copy
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import MarkovChurnDynamics, Scenario
from repro.core.policies import (OnlinePolicy, _jax_gradient_gap,
                                 _jax_trace_v_norm)

BETAS = [0.0, 0.5, 0.9, 0.999]
DTYPES = pytest.mark.parametrize("x64", [False, True], ids=["f32", "x64"])


@pytest.fixture
def precision(x64):
    prev = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", x64)
    yield
    jax.config.update("jax_enable_x64", prev)


def bits(x):
    u = jnp.uint64 if x.dtype.itemsize == 8 else jnp.uint32
    return jax.lax.bitcast_convert_type(x, u)


@jax.jit
def gathered(sched, in_flight, vn, eta, beta):
    before = jnp.cumsum(sched) - sched
    lag = in_flight + jnp.arange(sched.shape[0] + 1)
    return _jax_gradient_gap(vn, lag, eta, beta)[before]


@jax.jit
def elementwise(sched, in_flight, vn, eta, beta):
    before = jnp.cumsum(sched) - sched
    return _jax_gradient_gap(vn, in_flight + before, eta, beta)


@DTYPES
@pytest.mark.parametrize("beta", BETAS)
def test_elementwise_gap_is_the_gathered_gap(precision, x64, beta):
    rng = np.random.default_rng([20, int(beta * 1000), x64])
    f = jnp.float64 if x64 else jnp.float32
    i = jnp.asarray(0).dtype
    for _ in range(12):
        n = int(rng.integers(1, 4097))
        sched = jnp.asarray(rng.random(n) < rng.random())
        args = (sched, jnp.asarray(rng.integers(0, 100_001), i),
                jnp.asarray(rng.lognormal(0.0, 2.0), f),
                jnp.asarray(rng.uniform(1e-4, 1.0), f), jnp.asarray(beta, f))
        want, got = gathered(*args), elementwise(*args)
        assert got.dtype == want.dtype == f
        np.testing.assert_array_equal(bits(got), bits(want))


def parent_hook(sv):
    """The hook's decision as it read with the gather: ``(start,
    gap_sum, idle_gap, branch)`` from the slot's view ``sv``."""
    lax, f, i = jax.lax, sv.float_dtype, sv.int_dtype
    waiting, has_app, H = sv.waiting, sv.has_app, sv.H
    vn = _jax_trace_v_norm(sv.v_norm0, sv.version, jnp, sv.fp_zero)
    p_s = jnp.where(has_app, sv.pcor_g, sv.PT)
    p_i = jnp.where(has_app, sv.papp_g, sv.PI)
    base = (sv.V * p_s * sv.t_d + sv.fp_zero) - sv.Q
    rhs = sv.V * p_i * sv.t_d
    gap_idle_v = sv.idle_gap + sv.epsilon
    gap_vec = _jax_gradient_gap(vn, sv.in_flight + jnp.arange(sv.n + 1),
                                sv.eta, sv.beta)

    def commit(sched):
        before = jnp.cumsum(sched) - sched
        gaps = jnp.where(waiting, jnp.where(sched, gap_vec[before],
                                            gap_idle_v), 0.0)
        return sched, jnp.sum(gaps[:sv.n])

    def cost_le(g):
        return base + (H * g + sv.fp_zero) \
            <= rhs + (H * gap_idle_v + sv.fp_zero)

    reach = jnp.arange(sv.n + 1) < jnp.sum(waiting)
    g_min = jnp.min(jnp.where(reach, gap_vec, jnp.inf))
    g_max = jnp.max(jnp.where(reach, gap_vec, -jnp.inf))
    always = waiting & cost_le(g_max)
    coupled = jnp.any(waiting & cost_le(g_min) & ~always)

    def slow(_):
        def body(c, xs_i):
            j, gs = c
            w_i, b_i, r_i, gi_i = xs_i
            do = w_i & (b_i + (H * gap_vec[j] + sv.fp_zero)
                        <= r_i + (H * gi_i + sv.fp_zero))
            gs = gs + jnp.where(w_i, jnp.where(do, gap_vec[j], gi_i), 0.0)
            return (j + do.astype(i), gs), do
        (_, gs), sched = lax.scan(
            body, (jnp.asarray(0, i), jnp.asarray(0.0, f)),
            (waiting, base, rhs, gap_idle_v))
        return sched, gs

    branch = jnp.where(H > 0.0, jnp.where(coupled, 2, 1), 0)
    start, gap_sum = lax.switch(
        branch, (lambda _: commit(waiting & (base <= rhs)),
                 lambda _: commit(always), slow), None)
    idle_gap = jnp.where(waiting & ~start, sv.idle_gap + sv.epsilon,
                         sv.idle_gap)
    return start, gap_sum, idle_gap, branch


class CheckedOnline(OnlinePolicy):
    """Alg. 2 as shipped, which also runs the gather formula on the same
    slot view and counts, per branch, the slots it took, the slots in
    which it scheduled a user, and the slots in which any of ``(start,
    gap_sum, idle_gap)`` differs in a bit."""

    def init_carry(self, n, cfg):
        return {k: np.zeros(3, np.int64)
                for k in ("slots", "scheduled", "differ")}

    def scan_step(self, carry, sv):
        start0, gap0, idle0, branch = parent_hook(copy.copy(sv))
        carry, (start, gap_sum) = super().scan_step(carry, sv)
        differ = (jnp.any(start != start0)
                  | jnp.any(bits(gap_sum) != bits(gap0))
                  | jnp.any(bits(sv.idle_gap) != bits(idle0)))
        seen = {"slots": jnp.asarray(True), "scheduled": jnp.any(start),
                "differ": differ}
        carry = {k: v.at[branch].add(seen[k].astype(v.dtype))
                 for k, v in carry.items()}
        return carry, (start, gap_sum)


BRANCHES = ("fast", "order_free", "slow")


@pytest.fixture(scope="module")
def checked_runs():
    """Per-branch counts of the checked hook, per precision and staleness
    budget. Under the default L_b H stays 0, so the fast branch
    schedules (at a V small enough for Q to pass it); a tight L_b (2.0,
    as the engine matrix's carry-protocol parity runs) builds H > 0
    inside the short horizon, so the in-slot coupling binds and the slow
    replay runs beside the order-free branch."""
    out = {}
    prev = jax.config.jax_enable_x64
    try:
        for x64 in (False, True):
            jax.config.update("jax_enable_x64", x64)
            for budget, V, L_b in (("loose", 10.0, 1000.0),
                                   ("tight", 2000.0, 2.0)):
                sim = Scenario(policy=CheckedOnline(), engine="jax",
                               n_users=10, horizon_s=1500,
                               app_arrival_p=0.01, seed=11, V=V,
                               L_b=L_b).build()
                res = sim.run()
                assert res.updates > 0
                out[x64, budget] = sim.state.carry
    finally:
        jax.config.update("jax_enable_x64", prev)
    return out


@DTYPES
@pytest.mark.parametrize("branch", BRANCHES)
def test_hook_matches_the_gather_formula(checked_runs, branch, x64):
    carry = checked_runs[x64, "loose" if branch == "fast" else "tight"]
    b = BRANCHES.index(branch)
    assert carry["scheduled"][b] > 0, carry
    assert carry["differ"][b] == 0, carry


def output_digest(sim, res):
    """sha256 of the push log's six columns, each user's energy and
    update count, and the Q and H traces, as float64 bytes."""
    h = hashlib.sha256()
    for col in res.push_log.arrays():
        h.update(np.ascontiguousarray(col, np.float64).tobytes())
    for a in (sim.state.energy, sim.state.updates, res.trace_Q,
              res.trace_H):
        h.update(np.ascontiguousarray(a, np.float64).tobytes())
    return h.hexdigest()


# 2,000 users x 600 slots in float32 with the push log on, V and L_b
# scaled with the fleet from the fleet cells' 4000 and 1000 at 10^5
# users, so that H > 0 in 111 (plain) and 493 (lose) of the slots.
# Digests, push counts and drops recorded with the hook's gather form.
PINNED = {
    "plain": ("a5c3250e45a8fa3caa84b01aa6045870b91645a6aefda8f9183d65d7c"
              "7294227", 3954, 0),
    "lose": ("7e8f8fa395f2470dfc100150ac573f83b3d9398b75e451724a6069a171e"
             "48709", 3595, 471),
}


@pytest.mark.parametrize("scenario", sorted(PINNED))
def test_online_output_is_pinned(scenario):
    kw = dict(n_users=2000, horizon_s=600, seed=20, app_arrival_p=0.001,
              engine="jax", policy="online", collect_push_log=True,
              V=80.0, L_b=20.0, trace_every=1)
    if scenario == "lose":
        kw["dynamics"] = MarkovChurnDynamics(
            dropout="lose", p_off=0.0004, p_on=0.005, battery_min=0.15,
            battery_resume=0.2, battery_quantum=1e-4, p_net_bad=0.01,
            p_net_recover=0.05)
    sim = Scenario(**kw).build()
    res = sim.run()
    assert (output_digest(sim, res), len(res.push_log), res.drops) == \
        PINNED[scenario]
