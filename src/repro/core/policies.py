"""Pluggable scheduling policies: one registry, one carry protocol,
three engine hooks.

The paper evaluates four fixed schedulers (Sec. VII.B); this module turns
them into registered ``Policy`` objects so alternative schedulers — e.g.
the energy-minimal scheduling families of Pilla '22 or AutoFL-style
heterogeneity-aware schedulers — plug into the simulator without touching
any engine file.

Policy state is declarative: ``init_carry(n, cfg)`` returns ONE pytree of
per-run policy state (e.g. greedy's per-user wait counters, offline's next
plan slot) that every engine threads for the policy — the loop oracle and
the numpy engine mutate it in place, the jax backend carries it through
``lax.scan`` inside ``EngineState.carry``. A policy implements up to three
hooks, one per engine:

``decide_loop(sim, t, waiting, carry)``
    Reference semantics on the per-user object loop (the oracle). Required.
``decide_vectorized(eng, t, carry)``
    Same decisions on the struct-of-arrays numpy engine
    (``core/vector_engine.py``); the batched state is ``eng.s`` (an
    ``EngineState``). Set ``supports_vectorized = True``.
``scan_step(carry, sv) -> (carry, (start_mask, gap_sum))``
    Traced decision step inside the ``jax.lax.scan`` backend; set
    ``supports_jax = True``. ``sv`` is the mutable slot view the engine
    builds per step (masks, table gathers, queue scalars, the full-horizon
    arrival arrays for oracle lookahead). The hook must be functional in
    ``carry`` and may reach back to the host with ``sv.jax.pure_callback``
    for decision logic that cannot be traced (the offline knapsack does).
    Instance knobs must flow through ``scan_operands`` (traced operands),
    NOT be closed over — compiled scans are cached per ``jax_cache_key()``,
    which defaults to the policy class. Policies without the hook
    transparently degrade to the vectorized engine.

Equivalence contract: for a given seed the three hooks must produce the
same decision sequence — tests/test_sim_engines.py, tests/test_scenario.py
and tests/test_engine_matrix.py pin loop/vectorized/jax schedule parity
(bit-for-bit under ``jax_enable_x64``) for every registered policy.

Strings keep working everywhere: ``SimConfig(policy="online")`` resolves
through the registry (``resolve_policy``), and string lookups hand out a
per-name singleton. New code should pass ``Policy`` instances (see
``core/scenario.py``).
"""
from __future__ import annotations

import os
from typing import Dict, List, Tuple, Type

import numpy as np

# jax's pure_callback round-trips its operands through jax.device_put onto
# the CPU device before invoking the host function; forcing them back to
# numpy inside the callback then waits on a device whose only execution
# thread is parked inside the custom call waiting for the callback to
# return. On one-core hosts that is a hard deadlock (observed on the
# offline policy's plan_window callback from n_users~100 up). A second
# host-platform device gives the operand transfer its own thread.
# Best-effort: the flag only takes effect if jax has not yet created its
# CPU client when this module is first imported.
if os.cpu_count() == 1 and "xla_force_host_platform_device_count" \
        not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=2"
                               ).strip()

from .energy import APPS
from .engine_state import (MODE_COOL, MODE_TRAIN, MODE_WAIT, PLAN_CORUN,
                           PLAN_HOLD, PLAN_SEP)
from .lyapunov import UserSlotState
from .offline import knapsack_schedule, lemma1_lag_bounds
from .staleness import gradient_gap

__all__ = ["Policy", "register_policy", "registered_policies",
           "resolve_policy", "plan_window",
           "SyncPolicy", "ImmediatePolicy", "OnlinePolicy", "OfflinePolicy",
           "GreedyThresholdPolicy", "EpsGreedyPolicy",
           "MODE_WAIT", "MODE_TRAIN", "MODE_COOL",
           "PLAN_HOLD", "PLAN_CORUN", "PLAN_SEP"]


class Policy:
    """Base scheduling policy. Subclass, set ``name``, implement hooks,
    and decorate with ``@register_policy`` to make the name resolvable.

    Class attributes describe engine semantics the engines must honor:

    - ``sync_rounds``: lock-step rounds — the global version bumps once per
      round close (all trainers finished), not per push.
    - ``uses_online_queue``: the per-slot Lyapunov decision runs on-device,
      so ``include_scheduler_overhead`` adds Table III's scheduler power
      while waiting.
    - ``supports_vectorized`` / ``supports_jax``: which engine hooks exist.
      ``SimConfig`` validates the flags against the actual hook methods at
      construction, so a mismatch fails fast with a clear message instead
      of erroring mid-run.
    - ``supports_vmap``: whether ``scan_step`` may run under ``jax.vmap``
      over a leading config axis (the batched sweep path,
      ``core.scenario.run_sweep``). True for pure traced hooks; set False
      for hooks with host side effects — under vmap ``lax.cond`` evaluates
      both branches per config, so e.g. a ``pure_callback`` guarded by a
      plan-slot cond would fire for every config at every slot.
    - ``supports_shard``: whether ``scan_step`` may run with the user axis
      sharded over a device mesh (``SimConfig.n_devices``,
      core/vector_engine.py). The engine hands the hook REPLICATED
      per-user inputs (so cross-user reductions like Eq. 16's gap sum
      keep the single-device float order) plus padding helpers:
      ``sv.n`` is always the LIVE user count, ``sv.n_arr`` the padded
      array length (== ``sv.n`` unsharded), and hooks drawing per-user
      randomness must draw at ``sv.n`` and extend via
      ``sv.pad_users(x, fill)`` — threefry draws are shape-dependent, so
      drawing at ``n_arr`` would fork the stream from the unsharded
      engines. Set False for hooks with host callbacks in the step.
    """

    name: str = ""
    sync_rounds: bool = False
    uses_online_queue: bool = False
    supports_vectorized: bool = False
    supports_jax: bool = False
    supports_vmap: bool = True
    supports_shard: bool = True

    # ------------------------------------------------------------ carry
    def init_carry(self, n: int, cfg):
        """Per-run policy state as ONE pytree shared by every engine:
        numpy arrays / scalars that the loop and numpy engines mutate in
        place and the jax backend converts to device arrays and threads
        through the scan (``EngineState.carry``). Return ``None`` for
        stateless policies."""
        return None

    def scan_operands(self, cfg) -> tuple:
        """Instance knobs the jax hook needs, as a flat tuple of scalars.
        They are passed as TRACED operands (``sv.consts``), so runs with
        different knob values share one compiled scan; reading instance
        attributes directly from ``scan_step`` instead would bake the
        first run's values into the class-keyed executable cache."""
        return ()

    def scan_statics(self, cfg) -> tuple:
        """Values the jax hook needs as STATIC Python constants (e.g.
        shapes of intermediate slices), as a flat hashable tuple. Unlike
        ``scan_operands`` these are baked into the trace (``sv.statics``)
        and included in the jit cache key, so each distinct tuple compiles
        its own scan — keep them to genuinely shape-like knobs."""
        return ()

    def jax_cache_key(self):
        """Hashable token identifying this policy's ``scan_step``
        behavior: two policies with equal keys share one compiled scan.

        The default keys by CLASS when that is provably safe — the
        instance carries no attributes, or it declares its knobs through
        ``scan_operands`` (traced) — so fresh instances of registry
        policies reuse one executable per shape. Any other instance is
        keyed by itself: a ``scan_step`` that reads ad-hoc instance state
        directly then at worst recompiles per instance, never silently
        reuses another instance's baked-in values. Policies that override
        ``scan_operands`` must route ALL hook-read knobs through it (or
        ``scan_statics``)."""
        if not vars(self) or \
                type(self).scan_operands is not Policy.scan_operands:
            return type(self)
        return self

    # ------------------------------------------------------------- loop hook
    def decide_loop(self, sim, t: int, waiting: list, carry
                    ) -> Tuple[int, float]:
        """Schedule waiting users for slot ``t`` via ``sim.begin_training``.
        Returns (served, gap_sum) feeding Eqs. (15)/(16)."""
        raise NotImplementedError(
            f"policy {self.name!r} implements no loop hook")

    # ------------------------------------------------- vectorized (numpy) hook
    def decide_vectorized(self, eng, t: int, carry) -> Tuple[int, float]:
        """Same decisions on the batched engine ``eng`` (state:
        ``eng.s``, an EngineState; per-slot masks: ``eng.waiting`` /
        ``eng.has_app``). Returns (served, gap_sum). Only called when
        ``supports_vectorized``; SimConfig validates the flag against the
        hook at construction."""
        raise TypeError(
            f"policy {self.name!r} sets supports_vectorized but inherits "
            "the base decide_vectorized; implement the hook or clear the "
            "flag")

    # ----------------------------------------------------------- jax scan hook
    def scan_step(self, carry, sv):
        """Traced decision inside the lax.scan step. Read the slot view
        ``sv`` (``waiting``, ``has_app``, per-user power gathers, queue
        scalars, ``sv.consts`` from ``scan_operands``); write ``sv.idle_gap``
        / ``sv.round_open`` / ``sv.plan`` if the policy owns them. Return
        ``(carry, (start_mask, gap_sum))``. Only called when
        ``supports_jax``; SimConfig validates the flag against the hook at
        construction."""
        raise TypeError(
            f"policy {self.name!r} sets supports_jax but inherits the base "
            "scan_step; implement the hook or clear the flag")


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------
_REGISTRY: Dict[str, Type[Policy]] = {}
_INSTANCES: Dict[str, Policy] = {}       # singletons for string lookups


def register_policy(cls: Type[Policy]) -> Type[Policy]:
    """Class decorator: make ``cls`` resolvable as ``cls.name``."""
    if not cls.name:
        raise ValueError(f"{cls.__name__} must set a registry name")
    _REGISTRY[cls.name] = cls
    _INSTANCES.pop(cls.name, None)       # re-registration wins
    return cls


def registered_policies() -> Tuple[str, ...]:
    return tuple(_REGISTRY)


def resolve_policy(policy) -> Policy:
    """String -> registered singleton; Policy instance -> itself."""
    if isinstance(policy, Policy):
        return policy
    if isinstance(policy, str):
        if policy not in _REGISTRY:
            raise ValueError(f"unknown policy {policy!r}; "
                             f"expected one of {registered_policies()} "
                             "or a Policy instance")
        if policy not in _INSTANCES:
            _INSTANCES[policy] = _REGISTRY[policy]()
        return _INSTANCES[policy]
    raise ValueError(f"policy must be a name or Policy instance, "
                     f"got {type(policy).__name__}")


def engine_support(policy: Policy) -> Dict[str, bool]:
    """Which engine hooks ``policy`` GENUINELY implements (flag set AND
    the base stub overridden). SimConfig uses this to reject
    flag-vs-implementation mismatches at construction instead of letting
    the base stubs raise mid-run."""
    cls = type(policy)
    return {
        "loop": cls.decide_loop is not Policy.decide_loop,
        "vectorized": (policy.supports_vectorized and
                       cls.decide_vectorized is not Policy.decide_vectorized),
        "jax": (policy.supports_jax and
                cls.scan_step is not Policy.scan_step),
    }


# ---------------------------------------------------------------------------
# jnp twins of the shared numpy formulas (np ufuncs don't dispatch on jax
# tracers on this JAX version). Any change to the originals MUST land here
# too — the jax-vs-loop parity suite is the tripwire.
# ---------------------------------------------------------------------------
def _jax_trace_v_norm(v_norm0, version, jnp, zero=0.0):
    """Mirror of simulator.trace_v_norm.

    ``zero`` must be a TRACED 0.0 when called inside jit: XLA's CPU
    codegen is free to contract ``1.0 + 0.05 * version`` into a single
    fma, which skips the product's rounding step and drifts an ulp from
    the numpy original (optimization_barrier does not survive fusion).
    Adding a runtime-opaque zero to the product forces the rounding:
    even if the inner add contracts, ``fma(0.05, version, 0.0)`` IS the
    correctly-rounded product, and the outer add has no fmul operand
    left to contract with."""
    return v_norm0 / jnp.sqrt(1.0 + (0.05 * version + zero))


def _jax_gradient_gap(v_norm, lag, eta, beta):
    """Mirror of staleness.gradient_gap/momentum_scale (Eq. 4). beta is a
    traced scalar, so no beta==0 branch: 0**0==1 makes the closed form
    agree at lag=0."""
    return eta * (1.0 - beta ** lag) / (1.0 - beta) * v_norm


# ---------------------------------------------------------------------------
# Offline window planning (Alg. 1) over array state — ONE implementation
# shared by the numpy engine's decide hook and the jax engine's
# pure_callback, so the knapsack decisions are bit-identical by
# construction on both batched engines.
# ---------------------------------------------------------------------------
def plan_window(plan, t, widx, app, app_sched, app_choice, T_COR, SRATE,
                window, v_norm, L_b, resolution, eta, beta, row0=0):
    """One Alg. 1 plan over the look-ahead window, mutating and returning
    ``plan`` (the per-user PLAN_* codes in ``EngineState.plan``).

    Candidates are waiting users (``widx``) with an app running now or an
    (oracle lookahead) arrival inside the window; the knapsack picks which
    of them wait to co-run, the rest train immediately. Users without an
    in-window arrival hold until the next plan.

    ``app_sched``/``app_choice`` may be the full horizon (``row0 = 0``,
    the numpy engine) or just a slice whose row i is absolute slot
    ``row0 + i`` (the jax callback ships only the window to the host)."""
    if not len(widx):
        return plan
    W = int(window)
    horizon = min(t + W, row0 + app_sched.shape[0])
    sub = app_sched[t - row0:horizon - row0][:, widx]  # (window, n_waiting)
    if sub.shape[0]:
        has_arr = sub.any(axis=0)
        first = sub.argmax(axis=0)                   # first arrival offset
    else:
        # sub-slot window (int(window) == 0) or horizon tail: no lookahead
        # rows — only users with an app running now are candidates (the
        # loop oracle's semantics; bare argmax would crash on the empty
        # axis, which the historical _plan_vec did)
        has_arr = np.zeros(len(widx), dtype=bool)
        first = np.zeros(len(widx), dtype=np.int64)
    ha = app[widx] >= 0
    cand = ha | has_arr
    plan[widx[~cand]] = PLAN_HOLD
    cidx = widx[cand]
    if not len(cidx):
        return plan
    ta = np.where(ha[cand], t, t + first[cand])      # absolute slots
    # np.where evaluates both branches: app-running candidates (ha) take
    # app[cidx], but their discarded app_choice gather still needs an
    # in-bounds row — clamp covers them (non-ha rows are in-window by
    # construction, so the clamp never alters a selected lane)
    if app_choice.shape[0]:
        pick = app_choice[np.minimum(ta - row0, app_choice.shape[0] - 1),
                          cidx]
    else:
        pick = np.zeros(len(cidx), dtype=np.int64)   # all-ha candidates
    aid = np.where(ha[cand], app[cidx], pick)
    durs = T_COR[cidx, aid]
    savings = SRATE[cidx, aid] * durs
    lags = lemma1_lag_bounds(np.full(len(cidx), t), ta, durs)
    gaps = np.asarray(gradient_gap(v_norm, lags, eta, beta), dtype=float)
    x, _ = knapsack_schedule(savings, gaps, L_b, resolution=resolution)
    plan[cidx] = np.where(x, PLAN_CORUN, PLAN_SEP)
    return plan


def _offline_plan_host(t, waiting, plan, app, version, sched_w, choice_w,
                       row0, T_COR, SRATE, window, L_b, resolution, eta,
                       beta, v_norm0):
    """pure_callback target for OfflinePolicy.scan_step: the same
    ``plan_window`` the numpy engine runs, on host numpy, fed entirely by
    traced operands (nothing closed over — the compiled scan is shared
    across runs). ``sched_w``/``choice_w`` are just the look-ahead window
    rows, sliced on device (row i = absolute slot ``row0 + i``), so the
    host transfer is O(window * n), not the full horizon. Returns the new
    plan array."""
    from .simulator import trace_v_norm

    t = int(t)
    plan = np.array(plan)                           # functional: copy
    widx = np.nonzero(np.asarray(waiting))[0]
    vn = trace_v_norm(float(v_norm0), int(version))
    out = plan_window(plan, t, widx, np.asarray(app),
                      np.asarray(sched_w), np.asarray(choice_w),
                      np.asarray(T_COR), np.asarray(SRATE),
                      float(window), vn, float(L_b), float(resolution),
                      float(eta), float(beta), row0=int(row0))
    return out.astype(plan.dtype, copy=False)


# ---------------------------------------------------------------------------
# The four paper policies (Sec. VII.B)
# ---------------------------------------------------------------------------
@register_policy
class SyncPolicy(Policy):
    """FedAvg lock-step: a round starts only when the whole cohort waits."""

    name = "sync"
    sync_rounds = True
    supports_vectorized = True
    supports_jax = True

    def decide_loop(self, sim, t, waiting, carry):
        served = 0
        if not sim._round_open and len(waiting) == sim.cfg.n_users:
            for u in waiting:
                sim.begin_training(u, t, corun=u.app is not None)
                served += 1
            sim._round_open = True
        return served, 0.0

    def decide_vectorized(self, eng, t, carry):
        s = eng.s
        if not s.round_open and \
                int(np.count_nonzero(eng.waiting)) == eng.n:
            eng.begin_training(eng.ar)
            s.round_open = True
            return eng.n, 0.0
        return 0, 0.0

    def scan_step(self, carry, sv):
        jnp = sv.jnp
        open_now = (~sv.round_open) & (jnp.sum(sv.waiting) == sv.n)
        start = sv.waiting & open_now
        sv.round_open = sv.round_open | open_now
        return carry, (start, jnp.asarray(0.0, sv.float_dtype))


@register_policy
class ImmediatePolicy(Policy):
    """ASync baseline: schedule every waiting user ASAP (energy ceiling)."""

    name = "immediate"
    supports_vectorized = True
    supports_jax = True

    def decide_loop(self, sim, t, waiting, carry):
        for u in waiting:
            sim.begin_training(u, t, corun=u.app is not None)
        return len(waiting), 0.0

    def decide_vectorized(self, eng, t, carry):
        if eng.waiting.any():
            widx = np.nonzero(eng.waiting)[0]
            eng.begin_training(widx)
            return len(widx), 0.0
        return 0, 0.0

    def scan_step(self, carry, sv):
        return carry, (sv.waiting, sv.jnp.asarray(0.0, sv.float_dtype))


@register_policy
class OnlinePolicy(Policy):
    """Lyapunov drift-plus-penalty controller (Alg. 2, Eqs. 21-23)."""

    name = "online"
    uses_online_queue = True
    supports_vectorized = True
    supports_jax = True

    def decide_loop(self, sim, t, waiting, carry):
        cfg = sim.cfg
        vn = sim._v_norm()
        served = 0
        gap_sum = 0.0
        for u in waiting:
            a = u.app is not None
            ap = u.device.apps[u.app] if a else None
            st = UserSlotState(
                p_corun=ap.p_corun if a else 0.0,
                p_app=ap.p_app if a else 0.0,
                p_train=u.device.p_train, p_idle=u.device.p_idle,
                app_running=a,
                lag_estimate=sim.in_flight,
                idle_gap=u.idle_gap)
            d = sim.sched.decide(st, vn)
            gap_sum += d.gap
            if d.schedule:
                sim.begin_training(u, t, corun=a)
                served += 1
            else:
                u.idle_gap += cfg.epsilon
        return served, gap_sum

    def decide_vectorized(self, eng, t, carry):
        if not eng.waiting.any():
            return 0, 0.0
        s = eng.s
        widx = np.nonzero(eng.waiting)[0]
        vn = eng.v_norm(s.version)
        d = eng.sched.decide_batch(eng.p_if_train[widx], eng.p_if_idle[widx],
                                   s.idle_gap[widx], s.in_flight, vn)
        if d.n_served:
            eng.begin_training(widx[d.schedule])
        if d.n_served != len(widx):
            s.idle_gap[widx[~d.schedule]] += eng.cfg.epsilon
        return d.n_served, d.gap_sum

    def scan_step(self, carry, sv):
        jnp, lax = sv.jnp, sv.lax
        f, i = sv.float_dtype, sv.int_dtype
        waiting, has_app = sv.waiting, sv.has_app
        H = sv.H
        vn = _jax_trace_v_norm(sv.v_norm0, sv.version, jnp, sv.fp_zero)
        p_s = jnp.where(has_app, sv.pcor_g, sv.PT)
        p_i = jnp.where(has_app, sv.papp_g, sv.PI)
        # fp_zero blocks fma contraction of the products (see
        # _jax_trace_v_norm): the host rounds V*P*t_d before subtracting
        base = (sv.V * p_s * sv.t_d + sv.fp_zero) - sv.Q
        rhs = sv.V * p_i * sv.t_d
        gap_idle_v = sv.idle_gap + sv.epsilon
        lag_idx = sv.in_flight + jnp.arange(sv.n + 1)
        gap_vec = _jax_gradient_gap(vn, lag_idx, sv.eta, sv.beta)

        def commit(sched):
            # sv.repl pins `sched` replicated: it has a sharded consumer
            # in the engine (begin-training), and without the pin GSPMD
            # propagates that layout back through cumsum/gather/where and
            # turns the gap_sum below into reassociated shard-local
            # partials + AllReduce (the reduce(all-gather) -> all-reduce
            # rewrite), flipping low bits of the Eq. 16 H update
            sched = sv.repl(sched)
            before = jnp.cumsum(sched) - sched
            gaps = jnp.where(waiting, jnp.where(sched, gap_vec[before],
                                                gap_idle_v), 0.0)
            # sum the LIVE lanes only ([:sv.n] folds to a no-op when the
            # sharded scan hasn't padded the user axis): pad lanes never
            # wait, and excluding their zeros keeps the reduction tree —
            # hence the Eq. 16 H update — bit-identical to unsharded
            return sched, jnp.sum(gaps[:sv.n])

        def fast(_):
            # H == 0: the gap term adds exactly 0 to both branches
            return commit(waiting & (base <= rhs))

        def cost_le(g):
            return base + (H * g + sv.fp_zero) \
                <= rhs + (H * gap_idle_v + sv.fp_zero)

        # H > 0: user i sees in-slot lag j = #scheduled before it, with j
        # below the waiting count. The cost is monotone in gap_vec[j], so a
        # user that passes at the largest reachable gap passes at every j
        # and one that fails at the smallest fails at every j: when no
        # waiting user lies between, the decisions are order-free and equal
        # the sequential replay's. Only a user in between needs the replay,
        # which on an accelerator costs one loop step per user. The Eq. 16
        # sum is then a tree, as in the NumPy engine's decide_batch, so H
        # matches the replay's left fold to rounding.
        reach = jnp.arange(sv.n + 1) < jnp.sum(waiting)
        g_min = jnp.min(jnp.where(reach, gap_vec, jnp.inf))
        g_max = jnp.max(jnp.where(reach, gap_vec, -jnp.inf))
        always = waiting & cost_le(g_max)
        coupled = jnp.any(waiting & cost_le(g_min) & ~always)

        def order_free(_):
            return commit(always)

        def slow(_):
            # sequential in-slot lag coupling, user-index order
            def body(c, xs_i):
                j, gs = c
                w_i, b_i, r_i, gi_i = xs_i
                do = w_i & (b_i + (H * gap_vec[j] + sv.fp_zero)
                            <= r_i + (H * gi_i + sv.fp_zero))
                gap_i = jnp.where(do, gap_vec[j], gi_i)
                gs = gs + jnp.where(w_i, gap_i, 0.0)
                return (j + do.astype(i), gs), do
            (j, gs), sched = lax.scan(
                body, (jnp.asarray(0, i), jnp.asarray(0.0, f)),
                (waiting, base, rhs, gap_idle_v))
            return sched, gs

        branch = jnp.where(H > 0.0, jnp.where(coupled, 2, 1), 0)
        start, gap_sum = lax.switch(branch, (fast, order_free, slow), None)
        sv.idle_gap = jnp.where(waiting & ~start,
                                sv.idle_gap + sv.epsilon, sv.idle_gap)
        return carry, (start, gap_sum)


@register_policy
class OfflinePolicy(Policy):
    """Oracle knapsack with look-ahead window (Alg. 1).

    Carry: the next plan slot. The window plan itself writes the per-user
    ``plan`` codes in ``EngineState.plan`` (engine state: the engines reset
    a user's plan to HOLD when it re-enters the waiting queue). Under the
    jax engine the knapsack DP — host numpy, pseudo-polynomial in
    ``L_b / resolution`` — runs through ``jax.pure_callback`` inside a
    ``lax.cond``, so the host is consulted only at plan slots (every
    ``offline_window`` seconds) and the decisions are bit-identical to the
    numpy engine's, which calls the same ``plan_window``."""

    name = "offline"
    supports_vectorized = True
    supports_jax = True
    # host knapsack via pure_callback: under vmap the plan-slot cond
    # runs both branches per config, consulting the host every slot for
    # every config — keep this policy on the per-point scan path
    supports_vmap = False
    # ... and the callback cannot run inside a GSPMD-partitioned step
    # either: keep it off the sharded scan (SimConfig.n_devices)
    supports_shard = False

    def init_carry(self, n, cfg):
        return {"next_plan": 0.0}

    def decide_loop(self, sim, t, waiting, carry):
        cfg = sim.cfg
        if t >= carry["next_plan"]:
            carry["next_plan"] = t + cfg.offline_window
            self._plan_loop(sim, t, waiting)
        served = 0
        for u in waiting:
            if u.plan == "corun":
                if u.app is not None:
                    sim.begin_training(u, t, corun=True)
                    served += 1
            elif u.plan == "separate":
                sim.begin_training(u, t, corun=u.app is not None)
                served += 1
            # plan == "hold"/"none": idle until the next window
        return served, 0.0

    def _plan_loop(self, sim, t: int, waiting: List):
        """Knapsack over the look-ahead window (Alg. 1), object form (the
        readable oracle; ``plan_window`` is its array twin).

        Users whose app arrival falls inside the window are knapsack
        candidates: selected -> wait for the arrival and co-run (x_i = 1);
        rejected -> train immediately, separate execution (x_i = 0). Users
        without an in-window arrival hold (idle) until the next window —
        with the paper's relaxed L_b = 1000 this reduces to the "greedy
        always waiting for co-running opportunities" behaviour of Fig. 4a.
        """
        cfg = sim.cfg
        W = int(cfg.offline_window)
        cands, t_app, t_now, durs, savings = [], [], [], [], []
        for u in waiting:
            # next app arrival within the window (oracle lookahead)
            i = u._uid
            horizon = min(t + W, sim.app_sched.shape[0])
            arr = np.nonzero(sim.app_sched[t:horizon, i])[0]
            if u.app is not None:
                ta, app = t, u.app
            elif len(arr):
                ta = t + int(arr[0])
                app = APPS[sim.app_choice[ta, i]]
            else:
                u.plan = "hold"
                continue
            cands.append(u)
            t_now.append(t)
            t_app.append(ta)
            durs.append(u.device.apps[app].t_corun)
            savings.append(u.device.energy_saving_rate(app)
                           * u.device.apps[app].t_corun)
        if not cands:
            return
        lags = lemma1_lag_bounds(np.array(t_now), np.array(t_app),
                                 np.array(durs))
        vn = sim._v_norm()
        gaps = np.array([gradient_gap(vn, int(l), cfg.eta, cfg.beta)
                         for l in lags])
        x, _ = knapsack_schedule(np.array(savings), gaps, cfg.L_b,
                                 resolution=cfg.offline_resolution)
        for u, chosen in zip(cands, x):
            u.plan = "corun" if chosen else "separate"

    def decide_vectorized(self, eng, t, carry):
        cfg = eng.cfg
        s = eng.s
        if t >= carry["next_plan"]:
            carry["next_plan"] = t + cfg.offline_window
            plan_window(s.plan, t, np.nonzero(eng.waiting)[0], s.app,
                        eng.app_sched, eng.app_choice, eng.T_COR, eng.SRATE,
                        cfg.offline_window, eng.v_norm(s.version),
                        cfg.L_b, cfg.offline_resolution, cfg.eta, cfg.beta)
        start = eng.waiting & (((s.plan == PLAN_CORUN) & eng.has_app) |
                               (s.plan == PLAN_SEP))
        if start.any():
            sidx = np.nonzero(start)[0]
            eng.begin_training(sidx)
            return len(sidx), 0.0
        return 0, 0.0

    def scan_statics(self, cfg) -> tuple:
        # the look-ahead slice shipped to the host callback needs a
        # static row count; baked into the trace + jit cache key
        return (int(cfg.offline_window),)

    def scan_step(self, carry, sv):
        jnp, lax, jax = sv.jnp, sv.lax, sv.jax
        nxt = carry["next_plan"]
        do_plan = sv.t >= nxt
        n, T, plan_dtype = sv.n, sv.T, sv.plan.dtype
        (W,) = sv.statics
        Wc = min(max(W, 0), T)          # static window rows

        def plan_now(args):
            t, waiting, plan, app, version = args
            # slice just the look-ahead window for the host (inside the
            # taken cond branch: the gather + device->host copy happen at
            # plan slots only, and cost O(window * n), never O(T * n));
            # the start clamps at the horizon tail, row0 re-anchors it
            row0 = jnp.minimum(t, T - Wc)
            sched_w = lax.dynamic_slice(sv.app_sched, (row0, 0), (Wc, n))
            choice_w = lax.dynamic_slice(sv.app_choice, (row0, 0), (Wc, n))
            return jax.pure_callback(
                _offline_plan_host,
                jax.ShapeDtypeStruct((n,), plan_dtype),
                t, waiting, plan, app, version, sched_w, choice_w, row0,
                sv.T_COR, sv.SRATE, sv.offline_window, sv.L_b,
                sv.offline_resolution, sv.eta, sv.beta, sv.v_norm0)

        args = (sv.t, sv.waiting, sv.plan, sv.app, sv.version)
        sv.plan = lax.cond(do_plan, plan_now, lambda a: a[2], args)
        nxt = jnp.where(do_plan, sv.t + sv.offline_window, nxt)
        start = sv.waiting & (((sv.plan == PLAN_CORUN) & sv.has_app) |
                              (sv.plan == PLAN_SEP))
        return {"next_plan": nxt}, \
            (start, jnp.asarray(0.0, sv.float_dtype))


# ---------------------------------------------------------------------------
# A genuinely new registered policy: proof the registry extends beyond the
# paper's four schedulers.
# ---------------------------------------------------------------------------
@register_policy
class GreedyThresholdPolicy(Policy):
    """Greedy energy-threshold baseline (not in the paper).

    Schedules a waiting user as soon as the *marginal* power of training is
    cheap — below ``theta`` watts over what the device would burn anyway:
    P^{a'} - P^a while an app runs (the co-run discount), P^b - P^d when
    idle. Users that never see a cheap slot are force-scheduled after
    ``patience`` waiting slots, so progress is guaranteed without any queue
    machinery. A natural midpoint between "immediate" (theta = inf) and
    "wait for co-runs" (theta small, patience large).

    Carry: the per-user wait counters — the canonical stateful-policy
    example of the carry protocol (one ``(n,)`` array threaded identically
    through the loop, numpy and lax.scan engines). ``theta``/``patience``
    reach the traced hook as ``scan_operands``, so a parameter sweep
    reuses one compiled scan.
    """

    name = "greedy"
    supports_vectorized = True
    supports_jax = True

    def __init__(self, theta: float = 0.3, patience: int = 240):
        if patience < 0:
            raise ValueError(f"patience must be >= 0, got {patience}")
        self.theta = float(theta)
        self.patience = int(patience)

    def init_carry(self, n, cfg):
        return {"waited": np.zeros(n, dtype=np.int64)}

    def scan_operands(self, cfg):
        return (self.theta, self.patience)

    def decide_loop(self, sim, t, waiting, carry):
        waited = carry["waited"]
        served = 0
        for u in waiting:
            a = u.app is not None
            if a:
                ap = u.device.apps[u.app]
                delta = ap.p_corun - ap.p_app
            else:
                delta = u.device.p_train - u.device.p_idle
            i = u._uid
            if delta <= self.theta or waited[i] >= self.patience:
                sim.begin_training(u, t, corun=a)
                waited[i] = 0
                served += 1
            else:
                waited[i] += 1
        return served, 0.0

    def decide_vectorized(self, eng, t, carry):
        w = eng.waiting
        if not w.any():
            return 0, 0.0
        # p_if_train/p_if_idle are exactly (P^{a'}, P^a) with an app and
        # (P^b, P^d) without — the same operands the loop hook compares
        delta = eng.p_if_train - eng.p_if_idle
        waited = carry["waited"]
        go = w & ((delta <= self.theta) | (waited >= self.patience))
        if go.any():
            eng.begin_training(np.nonzero(go)[0])
        waited[go] = 0
        waited[w & ~go] += 1
        return int(np.count_nonzero(go)), 0.0

    def scan_step(self, carry, sv):
        jnp = sv.jnp
        theta, patience = sv.consts
        waited = carry["waited"]
        delta = jnp.where(sv.has_app, sv.pcor_g - sv.papp_g, sv.PT - sv.PI)
        go = sv.waiting & ((delta <= theta) | (waited >= patience))
        waited = jnp.where(go, 0,
                           jnp.where(sv.waiting & ~go, waited + 1, waited))
        return {"waited": waited}, \
            (go, jnp.asarray(0.0, sv.float_dtype))


# ---------------------------------------------------------------------------
# A stochastic registry policy: draws ride the run's EngineState.rng_key
# through the carry protocol, so the SAME threefry stream drives the loop
# oracle, the numpy engine and the lax.scan backend bit-identically.
# ---------------------------------------------------------------------------
def _eps_draw(rng_key, n):
    """One slot's exploration draws on the host: split the run key, draw
    ``(n,)`` f32 uniforms. jax's counter-based threefry PRNG produces the
    SAME bits eagerly (here) and traced (inside ``scan_step``), which is
    what makes the three engine hooks decision-identical."""
    import jax
    import jax.numpy as jnp

    k2, sub = jax.random.split(jnp.asarray(rng_key))
    u = jax.random.uniform(sub, (n,), jnp.float32)
    return np.asarray(k2, dtype=np.uint32), np.asarray(u)


@register_policy
class EpsGreedyPolicy(Policy):
    """Epsilon-greedy exploration over the greedy marginal-power rule.

    Exploit: schedule a waiting user when training is marginally cheap
    (the ``GreedyThresholdPolicy`` comparison, ``delta <= theta``).
    Explore: with probability ``eps`` per user per slot, schedule anyway
    — a stochastic escape hatch that guarantees progress without wait
    counters and trades energy for staleness at a tunable rate.

    The randomness is drawn from ``EngineState.rng_key`` — the seeded
    ``(2,)`` uint32 counter key every engine threads — via one
    ``jax.random.split`` + ``(n,)`` uniform per slot, consumed
    UNCONDITIONALLY (even with nobody waiting) so the key chain advances
    identically on every engine: the loop and numpy hooks draw eagerly
    and write the split key back into the state, the jax hook draws
    traced inside the scan and threads it through ``sv.rng_key``.
    threefry is counter-based and jit-invariant, so the decisions are
    bit-identical across all three engines (pinned by the engine
    matrix). ``eps``/``theta`` reach the traced hook as
    ``scan_operands``, so a parameter sweep reuses one compiled scan.
    """

    name = "eps_greedy"
    supports_vectorized = True
    supports_jax = True

    def __init__(self, eps: float = 0.05, theta: float = 0.3):
        if not 0.0 <= eps <= 1.0:
            raise ValueError(f"eps must be in [0, 1], got {eps}")
        self.eps = float(eps)
        self.theta = float(theta)

    def scan_operands(self, cfg):
        return (self.eps, self.theta)

    def decide_loop(self, sim, t, waiting, carry):
        s = sim.state
        s.rng_key, u = _eps_draw(s.rng_key, sim.cfg.n_users)
        served = 0
        for usr in waiting:
            a = usr.app is not None
            if a:
                ap = usr.device.apps[usr.app]
                delta = ap.p_corun - ap.p_app
            else:
                delta = usr.device.p_train - usr.device.p_idle
            if u[usr._uid] < self.eps or delta <= self.theta:
                sim.begin_training(usr, t, corun=a)
                served += 1
        return served, 0.0

    def decide_vectorized(self, eng, t, carry):
        s = eng.s
        s.rng_key, u = _eps_draw(s.rng_key, eng.n)
        w = eng.waiting
        if not w.any():
            return 0, 0.0
        delta = eng.p_if_train - eng.p_if_idle
        go = w & ((u < self.eps) | (delta <= self.theta))
        if go.any():
            eng.begin_training(np.nonzero(go)[0])
        return int(np.count_nonzero(go)), 0.0

    def scan_step(self, carry, sv):
        jnp, jax = sv.jnp, sv.jax
        eps, theta = sv.consts
        k2, sub = jax.random.split(sv.rng_key)
        u = jax.random.uniform(sub, (sv.n,), jnp.float32)
        # live-n draw + fill-1.0 pad: keeps the threefry stream identical
        # to the unsharded engines when the sharded scan pads the user
        # axis (1.0 is never < eps, so pad lanes never explore)
        u = sv.pad_users(u, 1.0)
        sv.rng_key = k2
        delta = jnp.where(sv.has_app, sv.pcor_g - sv.papp_g, sv.PT - sv.PI)
        go = sv.waiting & ((u < eps) | (delta <= theta))
        return carry, (go, jnp.asarray(0.0, sv.float_dtype))
