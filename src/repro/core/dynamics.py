"""Device dynamics: availability, battery, and network churn as per-user
state machines updated in-scan.

The paper's simulator assumes an always-on fleet — every device that pulls
a model finishes and pushes, batteries never gate participation, and the
post-push re-arrival delay is a constant. Real battery-powered fleets
churn constantly: AutoFL (Kim & Wu '21) shows stochastic runtime/energy
variance from co-running apps and network conditions is first-order, and
DEAL (Zou et al. '21) gates participation on battery level. This module
makes that churn a first-class simulation layer, following the
Policy/AggregationRule protocol shape (core/policies.py,
core/aggregation.py): a registry of ``DeviceDynamics`` objects whose
per-user state rides in ``EngineState.dyn`` and whose per-slot transition
runs at the TOP of every slot on all three engines —

``init_state(n, cfg, fleet=None)``
    One pytree of per-user ``(n,)`` arrays (availability chain state,
    battery level, network state, drop counters, plus any run-constant
    per-user parameter gathers — per-device-class values must be gathered
    per user here, like ``hetero_aware``'s scale carry) and 0-d run
    counters. ``None`` for the inactive ``none`` dynamics.
``host_step(dyn, rng_key, mode, corun, t_d)``
    The host (numpy) transition, shared verbatim by the loop oracle and
    the numpy engine — ONE implementation, so loop/vectorized parity
    holds by construction. Randomness comes from the run's
    ``EngineState.rng_key`` via jax's counter-based threefry (drawn
    eagerly here, traced in ``scan_step`` — identical bits, the
    ``eps_greedy`` trick), consumed UNCONDITIONALLY once per slot so the
    key chain advances identically on every engine. Returns
    ``(new_dyn, new_rng_key, DynEffects)``.
``scan_step(dyn, dv)``
    The traced twin inside the jax engine's ``lax.scan`` step. ``dv`` is
    the dynamics slot view (``jnp``/``jax``, ``rng_key`` — read AND
    write back the split key — ``mode``, ``corun``, ``t_d``, ``fp_zero``
    — a traced 0.0 for fma-contraction armor — ``consts``
    from ``scan_operands``). Returns ``(new_dyn, DynEffects)`` with
    jnp-array fields. Instance knobs must flow through
    ``scan_operands`` (traced), never be closed over; compiled scans are
    cached per ``jax_cache_key()``.

The ENGINES apply the effects — the dynamics object only decides who went
up/down. The shared effect semantics every engine implements identically
(pinned by tests/test_dynamics_faults.py):

- a WAITING user that goes down leaves the request queue: ``mode`` becomes
  OFF and the slot's ``departures`` count feeds
  ``OnlineScheduler.update_queues`` (Eq. 15 becomes
  ``Q <- max(Q - served - departures, 0) + arrivals``);
- a TRAINING user that goes down follows the dynamics' ``dropout`` rule:
  ``"lose"`` — the in-flight work is lost (mode OFF, ``train_rem``
  cleared, ``in_flight`` decremented, no push, no version bump);
  ``"resume"`` — the user stays in TRAIN but paused (``train_rem``
  frozen while down) and pays ``resume_penalty`` extra training seconds,
  so the eventual push lands with extra lag;
- a COOLING user that goes down parks in OFF;
- an OFF user that comes back up re-enters the arrival process: mode COOL
  with ``cooldown = ready_delay + net_extra`` (the time-varying network
  state feeding the lag model — a bad-network user re-arrives late, so
  its next pull is staler), then cooldown -> waiting counts as a queue
  arrival exactly like a normal re-arrival;
- down users draw no power (the device is off) and a paused trainer makes
  no training progress; app arrivals stay exogenous (the pre-sampled
  usage trace keeps its meaning and no rng stream shifts).

Every run counts the churn it had (``DeviceDynamics.counters``, copied
onto ``SimResult``): ``drops`` (mid-training down-edges), ``departures``
(waiting users that left the queue), ``recoveries`` (up-edges) and
``lost`` (trainings lost: the drops under ``"lose"``, 0 under
``"resume"``). The transition counts departures and recoveries from the
slot's pre-effect modes, so every engine reports the same numbers; the
jax scan keeps them as 0-d leaves of its carry.

``none`` (the default) is INACTIVE: no state, no draws, no effect — runs
are bit-identical to the pre-dynamics engines (the goldens pin this).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple, Type

import numpy as np

from .engine_state import MODE_TRAIN, MODE_WAIT

__all__ = ["DeviceDynamics", "DynEffects", "NoDynamics",
           "MarkovChurnDynamics", "register_dynamics",
           "registered_dynamics", "resolve_dynamics", "dynamics_support"]

DROPOUT_RULES = ("lose", "resume")
# the churn every engine reports (SimResult fields of these names): the
# per-user drop counts summed, the 0-d run totals of the dyn state, and
# the trainings lost (the drops under "lose")
RUN_TOTALS = ("departures", "recoveries")
COUNTERS = ("drops",) + RUN_TOTALS + ("lost",)
# the battery's levels and rates, in MarkovChurnDynamics._battery_units order
BATTERY_KNOBS = ("battery_capacity", "drain_train", "drain_corun",
                 "charge_rate", "battery_min", "battery_resume")


@dataclasses.dataclass
class DynEffects:
    """One slot's transition outcome, host or traced arrays alike.

    ``up`` is the post-transition effective availability (Markov state AND
    battery above threshold) — engines gate energy and training progress
    on it; ``went_down``/``went_up`` the edge masks; ``net_extra`` the
    per-user extra re-arrival delay (slots) of the current network state,
    read both at recovery and at push-finish time; ``resume_penalty`` the
    extra training seconds a dropped-and-resumed user pays (scalar)."""

    up: Any
    went_down: Any
    went_up: Any
    net_extra: Any
    resume_penalty: Any


class DeviceDynamics:
    """Base device-dynamics model. Subclass, set ``name``, implement the
    paths, and decorate with ``@register_dynamics``.

    Class attributes engines dispatch on:

    - ``active``: False means the engines skip the dynamics phase
      entirely (no state, no rng draws — bit-identical to the historical
      engines). Only ``NoDynamics`` should clear it.
    - ``supports_jax``: a traced ``scan_step`` exists. ``SimConfig``
      validates the flag against the actual hook at construction; active
      dynamics without it degrade the jax engine to the numpy path.
    - ``supports_shard``: ``scan_step`` may run with the user axis
      sharded over a device mesh (``SimConfig.n_devices``). The engine's
      slot view then carries ``dv.n`` (LIVE user count), ``dv.n_arr``
      (padded array length) and ``dv.pad_users(x, fill)`` — per-user
      draws must happen at ``dv.n`` and pad with a fill that keeps pad
      lanes inert (threefry draws are shape-dependent; drawing at
      ``n_arr`` would fork the stream from the unsharded engines).

    ``dropout`` is the instance's ``DropoutRule`` — ``"lose"`` or
    ``"resume"`` — a STATIC behavioral branch (engines compile/apply it
    structurally), so it must be part of ``jax_cache_key()``.
    """

    name: str = ""
    active: bool = True
    supports_jax: bool = True
    supports_shard: bool = True
    dropout: str = "lose"

    # ------------------------------------------------------------- state
    def init_state(self, n: int, cfg=None, fleet=None):
        """Per-run per-user state as ONE pytree of ``(n,)``-leading
        arrays and 0-d run totals (``EngineState.dyn``); ``None`` for
        inactive dynamics. Per-device-class parameters must be gathered
        per user HERE (the scan reads only this carry plus
        ``scan_operands`` scalars)."""
        return None

    def scan_operands(self, cfg) -> tuple:
        """Scalar instance knobs the traced hook needs (traced operands
        — ``dv.consts`` — so knob sweeps share one compiled scan)."""
        return ()

    def pad_state(self, k: int):
        """``(k,)``-leading INERT rows matching ``init_state``'s pytree
        structure, appended when the sharded scan pads the user axis to a
        multiple of the mesh size (``SimConfig.n_devices`` with a
        non-divisible ``n_users``; core/vector_engine.py). Inert means:
        the rows must keep their users permanently up under the engine's
        fill-1.0 padded draws — no ``went_up``/``went_down`` edges ever,
        so a pad user parked in MODE_OFF stays there. The base returns
        None ("no recipe"), which makes a padded sharded run fail fast
        with instructions; see ``MarkovChurnDynamics.pad_state``."""
        return None

    def jax_cache_key(self):
        """Hashable token identifying this dynamics' ``scan_step`` AND
        effect semantics (the ``dropout`` rule is applied structurally by
        the engines, so it is always part of the key). Class-keyed when
        provably safe — no ad-hoc instance attrs, or knobs routed
        through ``scan_operands`` — else instance-keyed (same contract
        as ``Policy.jax_cache_key``)."""
        if not vars(self) or \
                type(self).scan_operands is not DeviceDynamics.scan_operands:
            return (type(self), self.dropout)
        return self

    # --------------------------------------------------------- host path
    def host_step(self, dyn, rng_key, mode, corun, t_d
                  ) -> Tuple[Any, Any, DynEffects]:
        """One slot's transition on host numpy — shared verbatim by the
        loop oracle and the numpy engine. Must consume the rng
        unconditionally (or not at all) so the key chain is
        engine-invariant."""
        raise NotImplementedError(
            f"dynamics {self.name!r} implements no host_step()")

    # ------------------------------------------------------- traced path
    def scan_step(self, dyn, dv):
        """Traced transition inside the jax scan step; read/write
        ``dv.rng_key``, return ``(dyn, DynEffects)``. Only called when
        ``supports_jax``."""
        raise TypeError(
            f"dynamics {self.name!r} sets supports_jax but inherits the "
            "base scan_step; implement the hook or clear the flag to "
            "degrade to the numpy engines")

    # -------------------------------------------------------- accessors
    def counters(self, dyn) -> Dict[str, int]:
        """Run totals of the churn recorded in ``dyn``: ``drops``,
        ``departures``, ``recoveries``, ``lost`` (all 0 when
        untracked)."""
        return dict.fromkeys(COUNTERS, 0)


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------
_REGISTRY: Dict[str, Type[DeviceDynamics]] = {}
_INSTANCES: Dict[str, DeviceDynamics] = {}      # singletons for strings


def register_dynamics(cls: Type[DeviceDynamics]) -> Type[DeviceDynamics]:
    """Class decorator: make ``cls`` resolvable as ``cls.name``."""
    if not cls.name:
        raise ValueError(f"{cls.__name__} must set a registry name")
    _REGISTRY[cls.name] = cls
    _INSTANCES.pop(cls.name, None)              # re-registration wins
    return cls


def registered_dynamics() -> Tuple[str, ...]:
    return tuple(_REGISTRY)


def resolve_dynamics(dyn) -> DeviceDynamics:
    """String -> registered singleton; DeviceDynamics instance -> itself."""
    if isinstance(dyn, DeviceDynamics):
        return dyn
    if isinstance(dyn, str):
        if dyn not in _REGISTRY:
            raise ValueError(
                f"unknown dynamics {dyn!r}; expected one of "
                f"{registered_dynamics()} or a DeviceDynamics instance")
        if dyn not in _INSTANCES:
            _INSTANCES[dyn] = _REGISTRY[dyn]()
        return _INSTANCES[dyn]
    raise ValueError(f"dynamics must be a name or DeviceDynamics instance, "
                     f"got {type(dyn).__name__}")


def dynamics_support(dyn: DeviceDynamics) -> Dict[str, bool]:
    """Which paths ``dyn`` GENUINELY implements (flag set AND the base
    stub overridden) — the SimConfig-validation twin of
    ``policies.engine_support``. Inactive dynamics support everything
    (there is nothing to run)."""
    cls = type(dyn)
    if not dyn.active:
        return {"host": True, "jax": True}
    return {
        "host": cls.host_step is not DeviceDynamics.host_step,
        "jax": (dyn.supports_jax and
                cls.scan_step is not DeviceDynamics.scan_step),
    }


# ---------------------------------------------------------------------------
# Shipped dynamics
# ---------------------------------------------------------------------------
@register_dynamics
class NoDynamics(DeviceDynamics):
    """The paper's always-on fleet (the default). Inactive: engines skip
    the dynamics phase entirely, so runs are bit-identical to the
    pre-dynamics engines — no per-user state, no rng draws."""

    name = "none"
    active = False


def _dyn_draw(rng_key, n):
    """One slot's dynamics uniforms on the host: split the run key, draw
    ``(2, n)`` f32 — row 0 drives the availability chain, row 1 the
    network chain. threefry is counter-based and jit-invariant, so the
    traced twin inside ``scan_step`` produces the same bits (the
    ``eps_greedy`` trick that makes the three engines decision-
    identical)."""
    import jax
    import jax.numpy as jnp

    k2, sub = jax.random.split(jnp.asarray(rng_key))
    u = jax.random.uniform(sub, (2, n), jnp.float32)
    return np.asarray(k2, dtype=np.uint32), np.asarray(u)


def _whole(x: float) -> bool:
    return abs(x - round(x)) <= 1e-9 * max(1.0, abs(x))


def _per_user(value, n, fleet, what, per_user=False) -> np.ndarray:
    """Broadcast a scalar to ``(n,)`` or gather a per-device-class
    vector (one entry per catalog row of the run's ``FleetSpec``) per
    user — the ``hetero_aware`` carry pattern. With ``per_user`` an
    ``(n,)`` array is taken as one value per user; where ``n`` equals
    the fleet's class count the two readings cannot be told apart and
    the vector is refused."""
    v = np.asarray(value, dtype=np.float64)
    if v.ndim == 0:
        return np.full(n, float(v))
    n_classes = None if fleet is None else len(fleet.tables.t_train)
    if per_user and v.shape == (n,):
        if n == n_classes:
            raise ValueError(
                f"{what} of shape ({n},) is ambiguous: this fleet has "
                f"{n} users and {n} device classes")
        return v.copy()
    if fleet is None:
        raise ValueError(
            f"per-device-class {what} needs the run's FleetSpec to "
            "gather per-user values; engines pass it automatically")
    if v.shape != (n_classes,):
        also = f" or a ({n},) per-user array" if per_user else ""
        raise ValueError(
            f"{what} must be a scalar or a ({n_classes},) per-device-"
            f"class vector{also} for this fleet, got shape {v.shape}")
    return v[fleet.device_ids]


@register_dynamics
class MarkovChurnDynamics(DeviceDynamics):
    """Markov availability + battery trajectories + 2-state network churn.

    Three coupled per-user state machines, stepped once per slot:

    - **Availability**: a 2-state Markov chain (FLGo-style per-client
      availability). ``p_off``/``p_on`` are per-slot transition
      probabilities — scalars, or per-device-class vectors (one entry
      per catalog row of the run's ``FleetSpec``, gathered per user at
      init like ``hetero_aware``'s scales).
    - **Battery**: drains while actually training (``drain_train``
      capacity-fractions/s; ``drain_corun`` while co-running — co-run
      training works the SoC harder) and charges otherwise
      (``charge_rate``), clipped to ``[0, capacity]``. The battery gates
      participation (DEAL-style): a user whose level falls to
      ``battery_min`` or below is held down, and is let up again only
      once its level is above ``battery_resume`` (default
      ``battery_min``: no hysteresis; a higher resume level keeps a user
      at the threshold from going up and down every few slots). The gate
      is part of effective availability, so a mid-training battery
      collapse IS a dropout. ``battery_init`` is the starting charge as
      a capacity fraction in [0, 1]: a scalar for the whole fleet, a
      per-device-class vector, or a per-user ``(n,)`` array (a fleet
      that starts at a spread of levels). With ``battery_quantum`` the
      level is kept as a whole count of quanta (capacity units per
      quantum): capacity, thresholds and every per-slot step must be
      whole quanta, starting levels round to the nearest quantum, and
      ``dyn["battery"]`` holds the count. Every level is then exact in
      float32 as in float64, so a threshold crossing lands in the same
      slot at either precision.
    - **Network**: a good/bad 2-state chain (``p_net_bad`` /
      ``p_net_recover``); in the bad state re-arrival — post-push AND
      post-recovery — costs ``net_delay_slots`` extra cooldown slots,
      feeding the lag model (late re-arrival => staler next pull).

    ``dropout`` picks the mid-training rule: ``"lose"`` (in-flight work
    lost) or ``"resume"`` (paused while down, ``resume_penalty_s`` extra
    training seconds). The state counts, per user, ``drops``
    (mid-training down-edges, either rule) and, as 0-d run totals,
    ``departures`` and ``recoveries`` (``counters``).
    """

    name = "markov"

    def __init__(self, p_off=0.002, p_on=0.05, *,
                 battery_capacity: float = 1.0,
                 battery_init=1.0,
                 drain_train: float = 2e-4, drain_corun: float = 3e-4,
                 charge_rate: float = 1e-4, battery_min: float = 0.0,
                 battery_resume=None, battery_quantum=None,
                 p_net_bad: float = 0.0, p_net_recover: float = 0.1,
                 net_delay_slots: int = 20,
                 dropout: str = "lose", resume_penalty_s: float = 0.0):
        for what, v in (("p_net_bad", p_net_bad),
                        ("p_net_recover", p_net_recover)):
            if not 0.0 <= float(v) <= 1.0:
                raise ValueError(f"{what} must be in [0, 1], got {v}")
        for what, v in (("p_off", p_off), ("p_on", p_on)):
            a = np.asarray(v, dtype=float)
            if a.size == 0 or not np.all((a >= 0.0) & (a <= 1.0)):
                raise ValueError(f"{what} must be in [0, 1], got {v}")
        if battery_capacity <= 0.0:
            raise ValueError(
                f"battery_capacity must be positive, got {battery_capacity}")
        init = np.asarray(battery_init, dtype=float)
        if init.ndim > 1 or init.size == 0 or \
                not np.all((init >= 0.0) & (init <= 1.0)):
            raise ValueError(
                "battery_init is a capacity fraction in [0, 1]: a scalar, "
                "a per-device-class vector or a per-user (n,) array, got "
                f"{battery_init if init.ndim == 0 else init.shape}")
        if not 0.0 <= battery_min < battery_capacity:
            raise ValueError(
                f"battery_min must be in [0, capacity), got {battery_min}")
        resume = battery_min if battery_resume is None \
            else float(battery_resume)
        if not battery_min <= resume < battery_capacity:
            raise ValueError(
                "battery_resume must be in [battery_min, capacity), got "
                f"{battery_resume}")
        if min(drain_train, drain_corun, charge_rate) < 0.0:
            raise ValueError("drain/charge rates must be non-negative")
        if net_delay_slots < 0:
            raise ValueError(
                f"net_delay_slots must be >= 0, got {net_delay_slots}")
        if dropout not in DROPOUT_RULES:
            raise ValueError(f"unknown dropout rule {dropout!r}; expected "
                             f"one of {DROPOUT_RULES}")
        if resume_penalty_s < 0.0:
            raise ValueError(
                f"resume_penalty_s must be >= 0, got {resume_penalty_s}")
        self.p_off = p_off
        self.p_on = p_on
        self.capacity = float(battery_capacity)
        self.battery_init = float(init) if init.ndim == 0 else init.copy()
        self.drain_train = float(drain_train)
        self.drain_corun = float(drain_corun)
        self.charge_rate = float(charge_rate)
        self.battery_min = float(battery_min)
        self.battery_resume = resume
        self.quantum = None
        if battery_quantum is not None:
            self.quantum = float(battery_quantum)
            if not self.quantum > 0.0:
                raise ValueError(
                    f"battery_quantum must be positive, got {battery_quantum}")
            for what, v in zip(BATTERY_KNOBS,
                               self._battery_units(quantized=False)):
                if not _whole(v / self.quantum):
                    raise ValueError(
                        f"{what} must be a whole number of battery_quantum "
                        f"({self.quantum}), got {v}")
            if self.capacity / self.quantum > 2 ** 24:
                raise ValueError(
                    "battery_capacity must be at most 2**24 quanta, the "
                    "whole numbers float32 holds exactly")
        self.p_net_bad = float(p_net_bad)
        self.p_net_recover = float(p_net_recover)
        self.net_delay_slots = int(net_delay_slots)
        self.dropout = dropout
        self.resume_penalty_s = float(resume_penalty_s)

    def _battery_units(self, quantized=True):
        """``capacity, drain_train, drain_corun, charge_rate,
        battery_min, battery_resume`` in the unit the state's levels are
        kept in: capacity units, or whole quanta with ``battery_quantum``
        (unless not ``quantized``)."""
        levels = (self.capacity, self.drain_train, self.drain_corun,
                  self.charge_rate, self.battery_min, self.battery_resume)
        if not quantized or self.quantum is None:
            return levels
        return tuple(float(round(v / self.quantum)) for v in levels)

    # ------------------------------------------------------------- state
    def init_state(self, n, cfg=None, fleet=None):
        level = _per_user(self.battery_init, n, fleet, "battery_init",
                          per_user=True) * self.capacity
        if self.quantum is not None:
            if cfg is not None:
                self._check_steps(cfg.t_d)
            level = np.rint(level / self.quantum)
        return {
            "on": np.ones(n, dtype=bool),
            "up": np.ones(n, dtype=bool),
            "battery": level,
            "low": np.zeros(n, dtype=bool),
            "net_bad": np.zeros(n, dtype=bool),
            "drops": np.zeros(n, dtype=np.int64),
            # run-constant per-user parameter gathers (traced carry)
            "p_off": _per_user(self.p_off, n, fleet, "p_off"),
            "p_on": _per_user(self.p_on, n, fleet, "p_on"),
            # run totals (0-d: replicated, never padded)
            **{k: np.zeros((), np.int32) for k in RUN_TOTALS},
        }

    def _check_steps(self, t_d):
        """With a quantum, each per-slot battery step (rate x ``t_d``, the
        product the transition forms) must be a whole number of quanta
        in float32 and in float64 alike."""
        for what, rate in zip(BATTERY_KNOBS[1:4], self._battery_units()[1:4]):
            for f in (np.float32, np.float64):
                step = f(rate) * f(t_d)
                if step != np.rint(step) or float(step) != rate * t_d:
                    raise ValueError(
                        f"{what} x t_d ({t_d}) must be a whole number of "
                        f"battery_quantum ({self.quantum}) in float32 and "
                        f"float64, got {rate * t_d} quanta")

    def scan_operands(self, cfg):
        return (*self._battery_units(), self.p_net_bad, self.p_net_recover,
                self.net_delay_slots, self.resume_penalty_s)

    def pad_state(self, k):
        # permanently-up rows: full battery (> battery_resume, validated),
        # p_off=0 keeps the availability chain on under the engine's
        # fill-1.0 padded draws (1.0 >= 0), the net chain never turns bad
        # (1.0 < p_net_bad is false), and `up` never edges — so pad users
        # parked in MODE_OFF draw nothing and stay parked forever. The
        # 0-d counters are not per-user: padding keeps the state's own.
        return {
            "on": np.ones(k, dtype=bool),
            "up": np.ones(k, dtype=bool),
            "battery": np.full(k, self._battery_units()[0]),
            "low": np.zeros(k, dtype=bool),
            "net_bad": np.zeros(k, dtype=bool),
            "drops": np.zeros(k, dtype=np.int64),
            "p_off": np.zeros(k),
            "p_on": np.zeros(k),
            **{c: np.zeros((), np.int32) for c in RUN_TOTALS},
        }

    def counters(self, dyn) -> Dict[str, int]:
        if dyn is None:
            return super().counters(dyn)
        drops = int(np.asarray(dyn["drops"]).sum())
        return {"drops": drops, **{c: int(dyn[c]) for c in RUN_TOTALS},
                "lost": drops if self.dropout == "lose" else 0}

    # ----------------------------------------------------------- the step
    # host_step and _transition/scan_step MUST stay formula-identical:
    # the fault-injection parity suite (tests/test_dynamics_faults.py)
    # pins loop/vectorized/jax push-log digests under x64.
    def host_step(self, dyn, rng_key, mode, corun, t_d):
        rng_key, u = _dyn_draw(rng_key, len(dyn["battery"]))
        dyn, eff = self._transition(
            np, dyn, u[0], u[1], mode, corun, t_d,
            *self.scan_operands(None))
        return dyn, rng_key, eff

    def scan_step(self, dyn, dv):
        jax, jnp = dv.jax, dv.jnp
        k2, sub = jax.random.split(dv.rng_key)
        u = jax.random.uniform(sub, (2, dv.n), jnp.float32)
        # live-n draw + fill-1.0 pad (identity unsharded): the threefry
        # stream matches the host engines, and 1.0 keeps pad lanes' chains
        # pinned on/never-bad (see pad_state)
        u = dv.pad_users(u, 1.0)
        dv.rng_key = k2
        return self._transition(
            jnp, dyn, u[0], u[1], dv.mode, dv.corun, dv.t_d, *dv.consts,
            zero=dv.fp_zero)

    @staticmethod
    def _transition(xp, dyn, u_avail, u_net, mode, corun, t_d,
                    capacity, drain_train, drain_corun, charge_rate,
                    battery_min, battery_resume, p_net_bad, p_net_recover,
                    net_delay_slots, resume_penalty_s, zero=0.0):
        """One slot, numpy or jnp (``xp``): elementwise only, identical
        operation order on both — bitwise parity under x64. ``zero`` is
        a traced 0.0 on the jax path: it forces the delta*t_d product to
        round before the battery add, which XLA's fma contraction would
        otherwise skip (see policies._jax_trace_v_norm). ``mode`` is the
        slot's mode before the engines apply the effects, which the run
        counters read."""
        up_prev = dyn["up"]
        training = mode == MODE_TRAIN
        # battery: drain while ACTUALLY training (a paused trainer is
        # off, not burning), charge otherwise — off devices are assumed
        # plugged/idle-charging
        active_train = training & up_prev
        drain = xp.where(corun & active_train, drain_corun, drain_train)
        battery = xp.clip(
            dyn["battery"]
            + (xp.where(active_train, -drain, charge_rate) * t_d + zero),
            0.0, capacity)
        # Markov chains: availability (per-user probabilities from the
        # carry) and network (scalar knobs)
        on = xp.where(dyn["on"], u_avail >= dyn["p_off"],
                      u_avail < dyn["p_on"])
        net_bad = xp.where(dyn["net_bad"], u_net >= p_net_recover,
                           u_net < p_net_bad)
        # the battery gate: held down at or below battery_min, let up
        # again above battery_resume
        low = xp.where(dyn["low"], battery <= battery_resume,
                       battery <= battery_min)
        # effective availability: chain on AND battery not held down
        up = on & ~low
        went_down = up_prev & ~up
        went_up = ~up_prev & up
        drops = dyn["drops"] + (went_down & training)
        net_extra = xp.where(net_bad, net_delay_slots, 0)
        dyn2 = {"on": on, "up": up, "battery": battery, "low": low,
                "net_bad": net_bad, "drops": drops, "p_off": dyn["p_off"],
                "p_on": dyn["p_on"],
                "departures": dyn["departures"]
                + xp.sum(went_down & (mode == MODE_WAIT)),
                "recoveries": dyn["recoveries"] + xp.sum(went_up)}
        return dyn2, DynEffects(up=up, went_down=went_down,
                                went_up=went_up, net_extra=net_extra,
                                resume_penalty=resume_penalty_s)
