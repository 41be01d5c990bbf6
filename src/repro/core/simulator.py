"""Slotted-time federated simulator (Sec. VII.B methodology).

Replays the paper's evaluation: n users each owning a testbed device
(Table II catalog), Bernoulli app arrivals per slot, four scheduling
policies — "sync" (FedAvg lock-step), "immediate" (ASync, schedule ASAP),
"offline" (knapsack with look-ahead window), "online" (Lyapunov) — with
per-slot energy accounting per Eq. (10) and queue dynamics per Eqs. (15-16).

Policies, arrival processes, and device fleets are composable objects with
registries (core/policies.py, core/arrivals.py, core/fleet.py); the paper's
setup is just the default composition. ``SimConfig.policy`` accepts either
a registry name or a ``Policy`` instance; ``FederatedSim`` additionally
takes ``arrivals=``/``fleet=`` objects. See core/scenario.py for the
experiment-facing ``Scenario``/``run_experiment`` entrypoint.

ml_mode="trace" tracks updates/staleness without real gradients (fast —
Fig. 4/6 energy results); ml_mode="real" couples the schedule to actual JAX
training of the paper's LeNet-5 (Fig. 5 convergence results).

Engines (SimConfig.engine): this class's per-user object loop is the
reference oracle ("loop"); "vectorized" runs the same semantics on
struct-of-arrays batched state (core/vector_engine.py), "jax" compiles the
horizon into chunked lax.scans, and "auto" (default) picks the vectorized
engine for pure trace-mode runs AND for real-mode runs driven by a
batched ml_backend (core/realml.py — vmap'd cohort training). All three
engines thread ONE state container — ``core.engine_state.EngineState``
(``sim.state``): per-user struct-of-arrays, scheduler scalars, RNG key and
the policy's carry pytree — and stream push events through
``core.engine_state.PushLog``. Seeded equivalence across engines is pinned
by tests/test_sim_engines.py, tests/test_engine_matrix.py and
tests/test_real_mode.py.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Union

import numpy as np
from jax.profiler import TraceAnnotation

from ..kernels.fused_update import KERNEL_MODES
from .aggregation import (AggregationRule, aggregation_support,
                          resolve_aggregation)
from .arrivals import ArrivalProcess, resolve_arrival_or_default
from .dynamics import (DROPOUT_RULES, DeviceDynamics, dynamics_support,
                       resolve_dynamics)
from .energy import APPS, DeviceProfile
from .engine_state import (MODE_COOL, MODE_OFF, MODE_TRAIN, MODE_WAIT,
                           EngineState, PushLog)
from .fleet import Fleet, resolve_fleet
from .lyapunov import OnlineScheduler
from .policies import Policy, engine_support, resolve_policy
from .staleness import gradient_gap


# The paper's four schedulers (Sec. VII.B). The full registry — these plus
# any registered extras — is policies.registered_policies().
POLICIES = ("sync", "immediate", "offline", "online")
ENGINES = ("auto", "loop", "vectorized", "jax")


@dataclasses.dataclass
class SimConfig:
    n_users: int = 25
    horizon_s: int = 10800          # paper: 3 hours
    t_d: float = 1.0                # slot length (s)
    # scalar = the paper's i.i.d. rate; an (n_users,) vector gives every
    # user its own Bernoulli rate (heterogeneous fleets)
    app_arrival_p: Any = 0.001      # paper: ~1 app per 1000 s
    policy: Union[str, Policy] = "online"   # registry name or Policy object
    V: float = 4000.0
    L_b: float = 1000.0
    epsilon: float = 0.05
    eta: float = 0.01
    beta: float = 0.9
    offline_window: float = 500.0   # paper: 500 s look-ahead
    offline_resolution: float = 0.01
    seed: int = 0
    ml_mode: str = "trace"          # trace | real
    # how the server APPLIES pushes (core/aggregation.py): registry name
    # or AggregationRule instance; "replace" is the paper's Sec. VI rule.
    # Every engine logs the applied weight per push (push_log "weight"
    # column); in real mode the weight actually mixes the global model.
    aggregation: Union[str, AggregationRule] = "replace"
    # how the apply is COMPUTED (kernels/fused_update): "pallas" fuses
    # mix + momentum + Eq. 4 norm into one HBM pass, "reference" keeps
    # the multi-dispatch jnp path (bit-stable with the goldens), "auto"
    # picks Pallas on TPU and reference elsewhere. Only real-ML mode
    # touches parameter pytrees, so the knob is a no-op in trace mode.
    kernel: str = "auto"
    ready_delay: int = 5            # slots between push and re-arrival
    trace_every: int = 30           # slots between trace samples
    include_scheduler_overhead: bool = False
    v_norm0: float = 1.0            # trace-mode momentum-norm model scale
    engine: str = "auto"            # auto | loop | vectorized | jax
    collect_push_log: bool = True   # push events; streamed on every engine
    jax_chunk: int = 1024           # slots per compiled scan chunk (jax);
    #                                 0 = auto-tune from per-device memory
    #                                 (core/autotune.py)
    push_log_capacity: int = 0      # initial per-chunk event buffer slots
    #                                 for the jax engine (0 = auto-sized;
    #                                 doubled + chunk retried on overflow)
    # Shard the user axis (jax engine): partition every per-user
    # EngineState leaf over a 1-D ("users",) mesh of
    # min(n_devices, available) devices (launch/mesh.py make_sim_mesh),
    # scheduler scalars replicated — Alg. 2 decisions stay bit-identical
    # to the single-device scan (core/vector_engine.py). 0 = unsharded.
    n_devices: int = 0
    # Device dynamics (core/dynamics.py): availability / battery / network
    # churn as per-user state machines. Registry name or DeviceDynamics
    # instance; "none" (the paper's always-on fleet) is bit-identical to
    # the pre-dynamics engines. MarkovChurnDynamics takes battery_init as
    # a scalar, a per-device-class vector or a per-user (n,) array of
    # capacity fractions; every engine counts the run's churn (drops,
    # departures, recoveries, lost) onto SimResult.
    dynamics: Union[str, DeviceDynamics] = "none"

    def __post_init__(self):
        # Fail at construction, not mid-run (a bad policy string used to
        # surface only once the first slot hit the decision branch).
        pol = resolve_policy(self.policy)   # raises ValueError on unknowns
        if self.engine not in ENGINES:
            raise ValueError(f"unknown engine {self.engine!r}; "
                             f"expected one of {ENGINES}")
        # Engine-capability validation: a policy whose support flags claim
        # an engine must actually implement its carry-protocol hook — a
        # flag/hook mismatch is a property of the policy, so it is
        # rejected for EVERY engine (auto included: auto dispatches on the
        # flags). An explicitly requested vectorized engine additionally
        # needs the vectorized hook. Catching this here replaces the
        # historical NotImplementedError raised mid-run from the
        # base-class hook stubs.
        sup = engine_support(pol)
        if pol.supports_vectorized and not sup["vectorized"]:
            raise ValueError(
                f"policy {pol.name!r} sets supports_vectorized but "
                "implements no decide_vectorized hook; implement "
                "decide_vectorized(eng, t, carry) or clear the flag")
        if pol.supports_jax and not sup["jax"]:
            raise ValueError(
                f"policy {pol.name!r} sets supports_jax but implements no "
                "scan_step carry hook; implement scan_step(carry, sv) or "
                "clear the flag to degrade to the vectorized engine")
        if self.engine == "vectorized" and not sup["vectorized"]:
            raise ValueError(
                f"policy {pol.name!r} implements no vectorized "
                "(decide_vectorized) hook; use engine='loop' (or 'auto', "
                "which falls back to the loop oracle)")
        if self.ml_mode not in ("trace", "real"):
            raise ValueError(f"unknown ml_mode {self.ml_mode!r}")
        if self.kernel not in KERNEL_MODES:
            raise ValueError(f"unknown kernel {self.kernel!r}; "
                             f"expected one of {KERNEL_MODES}")
        # Aggregation-rule validation mirrors the policy validation: the
        # name must resolve, and a rule whose supports_jax flag claims a
        # traced path must actually implement scan_weight (rules without
        # one degrade the jax engine to the numpy path, see
        # resolve_engine).
        agg = resolve_aggregation(self.aggregation)  # raises on unknowns
        asup = aggregation_support(agg)
        if not asup["host"]:
            raise ValueError(
                f"aggregation rule {agg.name!r} implements no weight() "
                "host path; every rule needs one (the loop oracle and "
                "the numpy engine run on it)")
        if agg.supports_jax and not asup["jax"]:
            raise ValueError(
                f"aggregation rule {agg.name!r} sets supports_jax but "
                "implements no scan_weight hook; implement "
                "scan_weight(carry, pv) or clear the flag to degrade to "
                "the numpy engines")
        # Dynamics validation, same shape: the name must resolve, an
        # active dynamics needs the shared host transition (the loop
        # oracle and the numpy engine both run on it), a supports_jax
        # flag without the traced hook is a lie, and the dropout rule
        # must be one the engines know how to apply structurally.
        dyn = resolve_dynamics(self.dynamics)    # raises on unknowns
        dsup = dynamics_support(dyn)
        if not dsup["host"]:
            raise ValueError(
                f"dynamics {dyn.name!r} implements no host_step() path; "
                "every active dynamics needs one (the loop oracle and "
                "the numpy engine run on it)")
        if dyn.active and dyn.supports_jax and not dsup["jax"]:
            raise ValueError(
                f"dynamics {dyn.name!r} sets supports_jax but implements "
                "no scan_step hook; implement scan_step(dyn, dv) or "
                "clear the flag to degrade to the numpy engines")
        if dyn.active and dyn.dropout not in DROPOUT_RULES:
            raise ValueError(
                f"dynamics {dyn.name!r} has unknown dropout rule "
                f"{dyn.dropout!r}; engines apply one of {DROPOUT_RULES}")
        if self.n_users <= 0:
            raise ValueError(f"n_users must be positive, got {self.n_users}")
        if self.t_d <= 0:
            raise ValueError(f"t_d must be positive, got {self.t_d}")
        if self.horizon_s <= 0:
            raise ValueError(
                f"horizon_s must be positive, got {self.horizon_s}")
        p = np.asarray(self.app_arrival_p, dtype=float)
        if p.ndim > 1:
            raise ValueError(
                f"app_arrival_p must be a scalar or an (n_users,) vector, "
                f"got shape {p.shape}")
        if p.ndim == 1 and p.shape[0] != self.n_users:
            raise ValueError(
                f"app_arrival_p vector has {p.shape[0]} entries for "
                f"n_users={self.n_users}")
        if p.size and not np.all((p >= 0.0) & (p <= 1.0)):
            # the conjunctive form also rejects NaN entries
            raise ValueError(
                f"app_arrival_p must be in [0, 1], got {self.app_arrival_p}")
        if p.ndim == 1:
            # normalize rate vectors to a plain tuple: keeps the
            # dataclass-generated __eq__/repr working (an ndarray field
            # would make config comparison raise) and the value hashable
            self.app_arrival_p = tuple(float(x) for x in p)
        if not 0.0 <= self.beta < 1.0:
            raise ValueError(f"beta must be in [0, 1), got {self.beta}")
        if self.V < 0 or self.L_b < 0 or self.epsilon < 0:
            raise ValueError("V, L_b and epsilon must be non-negative")
        if self.eta < 0 or self.v_norm0 < 0:
            # negative eta/v_norm would invert Eq. 4's gap monotonicity,
            # which the batched online argmin relies on
            raise ValueError("eta and v_norm0 must be non-negative")
        if self.offline_window <= 0 or self.offline_resolution <= 0:
            raise ValueError(
                "offline_window and offline_resolution must be positive")
        if self.ready_delay < 0:
            raise ValueError(
                f"ready_delay must be non-negative, got {self.ready_delay}")
        if self.trace_every <= 0:
            raise ValueError(
                f"trace_every must be positive, got {self.trace_every}")
        if self.jax_chunk < 0:
            raise ValueError(
                f"jax_chunk must be positive (or 0 = auto-tune from "
                f"device memory), got {self.jax_chunk}")
        if self.push_log_capacity < 0:
            raise ValueError(
                f"push_log_capacity must be non-negative, got "
                f"{self.push_log_capacity}")
        if self.n_devices < 0:
            raise ValueError(
                f"n_devices must be >= 0 (0 = unsharded), got "
                f"{self.n_devices}")
        if self.n_devices:
            # The sharded scan only exists on the jax engine and has no
            # silent degrade path (falling back to one device would make
            # the knob lie about what ran) — reject ineligible configs
            # here with the reason, not mid-run.
            if self.engine in ("loop", "vectorized"):
                raise ValueError(
                    f"n_devices={self.n_devices} shards the jax chunked "
                    f"scan; it cannot run under engine={self.engine!r} — "
                    "use engine='jax' or 'auto'")
            for what, obj in (("policy", pol), ("dynamics", dyn)):
                if (what == "dynamics" and not dyn.active):
                    continue
                if not getattr(obj, "supports_jax", False):
                    raise ValueError(
                        f"n_devices={self.n_devices} needs a jax-capable "
                        f"{what}; {obj.name!r} has supports_jax=False")
                if not getattr(obj, "supports_shard", True):
                    raise ValueError(
                        f"{what} {obj.name!r} does not support the "
                        "sharded scan (supports_shard=False, e.g. host "
                        "callbacks inside the step); run with n_devices=0")
            if self.collect_push_log:
                if not asup["jax"]:
                    raise ValueError(
                        f"n_devices={self.n_devices} with a push log "
                        f"needs a jax-capable aggregation rule; "
                        f"{agg.name!r} implements no scan_weight hook")
                if not getattr(agg, "supports_shard", True):
                    raise ValueError(
                        f"aggregation rule {agg.name!r} does not support "
                        "the sharded scan (supports_shard=False); run "
                        "with n_devices=0")


@dataclasses.dataclass
class UserState:
    device: DeviceProfile
    mode: str = "cooldown"          # waiting | training | cooldown | off
    cooldown: int = 0
    app: Optional[str] = None
    app_remaining: float = 0.0
    train_remaining: float = 0.0
    corun: bool = False
    idle_gap: float = 0.0
    pulled_at: int = 0              # global version at pull
    started_at: int = 0
    energy_j: float = 0.0
    updates: int = 0
    plan: str = "none"              # offline policy: corun | separate | hold


@dataclasses.dataclass
class SimResult:
    energy_j: float
    updates: int
    trace_t: np.ndarray
    trace_energy: np.ndarray
    trace_Q: np.ndarray
    trace_H: np.ndarray
    push_log: Any                   # PushLog (list-of-dicts view): per push
    #                                 t, user, lag, gap, corun
    accuracy: List[tuple]           # (sim_t, test_acc) if ml_mode == real
    mean_Q: float
    mean_H: float
    corun_fraction: float
    # device churn (core/dynamics.py ``COUNTERS``; all 0 with
    # dynamics="none"): mid-training down-edges, waiting users that left
    # the queue, up-edges, and trainings lost under the "lose" rule
    drops: int = 0
    departures: int = 0
    recoveries: int = 0
    lost: int = 0
    placement: Optional[dict] = None  # jax scan: leaf name -> (device ids,
    #                                 shard shape) of the final carry


# UserState.mode string <-> shared engine code (engine_state constants);
# the loop oracle builds the dynamics layer's mode view through this map.
_MODE_CODE = {"waiting": MODE_WAIT, "training": MODE_TRAIN,
              "cooldown": MODE_COOL, "off": MODE_OFF}


def n_slots(cfg: SimConfig) -> int:
    """Slots in the horizon. round() before int: 48 s / 1.6 s is
    29.999999999999996 in floats and plain int() would drop a slot."""
    return int(round(cfg.horizon_s / cfg.t_d))


def trace_v_norm(v_norm0: float, version) -> float:
    """Trace-mode momentum-norm model: ||v|| decays with global progress.
    Shared by the loop oracle and the vectorized engines (version may be an
    array of per-finisher versions)."""
    return v_norm0 / np.sqrt(1.0 + 0.05 * version)


class FederatedSim:
    def __init__(self, cfg: SimConfig, ml_hooks: Optional[dict] = None, *,
                 ml_backend=None,
                 arrivals: Union[str, ArrivalProcess, None] = None,
                 fleet: Union[str, Fleet, None] = None):
        """ml_hooks (real mode): {"pull": fn()->params_version, "push":
        fn(uid, params)->PushResult, "local_train": fn(uid, params)->params,
        "evaluate": fn()->acc, "sync_submit", "sync_aggregate", "v_norm": fn()->float}

        ``ml_backend`` (real mode): a ``core.realml.BatchedMLBackend`` —
        the batched alternative to ``ml_hooks`` that the vectorized engine
        can drive cohort-at-a-time (the loop engine drives the same backend
        through its ``hooks()`` adapter). Pass one or the other, not both.

        ``arrivals``/``fleet`` plug in non-paper arrival processes and
        device fleets (core/arrivals.py, core/fleet.py); the defaults —
        Bernoulli(cfg.app_arrival_p) on the Table II round-robin fleet —
        consume the seeded rng stream draw-for-draw like the historical
        hard-coded setup, so existing seeded runs reproduce bit-for-bit.

        ``self.state`` is the run's ``EngineState`` — the one state pytree
        every engine threads. The loop oracle keeps its per-user
        ``UserState`` objects as the readable working view and routes the
        scalar fields (version, in_flight, round_open) plus the policy
        carry through the container; the batched engines consume it whole.
        """
        self.cfg = cfg
        self.policy = resolve_policy(cfg.policy)
        self.agg = resolve_aggregation(cfg.aggregation)
        self.dynamics = resolve_dynamics(cfg.dynamics)
        self.rng = np.random.default_rng(cfg.seed)
        self.ml_backend = ml_backend
        if ml_backend is not None:
            if ml_hooks is not None:
                raise ValueError(
                    "pass either ml_hooks or ml_backend, not both")
            if cfg.ml_mode != "real":
                raise ValueError(
                    "ml_backend requires ml_mode='real' (a backend couples "
                    "the schedule to actual training)")
            if getattr(ml_backend, "n_users", cfg.n_users) != cfg.n_users:
                raise ValueError(
                    f"ml_backend was built for {ml_backend.n_users} users; "
                    f"config has n_users={cfg.n_users}")
            self.ml = ml_backend.hooks()
        else:
            self.ml = ml_hooks or {}
        self.fleet = resolve_fleet(fleet if fleet is not None else "paper")
        self.fleet_spec = self.fleet.build(self.rng, cfg.n_users)
        self._users = None      # the loop engine's view, built on access
        # the jax engine's last dispatched chunk (vector_engine.ScanChunk)
        self.scan_chunk = None
        self.sched = OnlineScheduler(cfg.V, cfg.L_b, cfg.eta, cfg.beta,
                                     cfg.epsilon, cfg.t_d)
        self.state = EngineState.init(cfg.n_users, cfg, self.policy,
                                      agg=self.agg, fleet=self.fleet_spec,
                                      dynamics=self.dynamics)
        if ml_backend is not None:
            # fleet-conditioned aggregation (hetero_aware) needs the
            # run's FleetSpec; the backend forwards it to its server,
            # gathers the rule carry for the fused push scan, and keeps
            # the config for the rule's scan_operands
            ml_backend.bind_fleet(self.fleet_spec, cfg)
            brule = getattr(getattr(ml_backend, "server", None), "rule",
                            None)

            def _knobs(r):   # public instance attrs = the rule's knobs
                return {k: v for k, v in vars(r).items()
                        if not k.startswith("_")}

            def _same_knobs(a, b):
                # per-value np.array_equal: dict != would raise the
                # ambiguous-truth ValueError on array-valued knobs
                return a.keys() == b.keys() and \
                    all(np.array_equal(a[k], b[k]) for k in a)

            if brule is not None and brule is not self.agg and \
                    (brule.name != self.agg.name or
                     not _same_knobs(_knobs(brule), _knobs(self.agg))):
                # name AND knobs must match: same-class rules with
                # different alpha/a/gap_ref would silently attribute the
                # run to the wrong hyperparameters
                raise ValueError(
                    f"ml_backend was built with aggregation rule "
                    f"{brule.name!r} ({_knobs(brule) or 'no knobs'}) "
                    f"but the config says {self.agg.name!r} "
                    f"({_knobs(self.agg) or 'no knobs'}); in real mode "
                    "the backend's server applies the pushes, so the "
                    "two must agree (Scenario threads cfg.aggregation "
                    "automatically)")
        # Pre-sample the app arrival schedule (offline policy needs
        # lookahead), one row per SLOT — t_d < 1 means more slots than
        # seconds. (For t_d == 1 this matches the historical horizon_s
        # sizing draw-for-draw, keeping seeded runs reproducible.)
        self.arrivals: ArrivalProcess = resolve_arrival_or_default(
            arrivals, cfg.app_arrival_p)
        T = n_slots(cfg)
        self.app_sched, self.app_choice = self.arrivals.sample(
            self.rng, T, cfg.n_users, len(APPS), cfg.t_d)
        self.app_sched = np.asarray(self.app_sched, dtype=bool)
        self.app_choice = np.asarray(self.app_choice, dtype=np.int64)
        if self.app_sched.shape != (T, cfg.n_users) or \
                self.app_choice.shape != (T, cfg.n_users):
            raise ValueError(
                f"arrival process {self.arrivals.name!r} produced shapes "
                f"{self.app_sched.shape}/{self.app_choice.shape}; "
                f"expected {(T, cfg.n_users)}")
        if T and (self.app_choice.min() < 0 or
                  self.app_choice.max() >= len(APPS)):
            # out-of-range choices would index catalog tables from the
            # end (numpy) or clamp (jax gather) — silently wrong energy
            raise ValueError(
                f"arrival process {self.arrivals.name!r} produced app "
                f"choices outside [0, {len(APPS)})")
        # int32 once, checked first so no wide value wraps into range:
        # the dtype the jax scan reads, so no run converts them again
        self.app_choice = self.app_choice.astype(np.int32)

    # ------------------------------------------------------------ state views
    @property
    def users(self) -> List[UserState]:
        """One ``UserState`` per fleet device: the loop oracle's per-user
        view (and ``decide_loop`` hooks'). Built on first access, so the
        batched engines, which never read it, never pay for it."""
        if self._users is None:
            self._users = [UserState(device=d)
                           for d in self.fleet_spec.devices]
        return self._users

    # Scalar server state lives in self.state (the shared EngineState);
    # these properties keep the historical sim.version / sim.in_flight /
    # sim._round_open spelling for policy hooks and ML backends.
    @property
    def version(self) -> int:
        return self.state.version

    @version.setter
    def version(self, v: int):
        self.state.version = v

    @property
    def in_flight(self) -> int:
        return self.state.in_flight

    @in_flight.setter
    def in_flight(self, v: int):
        self.state.in_flight = v

    @property
    def _round_open(self) -> bool:
        return self.state.round_open

    @_round_open.setter
    def _round_open(self, v: bool):
        self.state.round_open = v

    # ------------------------------------------------------------------ utils
    def _v_norm(self) -> float:
        if "v_norm" in self.ml:
            return self.ml["v_norm"]()
        return trace_v_norm(self.cfg.v_norm0, self.version)

    def begin_training(self, u: UserState, t: int, corun: bool):
        """Start user ``u`` training this slot (public: the loop-engine
        twin of _NumpyEngine.begin_training, called from Policy.decide_loop
        hooks)."""
        u.mode = "training"
        u.corun = corun and u.app is not None
        u.train_remaining = u.device.duration(u.corun, u.app)
        u.pulled_at = self.version
        u.started_at = t
        self.in_flight += 1
        if self.ml.get("pull"):
            u._params = self.ml["pull"](u._uid)

    def _finish_training(self, u: UserState, t: int, log: PushLog,
                         extra_delay: int = 0):
        """``extra_delay`` is the device-dynamics network penalty (slots):
        a finisher in the bad network state re-arrives late, so its next
        pull is staler — the churn layer's feed into the lag model."""
        lag = self.version - u.pulled_at
        vn = self._v_norm()
        gap = gradient_gap(vn, lag, self.cfg.eta, self.cfg.beta)
        res = None
        if self.policy.sync_rounds:
            if self.ml.get("sync_submit"):
                trained = self.ml["local_train"](u._uid, u._params)
                self.ml["sync_submit"](trained)
        else:
            self.version += 1
            if self.ml.get("push"):
                trained = self.ml["local_train"](u._uid, u._params)
                res = self.ml["push"](u._uid, trained)
        u.updates += 1
        u.mode = "cooldown"
        u.cooldown = self.cfg.ready_delay + extra_delay
        u.idle_gap = 0.0
        self.in_flight -= 1
        if self.cfg.collect_push_log:
            # applied aggregation weight, only materialized for the log:
            # what the server DID (real mode), the rule's value (trace),
            # or 1.0 for FedAvg rounds (no per-push weight)
            if self.policy.sync_rounds:
                weight = 1.0
            elif res is not None and \
                    getattr(res, "applied_weight", None) is not None:
                weight = float(res.applied_weight)
            else:
                weight = float(self.agg.weight(lag, gap, vn,
                                               fleet=self.fleet_spec,
                                               users=u._uid))
            log.append(t, u._uid, lag, gap, u.corun, weight)

    # ------------------------------------------------------------------ main
    def resolve_engine(self) -> str:
        """Pick the engine to run. The vectorized SoA engine covers two
        regimes: pure trace mode (real-ML *hooks* other than the
        slot-constant ``v_norm`` need the per-user object loop) and real
        mode driven by a batched ``ml_backend`` (core/realml.py), whose
        cohort-level entry points the engine dispatches once per slot.
        ``auto`` selects it whenever the policy implements the vectorized
        hook; real mode with per-user hooks (or no backend) stays on the
        loop oracle. The jax backend covers hook-free trace runs of
        policies with the ``scan_step`` carry hook — all registry policies
        qualify, including offline (its knapsack plan runs through a host
        callback) and greedy (wait counters in the carry); push-log
        collection streams out of the scan and is NOT a jax blocker. With
        a ``v_norm`` hook or an ml_backend (Python callbacks cannot run
        under the scan per slot) it degrades to the numpy engine, which
        honors both; policies without scan_step degrade the same way."""
        cfg = self.cfg
        pol = self.policy
        vec_ok = (cfg.ml_mode == "trace" and set(self.ml) <= {"v_norm"}) \
            or (cfg.ml_mode == "real" and self.ml_backend is not None)
        engine = cfg.engine
        if cfg.n_devices:
            # the sharded scan (SimConfig validated policy/agg/dynamics
            # shard support at construction) runs only on the jax engine
            # and never degrades silently — remaining blockers are the
            # per-slot host callbacks the scan cannot shard
            if self.ml or self.ml_backend is not None:
                raise ValueError(
                    f"n_devices={cfg.n_devices} shards the jax chunked "
                    "scan, which cannot run per-user ML hooks or a "
                    "real-ML backend; set n_devices=0 for those runs")
            return "jax"
        if engine == "auto":
            return "vectorized" if (vec_ok and pol.supports_vectorized) \
                else "loop"
        if engine in ("vectorized", "jax") and not vec_ok:
            raise ValueError(
                f"engine={engine!r} supports trace-mode runs without "
                "per-user ML hooks, or ml_mode='real' with a batched "
                "ml_backend; use engine='loop' (or 'auto') for "
                "hook-based real-ML runs")
        if engine == "vectorized" and not pol.supports_vectorized:
            raise ValueError(
                f"policy {pol.name!r} implements no vectorized hook; "
                "use engine='loop' (or 'auto')")
        if engine == "jax":
            # a push log under a rule without a traced scan_weight cannot
            # fill the weight column in-scan: degrade like a policy
            # without scan_step (weight-free runs are unaffected)
            agg_jax = aggregation_support(self.agg)["jax"] or \
                not cfg.collect_push_log
            # an active dynamics without a traced scan_step degrades the
            # same way (the numpy engine runs its host transition)
            dyn_jax = dynamics_support(self.dynamics)["jax"]
            if pol.supports_jax and agg_jax and dyn_jax and \
                    not self.ml and self.ml_backend is None:
                return "jax"
            # degrade in capability order: numpy SoA if the policy has the
            # hook (any policy under a v_norm callback, or any real-mode
            # backend run), else the loop oracle, which runs everything
            return "vectorized" if pol.supports_vectorized else "loop"
        return engine

    def run(self) -> SimResult:
        engine = self.resolve_engine()
        with TraceAnnotation("sim.run", engine=engine,
                             n_users=self.cfg.n_users,
                             slots=n_slots(self.cfg)):
            if getattr(self, "_ran", False):
                # a run consumes the mutable EngineState / UserState
                # objects; reallocate the one and drop the other (rebuilt
                # on access) so repeated run() calls (warmup-then-timed
                # patterns) start fresh instead of continuing silently
                # from the previous run's state. A
                # real-ML backend is reset too (core/realml.py), so the
                # run repeats the first; bare ml_hooks dicts have no
                # reset and carry their state over.
                with TraceAnnotation("sim.reset"):
                    if self.ml_backend is not None:
                        self.ml_backend.reset()
                    self.state = EngineState.init(
                        self.cfg.n_users, self.cfg, self.policy,
                        agg=self.agg, fleet=self.fleet_spec,
                        dynamics=self.dynamics)
                    self._users = None
                    self.sched.Q = 0.0
                    self.sched.H = 0.0
            self._ran = True
            if engine == "loop":
                return self._run_loop()
            from .vector_engine import run_vectorized
            return run_vectorized(self, backend=engine)

    def _run_loop(self) -> SimResult:
        cfg = self.cfg
        policy = self.policy
        es = self.state                   # scalar/carry state container
        dynamics = self.dynamics
        dyn_active = dynamics.active
        up = net_extra = None
        for i, u in enumerate(self.users):
            u._uid = i
            u._params = None
        T = n_slots(cfg)
        trace_t, trace_E, trace_Q, trace_H = [], [], [], []
        push_log = PushLog()
        accuracy: List[tuple] = []
        carry = es.carry

        for t in range(T):
            arrivals = 0
            departures = 0

            # --- device dynamics (churn) ------------------------------------
            # Runs FIRST in the slot on every engine: the shared host
            # transition decides who went up/down, then the effects are
            # applied in the loop idiom — a waiting user that churns off
            # leaves the request queue (departure), a training user drops
            # per the dynamics' rule ("lose": in-flight work discarded;
            # "resume": paused, pays the penalty), a recovered user
            # re-enters the arrival process through cooldown with the
            # network state's extra delay.
            if dyn_active:
                mode_arr = np.array([_MODE_CODE[u.mode] for u in self.users],
                                    dtype=np.int8)
                corun_arr = np.array([u.corun for u in self.users],
                                     dtype=bool)
                es.dyn, es.rng_key, eff = dynamics.host_step(
                    es.dyn, es.rng_key, mode_arr, corun_arr, cfg.t_d)
                up = np.asarray(eff.up)
                net_extra = np.asarray(eff.net_extra)
                for i, u in enumerate(self.users):
                    if eff.went_down[i]:
                        if u.mode == "waiting":
                            u.mode = "off"
                            departures += 1
                        elif u.mode == "training":
                            if dynamics.dropout == "lose":
                                u.mode = "off"
                                u.train_remaining = 0.0
                                self.in_flight -= 1
                            else:       # resume: paused, extra seconds
                                u.train_remaining += float(
                                    eff.resume_penalty)
                        elif u.mode == "cooldown":
                            u.mode = "off"
                    elif eff.went_up[i] and u.mode == "off":
                        u.mode = "cooldown"
                        u.cooldown = cfg.ready_delay + int(net_extra[i])

            # --- app arrivals / progression -------------------------------
            for i, u in enumerate(self.users):
                if u.app is None and self.app_sched[t, i]:
                    u.app = APPS[self.app_choice[t, i]]
                    u.app_remaining = u.device.apps[u.app].t_corun
                elif u.app is not None:
                    u.app_remaining -= cfg.t_d
                    if u.app_remaining <= 0:
                        u.app, u.app_remaining = None, 0.0

            # --- cooldown -> waiting (queue arrival) ------------------------
            for u in self.users:
                if u.mode == "cooldown":
                    u.cooldown -= 1
                    if u.cooldown <= 0:
                        u.mode = "waiting"
                        u.plan = "hold"   # offline: wait for next plan window
                        arrivals += 1

            # --- policy decisions for waiting users -------------------------
            waiting = [u for u in self.users if u.mode == "waiting"]
            served, gap_sum = policy.decide_loop(self, t, waiting, carry)

            # --- training progression ---------------------------------------
            # Under churn a down trainer makes no progress (a "resume"
            # dropout is paused, not working), and a finisher's cooldown
            # carries the current network state's extra delay.
            for u in self.users:
                if u.mode == "training" and (not dyn_active or up[u._uid]):
                    u.train_remaining -= cfg.t_d
                    if u.train_remaining <= 0:
                        self._finish_training(
                            u, t, push_log,
                            extra_delay=int(net_extra[u._uid])
                            if dyn_active else 0)
                        if u.corun:
                            es.corun_updates += 1
            if policy.sync_rounds and self._round_open and \
                    all(u.mode != "training" for u in self.users):
                self._round_open = False
                self.version += 1
                if self.ml.get("sync_aggregate"):
                    self.ml["sync_aggregate"]()

            # --- energy accounting (Eq. 10) ---------------------------------
            # A down device draws nothing (off) — a paused "resume"
            # trainer included.
            for u in self.users:
                p = u.device.power(u.mode == "training", u.app is not None, u.app)
                if cfg.include_scheduler_overhead and u.mode == "waiting" \
                        and policy.uses_online_queue:
                    p += u.device.p_sched - u.device.p_idle
                if dyn_active and not up[u._uid]:
                    p = 0.0
                u.energy_j += p * cfg.t_d

            # --- queues ------------------------------------------------------
            self.sched.update_queues(arrivals, served, gap_sum, departures)
            es.Q, es.H = self.sched.Q, self.sched.H
            es.sum_Q += es.Q
            es.sum_H += es.H

            if t % cfg.trace_every == 0:
                trace_t.append(t)
                trace_E.append(sum(u.energy_j for u in self.users))
                trace_Q.append(es.Q)
                trace_H.append(es.H)
            eval_every = self.ml.get("eval_every", 600)
            if self.ml.get("evaluate") and eval_every and \
                    t % eval_every == 0 and t > 0:
                accuracy.append((t, self.ml["evaluate"]()))

        if self.ml.get("evaluate"):
            accuracy.append((T, self.ml["evaluate"]()))
        updates = sum(u.updates for u in self.users)
        return SimResult(
            energy_j=sum(u.energy_j for u in self.users),
            updates=updates,
            trace_t=np.array(trace_t), trace_energy=np.array(trace_E),
            trace_Q=np.array(trace_Q), trace_H=np.array(trace_H),
            push_log=push_log, accuracy=accuracy,
            mean_Q=es.sum_Q / T if T else 0.0,
            mean_H=es.sum_H / T if T else 0.0,
            corun_fraction=es.corun_updates / max(updates, 1),
            **dynamics.counters(es.dyn))
