"""Struct-of-arrays vectorized simulator engine.

Replaces ``FederatedSim``'s per-slot, per-user Python object loop with
batched per-user state arrays — the run's ``EngineState``
(core/engine_state.py): mode, cooldown, app id, app/train remaining,
pulled-at version, energy, idle gap all live in ``(n_users,)`` NumPy
arrays, and the fleet's catalog is flattened into ``(n_devices, n_apps)``
lookup tables (``FleetSpec.tables``) gathered per user once at startup;
the jax slot step selects each user's entry from these per-user tables by
app column (a where-chain over the app axis, no per-slot gather).
Every phase of a slot — app arrivals, cooldown transitions, policy
decisions, training progression, Eq. (10) energy accounting, Eq. (15)/(16)
queue updates — is a handful of vector ops instead of an O(n) Python loop.

Policy dispatch is pluggable (core/policies.py): the engine exposes the
shared state as ``eng.s`` (an ``EngineState``) plus per-slot masks and
each user's table entries for its current app, threads the policy's
carry pytree (``Policy.init_carry``), and calls the
``decide_vectorized`` hook once per slot; registered paper policies and
any custom policy with the hook run here unmodified.

Real-ML runs are batched too (core/realml.py): with an ``ml_backend`` the
engine snapshots pulls per starting cohort (``pull_batch``, at the
EngineState's global version) and, when a slot's trainers finish,
dispatches ONE vmap'd local-train over the whole finisher cohort followed
by ordered server pushes (``_finish_cohort``) — instead of the loop
engine's n Python callbacks. Accuracy is sampled on the same cadence as
the loop oracle. The engine's calls into the backend open host spans
(``ml.pull``, ``ml.finish``, ``ml.eval``) inside the run's ``sim.run``.

Equivalence contract: seeded runs reproduce the reference loop engine
(``FederatedSim._run_loop``) — identical decision sequences, update counts,
push logs and queue traces; energies match to float-sum reordering
(pairwise vs sequential reduction, ~1e-15 relative). The only sequential
coupling inside a slot is the online policy's lag estimate (every user that
schedules raises the next user's in-flight count); ``OnlineScheduler.
decide_batch`` collapses it to one elementwise comparison when H == 0 (the
gap term then cannot affect the argmin) and replays it exactly otherwise.

``backend="jax"`` compiles the horizon into ``lax.scan`` chunks of
``SimConfig.jax_chunk`` slots whose carry is the SAME ``EngineState``
pytree (jit-compiled once per (shape, policy class); scalar knobs like
V/L_b and policy ``scan_operands`` passed as traced operands so sweeps
reuse the executable). The jax backend covers every policy implementing
the ``scan_step`` carry hook — all registry policies, including offline
(host knapsack via ``jax.pure_callback`` at plan slots) and greedy (wait
counters carried through the scan); others stay on the numpy path.

Push logs stream out of the scan through a fixed-width event buffer
(``engine_state.PushBuffer``): each finishing user writes one
``(t, user, lag, gap, corun, weight)`` row at the buffer cursor — the
``weight`` column is the aggregation rule's applied mixing weight
(core/aggregation.py, ``SimConfig.aggregation``), computed in-jit through
the rule's ``scan_weight`` hook with its carry riding in
``EngineState.agg_carry``. A slot with finishers sorts them to the front
in user order and writes their rows as contiguous blocks of ``K`` rows
(``_push_block``); a slot without any does no push-log work. The host
drains and resets the buffer after every chunk, and an overflowing chunk
is re-run with a doubled buffer (``count`` always records the true push
total) — so ``collect_push_log=True`` costs O(chunk) memory at any fleet
size, never O(T * n). Enable jax x64 for f64 parity with the numpy
engines; in f32, user ids stay exact up to 2**24.

``SimConfig.n_devices`` > 0 shards the SAME chunked scan over a 1-D
``("users",)`` mesh (launch/mesh.py ``make_sim_mesh``) via GSPMD
constraint steering: per-user EngineState leaves, the per-user tables
(and the app columns the slot step selects from) and arrival columns
carry ``PartitionSpec("users")`` constraints, scheduler scalars stay
replicated, and XLA's SPMD partitioner inserts the collectives.
Bit-consistency with the single-device scan is by
construction, not luck: every input of the POLICY DECISION phase is
constrained replicated before the ``scan_step`` hook runs, so Alg. 2's
float reductions (Eq. 16's gap sum feeding H) compile to the exact
single-device reduction order — a shard-local partial sum + AllReduce
would reassociate them and could flip a decision. The surrounding
per-user phases (arrivals, training progression, Eq. 10 energy, churn)
stay sharded; their cross-user reductions are integer counts, which
psum exactly. A non-divisible ``n_users`` pads the axis with INERT rows
(``engine_state.pad_state_per_user``: MODE_OFF, zeroed catalog rows,
arrival columns that never fire, dynamics rows pinned up) and
stochastic hooks draw at the LIVE n, padding draws with fill 1.0
(threefry output is shape-dependent) — so push logs, queue traces and
decisions are digest-identical to the unsharded engine at any
(n, mesh) combination; only energy sums differ by float reduction
order. ``jax_chunk=0`` / ``push_log_capacity=0`` auto-tune from the
per-device memory budget (core/autotune.py).
"""
from __future__ import annotations

import dataclasses
import logging
from types import SimpleNamespace
from typing import List, Tuple

import numpy as np
from jax.profiler import TraceAnnotation

from .engine_state import (EngineState, PushBuffer, PushLog, MODE_COOL,
                           MODE_OFF, MODE_TRAIN, MODE_WAIT, PLAN_CORUN,
                           PLAN_HOLD, PLAN_SEP, _PER_USER_FIELDS,
                           pad_state_per_user, pad_to_devices,
                           state_shardings, unpad_state_per_user)
from .policies import _jax_gradient_gap, _jax_trace_v_norm
from .simulator import SimResult, n_slots, trace_v_norm
from .staleness import gradient_gap

__all__ = ["run_vectorized", "run_jax_sweep", "sweep_bucket_key",
           "jax_cache_stats", "reserve_jax_cache_capacity", "ScanChunk",
           "MODE_WAIT", "MODE_TRAIN", "MODE_COOL",
           "PLAN_HOLD", "PLAN_CORUN", "PLAN_SEP"]

_LOG = logging.getLogger(__name__)


def run_vectorized(sim, backend: str = "vectorized") -> SimResult:
    """Run ``sim`` (a constructed FederatedSim) on a batched engine."""
    if backend == "jax":
        return _run_jax(sim)
    return _NumpyEngine(sim).run()


def _user_tables(sim):
    """Gather the fleet's catalog rows for each user's device, once per
    run. Any fleet works — the tables come from ``sim.fleet_spec``, not
    the frozen Table II catalog."""
    tab = sim.fleet_spec.tables
    dev = sim.fleet_spec.device_ids
    return (tab.p_train[dev], tab.t_train[dev], tab.p_idle[dev],
            tab.p_sched[dev], tab.p_app[dev], tab.p_corun[dev],
            tab.t_corun[dev], tab.saving_rate[dev])


# ======================================================================
# NumPy backend
# ======================================================================
class _NumpyEngine:
    """Per-run slot loop over the shared ``EngineState``. Policies
    read/mutate state from their ``decide_vectorized`` hook:

    - ``s``: the run's ``EngineState`` (``sim.state``) — per-user arrays,
      scheduler scalars (version, in_flight, round_open, Q, H) and the
      policy carry
    - ``waiting`` / ``has_app``: this slot's masks (set before dispatch)
    - ``p_if_train`` / ``p_if_idle``: Eq. (10) powers of the train/idle
      branch per user (co-run aware, maintained incrementally — derived
      caches over ``s.app``, not canonical state)
    - ``T_COR``, ``SRATE``, ``app_sched``, ``app_choice``: lookahead tables
    - ``begin_training(idx)``: schedule users ``idx`` this slot
    - ``v_norm(ver)``: momentum-norm model (honors the ``v_norm`` hook)
    - ``sched``: the OnlineScheduler queue-update rule + decide_batch
      (``s.Q``/``s.H`` mirror its state after every slot)
    """

    def __init__(self, sim):
        cfg = sim.cfg
        self.cfg = cfg
        self.n = cfg.n_users
        self.T = n_slots(cfg)
        (self.PT, self.TT, self.PI, self.PS, self.P_APP, self.P_COR,
         self.T_COR, self.SRATE) = _user_tables(sim)
        self.OVERHEAD = self.PS - self.PI
        self.app_sched, self.app_choice = sim.app_sched, sim.app_choice
        self.sched = sim.sched             # queue update rule + decide_batch
        self.policy = sim.policy
        self.agg = sim.agg                 # aggregation rule (weight path)
        self.dynamics = sim.dynamics       # device churn (core/dynamics.py)
        self.fleet_spec = sim.fleet_spec
        self._v_hook = sim.ml.get("v_norm")
        # batched real-ML backend (core/realml.py): pull/train/push whole
        # cohorts instead of per-user callbacks; None for trace runs
        self.backend = sim.ml_backend
        self.ar = np.arange(self.n)

        # ---- the shared state container -------------------------------
        self.s = sim.state
        # App-dependent lookups, maintained incrementally on the (rare) app
        # arrival/expiry events instead of re-gathered every slot:
        #   p_if_train  = Eq. 10 power if training (P^{a'} with app, else P^b)
        #   p_if_idle   = Eq. 10 power if not     (P^a with app, else P^d)
        #   t_if_corun  = co-run training duration for the current app
        self.p_if_train = self.PT.copy()
        self.p_if_idle = self.PI.copy()
        self.t_if_corun = np.zeros(self.n)

        self.waiting = np.zeros(self.n, dtype=bool)
        self.has_app = np.zeros(self.n, dtype=bool)

    def v_norm(self, ver):
        """ver may be a scalar or an array of per-finisher versions; the
        v_norm hook (slot-constant by contract) broadcasts."""
        if self._v_hook is not None:
            return self._v_hook()
        return trace_v_norm(self.cfg.v_norm0, ver)

    def _finish_cohort(self, fidx, lags):
        """Real-ML finish: one batched local-train for the slot's whole
        finisher cohort, then sequential server application in user order
        (the loop oracle's push ordering — each finisher's Eq. (4) gap
        sees the momentum norm left by the previous one). Returns the
        per-finisher ``(gaps, weights)`` for the push log."""
        b = self.backend
        cfg = self.cfg
        if b.sync == self.policy.sync_rounds:
            if b.sync:
                trained = b.local_train_batch(fidx, self.s.pulled_at[fidx])
                return b.submit_batch(fidx, trained, lags, cfg.eta,
                                      cfg.beta)
            return b.finish_async_batch(fidx, self.s.pulled_at[fidx], lags,
                                        cfg.eta, cfg.beta,
                                        need_gaps=cfg.collect_push_log)
        # policy/backend round-mode mismatch: the loop oracle finds no
        # matching hook and skips training; keep the log gaps AND the
        # rule-fallback weights consistent with the oracle's
        vn = b.v_norm()
        gaps = np.asarray(gradient_gap(vn, lags, cfg.eta, cfg.beta),
                          dtype=float)
        if self.policy.sync_rounds:
            return gaps, np.ones(len(lags))
        return gaps, np.asarray(self.agg.weight(lags, gaps, vn,
                                                fleet=self.fleet_spec,
                                                users=fidx), dtype=float)

    def begin_training(self, idx):
        """idx: user indices starting training this slot (corun iff app)."""
        s = self.s
        ha = s.app[idx] >= 0
        s.corun[idx] = ha
        s.train_rem[idx] = np.where(ha, self.t_if_corun[idx], self.TT[idx])
        s.mode[idx] = MODE_TRAIN
        s.pulled_at[idx] = s.version
        s.in_flight += len(idx)
        if self.backend is not None:
            with TraceAnnotation("ml.pull", cohort=len(idx)):
                self.backend.pull_batch(np.asarray(idx), s.version)

    def run(self) -> SimResult:
        cfg = self.cfg
        policy = self.policy
        t_d = cfg.t_d
        n, T = self.n, self.T
        s = self.s
        sched = self.sched
        app_sched, app_choice = self.app_sched, self.app_choice
        mode, app, app_rem = s.mode, s.app, s.app_rem
        carry = s.carry

        trace_t: List[int] = []
        trace_E: List[float] = []
        trace_Q: List[float] = []
        trace_H: List[float] = []
        accuracy: List[Tuple] = []
        eval_every = self.backend.eval_every if self.backend is not None \
            else 0
        push_log = PushLog()      # fixed-width blocks, decoded lazily
        dynamics = self.dynamics
        dyn_active = dynamics.active
        dyn_lose = dynamics.dropout == "lose"
        up = net_extra = None

        for t in range(T):
            departures = 0

            # --- device dynamics (churn) -----------------------------------
            # Same shared host transition as the loop oracle, effects
            # applied as masked writes: waiting -> off is a queue
            # departure, training -> off follows the dropout rule,
            # cooling parks in off, and recovered users re-enter through
            # cooldown with the network state's extra delay.
            if dyn_active:
                s.dyn, s.rng_key, eff = dynamics.host_step(
                    s.dyn, s.rng_key, mode, s.corun, t_d)
                up = np.asarray(eff.up)
                net_extra = np.asarray(eff.net_extra)
                wd = np.asarray(eff.went_down)
                if wd.any():
                    dwait = wd & (mode == MODE_WAIT)
                    dtrain = wd & (mode == MODE_TRAIN)
                    dcool = wd & (mode == MODE_COOL)
                    departures = int(np.count_nonzero(dwait))
                    mode[dwait | dcool] = MODE_OFF
                    if dyn_lose:
                        mode[dtrain] = MODE_OFF
                        s.train_rem[dtrain] = 0.0
                        s.in_flight -= int(np.count_nonzero(dtrain))
                    else:       # resume: paused, pays the extra seconds
                        s.train_rem[dtrain] += float(eff.resume_penalty)
                ret = np.asarray(eff.went_up) & (mode == MODE_OFF)
                if ret.any():
                    mode[ret] = MODE_COOL
                    s.cooldown[ret] = cfg.ready_delay + net_extra[ret]

            # --- app arrivals / progression -------------------------------
            srow = app_sched[t]
            has_app = app >= 0
            new_app = srow & ~has_app
            if has_app.any():
                app_rem[has_app] -= t_d
                ended = has_app & (app_rem <= 0.0)
                if ended.any():
                    app[ended] = -1
                    app_rem[ended] = 0.0
                    self.p_if_train[ended] = self.PT[ended]
                    self.p_if_idle[ended] = self.PI[ended]
            if new_app.any():
                nidx = np.nonzero(new_app)[0]
                aid = app_choice[t, nidx]
                app[nidx] = aid
                app_rem[nidx] = self.T_COR[nidx, aid]
                self.p_if_train[nidx] = self.P_COR[nidx, aid]
                self.p_if_idle[nidx] = self.P_APP[nidx, aid]
                self.t_if_corun[nidx] = self.T_COR[nidx, aid]

            # --- cooldown -> waiting (queue arrival) -----------------------
            arrivals = 0
            cooling = mode == MODE_COOL
            if cooling.any():
                s.cooldown[cooling] -= 1
                to_wait = cooling & (s.cooldown <= 0)
                arrivals = int(np.count_nonzero(to_wait))
                if arrivals:
                    mode[to_wait] = MODE_WAIT
                    s.plan[to_wait] = PLAN_HOLD
            self.waiting = mode == MODE_WAIT
            self.has_app = app >= 0

            # --- policy decisions for waiting users ------------------------
            served, gap_sum = policy.decide_vectorized(self, t, carry)

            # --- training progression --------------------------------------
            # under churn a down trainer is paused (resume rule) and
            # makes no progress
            training = (mode == MODE_TRAIN) & up if dyn_active \
                else mode == MODE_TRAIN
            if training.any():
                s.train_rem[training] -= t_d
                fin = training & (s.train_rem <= 0.0)
                fidx = np.nonzero(fin)[0]
                k = len(fidx)
                if k:
                    gaps = weights = None
                    if policy.sync_rounds:
                        lags = s.version - s.pulled_at[fidx]
                        if self.backend is None and cfg.collect_push_log:
                            gaps = gradient_gap(self.v_norm(s.version),
                                                lags, cfg.eta, cfg.beta)
                            # FedAvg rounds average; no per-push weight
                            weights = np.ones(k)
                    else:
                        # async finishers bump the version one by one, in
                        # user order — each sees the versions of earlier
                        # finishers
                        vers = s.version + np.arange(k)
                        lags = vers - s.pulled_at[fidx]
                        if self.backend is None and cfg.collect_push_log:
                            vns = self.v_norm(vers)
                            gaps = gradient_gap(vns, lags, cfg.eta,
                                                cfg.beta)
                            weights = self.agg.weight(
                                lags, gaps, vns, fleet=self.fleet_spec,
                                users=fidx)
                        s.version += k
                    if self.backend is not None:
                        # one vmap'd local-train + ordered server pushes
                        with TraceAnnotation("ml.finish", pushes=k):
                            gaps, weights = self._finish_cohort(fidx, lags)
                    s.updates[fidx] += 1
                    mode[fidx] = MODE_COOL
                    s.cooldown[fidx] = cfg.ready_delay if not dyn_active \
                        else cfg.ready_delay + net_extra[fidx]
                    s.idle_gap[fidx] = 0.0
                    s.in_flight -= k
                    s.corun_updates += int(np.count_nonzero(s.corun[fidx]))
                    if cfg.collect_push_log:
                        push_log.extend(t, fidx, lags, gaps, s.corun[fidx],
                                        weights)
            if policy.sync_rounds and s.round_open and \
                    not np.any(mode == MODE_TRAIN):
                s.round_open = False
                s.version += 1
                if self.backend is not None and self.backend.sync:
                    self.backend.sync_aggregate()

            # --- energy accounting (Eq. 10) --------------------------------
            training = mode == MODE_TRAIN
            p = np.where(training, self.p_if_train, self.p_if_idle)
            if cfg.include_scheduler_overhead and policy.uses_online_queue:
                p = np.where(mode == MODE_WAIT, p + self.OVERHEAD, p)
            if dyn_active:     # a down device draws nothing
                p = np.where(up, p, 0.0)
            if t_d != 1.0:     # p * 1.0 == p bitwise; skip the alloc
                p *= t_d
            s.energy += p

            # --- queues -----------------------------------------------------
            sched.update_queues(arrivals, served, gap_sum, departures)
            s.Q, s.H = sched.Q, sched.H
            s.sum_Q += s.Q
            s.sum_H += s.H
            if t % cfg.trace_every == 0:
                trace_t.append(t)
                trace_E.append(float(s.energy.sum()))
                trace_Q.append(s.Q)
                trace_H.append(s.H)
            if eval_every and t % eval_every == 0 and t > 0:
                with TraceAnnotation("ml.eval"):
                    accuracy.append((t, self.backend.evaluate()))

        if self.backend is not None:
            with TraceAnnotation("ml.eval"):
                accuracy.append((T, self.backend.evaluate()))
        updates_total = int(s.updates.sum())
        return SimResult(
            energy_j=float(s.energy.sum()),
            updates=updates_total,
            trace_t=np.array(trace_t), trace_energy=np.array(trace_E),
            trace_Q=np.array(trace_Q), trace_H=np.array(trace_H),
            push_log=push_log, accuracy=accuracy,
            mean_Q=s.sum_Q / T if T else 0.0,
            mean_H=s.sum_H / T if T else 0.0,
            corun_fraction=s.corun_updates / max(updates_total, 1),
            **self.dynamics.counters(s.dyn))


# ======================================================================
# JAX backend: the horizon as chunked lax.scans over the EngineState
# pytree, jitted per (shape, policy class, chunk, buffer capacity)
# ======================================================================
_JAX_FN_CACHE: dict = {}
_JAX_FN_CACHE_MAX = 32
_JAX_CACHE_STATS = {"hits": 0, "misses": 0, "evictions": 0}


def jax_cache_stats() -> dict:
    """Counters for the jitted-chunk cache: ``hits`` (executable reused),
    ``misses`` (a trace + compile happened), ``evictions`` (an LRU entry
    was dropped to make room — if these climb during a sweep the cap is
    too small; see :func:`reserve_jax_cache_capacity`)."""
    return dict(_JAX_CACHE_STATS)


def reserve_jax_cache_capacity(k: int) -> None:
    """Raise (never lower) the jitted-chunk cache cap so every bucket of
    a shape-bucketed sweep stays resident for the sweep's whole lifetime.
    ``run_sweep`` calls this before running its buckets; evicting a hot
    bucket mid-sweep would silently recompile it on the next chunk."""
    global _JAX_FN_CACHE_MAX
    _JAX_FN_CACHE_MAX = max(_JAX_FN_CACHE_MAX, int(k))


def _mesh_key(mesh):
    """Hashable signature of a sharding mesh for the executable caches:
    axis names, axis sizes AND the concrete device ids — two meshes over
    different devices must never alias one executable (their compiled
    collectives bake in device assignments). ``None`` = unsharded."""
    if mesh is None:
        return None
    return (tuple(mesh.axis_names),
            tuple(int(mesh.shape[a]) for a in mesh.axis_names),
            tuple(int(d.id) for d in np.asarray(mesh.devices).flat))


def _jax_chunk_fn(n: int, chunk: int, T: int, policy, overhead: bool,
                  collect: bool, capacity: int, statics: tuple = (),
                  agg=None, dynamics=None, batch: int = 0,
                  mesh=None, n_arr: int = 0):
    """Build + jit one scan chunk, memoized on (shapes,
    ``policy.jax_cache_key()``, overhead/collect flags, event-buffer
    capacity, the policy's ``scan_statics``, and — when the push log is
    collected — the aggregation rule's ``jax_cache_key()``). Policies
    and rules key by class by default, so both
    ``SimConfig(policy="online")`` and a fresh ``OnlinePolicy()`` per
    run share one executable; scalar knobs (V, L_b, ...,
    ``scan_operands``) are traced operands, so e.g. a V-sweep compiles
    once. With ``batch`` > 0 the chunk is ``jax.vmap``-ped over a
    leading config axis on every operand except ``t0`` — one program
    advances ``batch`` stacked scenarios a chunk at a time (the sweep
    path). With ``mesh`` set the chunk is built with GSPMD sharding
    constraints over the mesh's ``users`` axis at the padded length
    ``n_arr`` — the mesh signature (axes, sizes, device ids) and
    ``n_arr`` join the memo key so sharded and unsharded executables of
    the same shape NEVER alias. The policy's ``scan_step`` hook supplies
    the decision block and the rule's ``scan_weight`` the push-log
    weight column; everything else — arrivals, cooldowns, training
    progression, Eq. 10 energy, Eq. 15/16 queues, the push-log write —
    is engine code shared by every policy."""
    if agg is None:
        from .aggregation import resolve_aggregation
        agg = resolve_aggregation("replace")
    if dynamics is None:
        from .dynamics import resolve_dynamics
        dynamics = resolve_dynamics("none")
    key = (n, chunk, T, policy.jax_cache_key(), overhead, collect, capacity,
           statics, agg.jax_cache_key() if collect else None,
           dynamics.jax_cache_key() if dynamics.active else None, batch,
           _mesh_key(mesh), n_arr or n)
    fn = _JAX_FN_CACHE.pop(key, None)   # pop+reinsert = LRU order
    if fn is None:
        _JAX_CACHE_STATS["misses"] += 1
        fn = _build_jax_chunk_fn(n, chunk, T, policy, overhead, collect,
                                 capacity, statics, agg, dynamics, batch,
                                 mesh, n_arr)
        while _JAX_FN_CACHE and len(_JAX_FN_CACHE) >= _JAX_FN_CACHE_MAX:
            old = next(iter(_JAX_FN_CACHE))
            _JAX_FN_CACHE.pop(old)      # evict LRU
            _JAX_CACHE_STATS["evictions"] += 1
            _LOG.info("jax chunk cache full (max=%d): evicted %r",
                      _JAX_FN_CACHE_MAX, old[:4])
    else:
        _JAX_CACHE_STATS["hits"] += 1
    _JAX_FN_CACHE[key] = fn
    return fn


def _push_block(n_arr: int) -> int:
    """Rows in one block of the slot step's push-log write. A slot with
    ``kfin`` finishers writes ``ceil(kfin / K)`` blocks, so the buffer
    carries ``K`` slack rows past its capacity for the last block."""
    return max(1, min(int(n_arr), 4096))


def _drain_counts(ts, K: int) -> dict:
    """``scan.drain``'s counters from the drained ``t`` columns of a chunk
    (one per config): ``push_slots``, the slots that wrote rows, and
    ``blocks``, the ``K``-row blocks the slot step wrote for them."""
    k = np.concatenate([np.unique(t, return_counts=True)[1] for t in ts])
    return {"push_slots": int(len(k)), "blocks": int(np.sum(-(-k // K)))}


def _build_jax_chunk_fn(n: int, chunk: int, T: int, policy, overhead: bool,
                        collect: bool, capacity: int, statics: tuple = (),
                        agg=None, dynamics=None, batch: int = 0,
                        mesh=None, n_arr: int = 0):
    import jax
    import jax.numpy as jnp
    from jax import lax

    # device churn (core/dynamics.py): the phase is compiled in only for
    # active dynamics — inactive runs trace the exact historical step —
    # and the dropout rule is a static structural branch (both are part
    # of the _jax_chunk_fn cache key)
    dyn_active = dynamics is not None and dynamics.active
    dyn_lose = dyn_active and dynamics.dropout == "lose"
    # uneven horizon: the driver pads arrivals to a whole number of
    # chunks and the scan skips slots past T, so the tail chunk reuses
    # THIS executable instead of compiling a second one per horizon
    pad = chunk > 0 and (T % chunk) != 0
    # sharded build (see module docstring): n_arr is the padded user-axis
    # length, shard/repl insert the GSPMD constraints, place dispatches
    # per leaf — all identity on the unsharded build, whose traced graph
    # stays byte-identical to the historical one
    n_arr = int(n_arr) or n
    K = _push_block(n_arr)
    if mesh is not None:
        if batch:
            raise ValueError("sharded chunks never batch: the mesh IS the "
                             "parallelism (sweep_bucket_key returns None)")
        from jax.sharding import NamedSharding, PartitionSpec
        _sh_users = NamedSharding(mesh, PartitionSpec("users"))
        _sh_repl = NamedSharding(mesh, PartitionSpec())

        def shard(x):
            return lax.with_sharding_constraint(x, _sh_users)

        def repl(x):
            return lax.with_sharding_constraint(x, _sh_repl)

        def place(x):       # carry/dyn leaves: per-user iff (n_arr,)-led
            if getattr(x, "ndim", 0) >= 1 and x.shape[0] == n_arr:
                return shard(x)
            return repl(x)

        def constrain_state(s2):
            # pin the scan carry's layout at the end of every slot so
            # GSPMD keeps per-user leaves sharded and the scheduler
            # scalars replicated across chunks — without this the
            # partitioner may pick a gather-heavy layout for the carry
            kw = {fld: shard(getattr(s2, fld)) for fld in _PER_USER_FIELDS}
            for fld in ("version", "in_flight", "round_open", "Q", "H",
                        "sum_Q", "sum_H", "corun_updates", "rng_key"):
                kw[fld] = repl(getattr(s2, fld))
            kw["carry"] = jax.tree.map(place, s2.carry)
            kw["agg_carry"] = jax.tree.map(place, s2.agg_carry)
            kw["dyn"] = jax.tree.map(place, s2.dyn)
            ev = s2.events
            if ev is not None:
                ev = PushBuffer(repl(ev.rows), repl(ev.count))
            kw["events"] = ev
            return EngineState(**kw)
    else:
        shard = repl = place = None

    def simulate(tables, app_sched, app_choice, scalars, pol_ops, agg_ops,
                 dyn_ops, t0, state):
        PT, TT, PI, PS, P_APP, P_COR, T_COR, SRATE = tables
        (V, L_b, epsilon, eta, beta, v_norm0, t_d, ready_delay,
         offline_window, offline_resolution, fp_zero) = scalars
        f = PT.dtype
        i = jnp.asarray(0).dtype     # default int dtype (honors x64)
        ar = jnp.arange(n_arr)
        sched_c = lax.dynamic_slice(app_sched, (t0, 0), (chunk, n_arr))
        choice_c = lax.dynamic_slice(app_choice, (t0, 0), (chunk, n_arr))
        ts = t0 + jnp.arange(chunk)
        # the per-user app tables as loop-invariant (n_arr,) app columns:
        # the slot step selects each user's entry by app id from these
        # (a where-chain, exact), never a per-element gather
        app_cols = [[tab[:, k] for k in range(tab.shape[-1])]
                    for tab in (T_COR, P_APP, P_COR)]

        if n_arr == n:
            def pad_users(x, fill):
                return x
        else:
            def pad_users(x, fill):
                ext = jnp.full(x.shape[:-1] + (n_arr - n,), fill, x.dtype)
                return jnp.concatenate([x, ext], axis=-1)

        # sv.repl / dv.repl: hooks pin a float-reduction OPERAND with
        # this before summing it, so GSPMD cannot pull the reduction
        # sharded through a downstream sharded consumer (a shard-local
        # partial sum + AllReduce reassociates the floats and flips
        # low bits of e.g. Eq. 16's gap_sum). Identity when unsharded.
        repl_pin = repl if mesh is not None else (lambda x: x)

        def _write_pushes(buf, agg_carry, count, kfin, fin, users, pulled,
                          corun, t, version):
            # compact the finishers to the front in user order: a stable
            # sort on "not finished" carries each row's inputs along as
            # payloads, so no per-user gather or scatter over n follows
            _, users, pulled, corun = lax.sort(
                (jnp.logical_not(fin).astype(jnp.int8), users, pulled,
                 corun), num_keys=1, is_stable=True)

            def block(j, c):
                buf, agg_carry = c
                # the last window slides back inside the n_arr sorted
                # users; the rows it repeats are the same values again
                lo = jnp.minimum(j * K, n_arr - K)
                u, pa, co = (lax.dynamic_slice(x, (lo,), (K,))
                             for x in (users, pulled, corun))
                rank = lo + jnp.arange(K)
                if policy.sync_rounds:
                    lag = version - pa
                    vn = _jax_trace_v_norm(v_norm0, version, jnp, fp_zero)
                else:
                    vers = version + rank
                    lag = vers - pa
                    vn = _jax_trace_v_norm(v_norm0, vers, jnp, fp_zero)
                gap = _jax_gradient_gap(vn, lag, eta, beta)
                if policy.sync_rounds:
                    # FedAvg rounds average; no per-push weight
                    w = jnp.ones((K,), f)
                else:
                    pv = SimpleNamespace(
                        jnp=jnp, lax=lax, jax=jax, float_dtype=f,
                        lag=lag, gap=gap, v_norm=vn, users=u,
                        consts=agg_ops)
                    agg_carry, w = agg.scan_weight(agg_carry, pv)
                    w = jnp.broadcast_to(w, (K,))
                rows = jnp.stack(
                    [jnp.broadcast_to(t, (K,)).astype(f), u.astype(f),
                     lag.astype(f), gap.astype(f), co.astype(f),
                     w.astype(f)], axis=1)
                # rows ranked past kfin land past the new count, where
                # the next pushing slot (or nothing) overwrites them; a
                # start past capacity clamps into the K slack rows
                return lax.dynamic_update_slice(buf, rows, (count + lo, 0)), \
                    agg_carry

            return lax.fori_loop(0, (kfin + K - 1) // K, block,
                                 (buf, agg_carry))

        def step(s, xs):
            srow, crow, t = xs
            if pad:
                # padded tail slots skip the WHOLE step — state, rng
                # chains and queues stay exactly where slot T-1 left
                # them, matching the host engines' T-slot histories
                return lax.cond(
                    t < T, _live_step,
                    lambda s, *_: (s, (s.Q, s.H, jnp.sum(s.energy))),
                    s, srow, crow, t)
            return _live_step(s, srow, crow, t)

        def _live_step(s, srow, crow, t):
            mode, cooldown, app, app_rem = s.mode, s.cooldown, s.app, \
                s.app_rem
            train_rem, corun, idle_gap = s.train_rem, s.corun, s.idle_gap
            pulled_at, energy, updates = s.pulled_at, s.energy, s.updates
            version, in_flight = s.version, s.in_flight
            Q, H = s.Q, s.H
            rng_key = s.rng_key
            dyn = s.dyn

            # device dynamics (churn): the traced twin of the host
            # transition, FIRST in the slot like the other engines; the
            # dynamics rng draw precedes the policy's so the key chain
            # matches the host engines bit for bit
            if dyn_active:
                with jax.named_scope("slot.dynamics"):
                    # dv.n is the LIVE user count — hooks draw at it and pad
                    # via dv.pad_users so the threefry stream matches the
                    # host engines at any padding (dv.n_arr == n unsharded)
                    dv = SimpleNamespace(jnp=jnp, jax=jax, lax=lax, n=n,
                                         n_arr=n_arr, pad_users=pad_users,
                                         repl=repl_pin,
                                         float_dtype=f, int_dtype=i,
                                         rng_key=rng_key, mode=mode,
                                         corun=corun, t_d=t_d, fp_zero=fp_zero,
                                         consts=dyn_ops)
                    dyn, eff = dynamics.scan_step(dyn, dv)
                    rng_key = dv.rng_key
                    up = eff.up
                    wd, wu = eff.went_down, eff.went_up
                    net_extra = eff.net_extra
                    dwait = wd & (mode == MODE_WAIT)
                    dtrain = wd & (mode == MODE_TRAIN)
                    dcool = wd & (mode == MODE_COOL)
                    departures = jnp.sum(dwait)
                    if dyn_lose:
                        mode = jnp.where(dwait | dtrain | dcool, MODE_OFF,
                                         mode)
                        train_rem = jnp.where(dtrain, 0.0, train_rem)
                        in_flight = in_flight - jnp.sum(dtrain)
                    else:       # resume: paused, pays the extra seconds
                        mode = jnp.where(dwait | dcool, MODE_OFF, mode)
                        train_rem = jnp.where(dtrain,
                                              train_rem + eff.resume_penalty,
                                              train_rem)
                    ret = wu & (mode == MODE_OFF)
                    mode = jnp.where(ret, MODE_COOL, mode)
                    cooldown = jnp.where(ret, ready_delay + net_extra,
                                         cooldown)

            # apps
            with jax.named_scope("slot.apps"):
                has_app0 = app >= 0
                new_app = srow & ~has_app0
                app_rem = jnp.where(has_app0, app_rem - t_d, app_rem)
                ended = has_app0 & (app_rem <= 0.0)
                app = jnp.where(ended, -1, app)
                app_rem = jnp.where(ended, 0.0, app_rem)
                app = jnp.where(new_app, crow, app)
                aid = jnp.maximum(app, 0)
                sel = [cols[0] for cols in app_cols]
                for k in range(1, len(app_cols[0])):
                    is_k = aid == k
                    sel = [jnp.where(is_k, cols[k], g)
                           for cols, g in zip(app_cols, sel)]
                tcor_g, papp_g, pcor_g = sel
                app_rem = jnp.where(new_app, tcor_g, app_rem)

            # cooldown -> waiting
            with jax.named_scope("slot.cooldown"):
                cooling = mode == MODE_COOL
                cooldown = jnp.where(cooling, cooldown - 1, cooldown)
                to_wait = cooling & (cooldown <= 0)
                mode = jnp.where(to_wait, MODE_WAIT, mode)
                plan = jnp.where(to_wait, PLAN_HOLD, s.plan)
                arrivals = jnp.sum(to_wait)
                waiting = mode == MODE_WAIT
                has_app = app >= 0

            # decisions: the policy's carry hook, on a mutable slot view.
            # Under a mesh every hook input (and the carry) is constrained
            # REPLICATED first: the hook's cross-user float reductions —
            # Eq. 16's gap_sum driving H, the online slow path's in-slot
            # replay — then compile to the single-device reduction order,
            # so Alg. 2 decisions are bit-identical to the unsharded scan
            # (a shard-local partial sum + AllReduce would reassociate
            # them). The engine keeps its own sharded views of the same
            # arrays for the surrounding per-user phases.
            with jax.named_scope("slot.policy"):
                if mesh is None:
                    pol_carry = s.carry
                    sv_waiting, sv_has_app, sv_app = waiting, has_app, app
                    sv_updates, sv_plan, sv_idle = updates, plan, idle_gap
                    sv_pcor, sv_papp, sv_tcor = pcor_g, papp_g, tcor_g
                    sv_PT, sv_TT, sv_PI, sv_PS = PT, TT, PI, PS
                else:
                    pol_carry = jax.tree.map(repl, s.carry)
                    sv_waiting, sv_has_app, sv_app = \
                        repl(waiting), repl(has_app), repl(app)
                    sv_updates, sv_plan, sv_idle = \
                        repl(updates), repl(plan), repl(idle_gap)
                    sv_pcor, sv_papp, sv_tcor = \
                        repl(pcor_g), repl(papp_g), repl(tcor_g)
                    sv_PT, sv_TT, sv_PI, sv_PS = \
                        repl(PT), repl(TT), repl(PI), repl(PS)
                sv = SimpleNamespace(
                    jnp=jnp, lax=lax, jax=jax, n=n, T=T,
                    n_arr=n_arr, pad_users=pad_users, repl=repl_pin,
                    float_dtype=f, int_dtype=i, t=t,
                    waiting=sv_waiting, has_app=sv_has_app, app=sv_app,
                    updates=sv_updates,
                    pcor_g=sv_pcor, papp_g=sv_papp, tcor_g=sv_tcor,
                    PT=sv_PT, TT=sv_TT, PI=sv_PI, PS=sv_PS,
                    T_COR=T_COR, SRATE=SRATE,
                    app_sched=app_sched, app_choice=app_choice,
                    plan=sv_plan, idle_gap=sv_idle, in_flight=in_flight,
                    version=version, round_open=s.round_open, Q=Q, H=H,
                    rng_key=rng_key,
                    V=V, L_b=L_b, epsilon=epsilon, eta=eta, beta=beta,
                    v_norm0=v_norm0, t_d=t_d, fp_zero=fp_zero,
                    offline_window=offline_window,
                    offline_resolution=offline_resolution,
                    consts=pol_ops, statics=statics)
                carry, (start, gap_sum) = policy.scan_step(pol_carry, sv)
                idle_gap = sv.idle_gap
                round_open = sv.round_open
                plan = sv.plan
                rng_key = sv.rng_key
                if mesh is not None:
                    # hook outputs return to the sharded layout for the
                    # per-user phases below. The inner repl() pin is load-
                    # bearing: without it GSPMD back-propagates the sharded
                    # consumer layout INTO the hook graph, reassociating its
                    # float reductions (Eq. 16's gap_sum) and partitioning
                    # its lax.scan bodies — the hook must compute fully
                    # replicated to stay bit-identical to the unsharded scan
                    start = shard(repl(start))
                    idle_gap = shard(repl(idle_gap))
                    plan = shard(repl(plan))
                    carry = jax.tree.map(lambda x: place(repl(x)), carry)
                served = jnp.sum(start)

            # begin training
            with jax.named_scope("slot.train"):
                mode = jnp.where(start, MODE_TRAIN, mode)
                corun = jnp.where(start, has_app, corun)
                train_rem = jnp.where(start, jnp.where(has_app, tcor_g, TT),
                                      train_rem)
                pulled_at = jnp.where(start, version, pulled_at)
                in_flight = in_flight + served

                # training progression (a down "resume" trainer is paused)
                training = (mode == MODE_TRAIN) & up if dyn_active \
                    else mode == MODE_TRAIN
                train_rem = jnp.where(training, train_rem - t_d, train_rem)
                fin = training & (train_rem <= 0.0)
                kfin = jnp.sum(fin)
                updates = updates + fin
                mode = jnp.where(fin, MODE_COOL, mode)
                cooldown = jnp.where(fin, ready_delay + net_extra if dyn_active
                                     else ready_delay, cooldown)
                idle_gap = jnp.where(fin, 0.0, idle_gap)
                in_flight = in_flight - kfin
                corun_updates = s.corun_updates + jnp.sum(fin & corun)

            # push events: one fixed-width row per finisher at the buffer
            # cursor, in user-index order within the slot (the loop
            # oracle's push order), written as ceil(kfin / K) contiguous
            # blocks of K rows — and nothing at all in a slot where nobody
            # finishes. count stays exact so the host loop can detect
            # overflow and retry
            events = s.events
            agg_carry = s.agg_carry
            if collect:
                with jax.named_scope("slot.push_log"):
                    # the phase runs REPLICATED under a mesh (pads never
                    # finish, so the ranks and the buffer cursor match the
                    # unsharded scan; the buffer itself is a replicated
                    # carry leaf) — cheap, since only (n,) vectors and the
                    # O(capacity) buffer are involved, never the big state
                    if mesh is None:
                        fin_e, corun_e, pulled_e, ar_e = fin, corun, \
                            pulled_at, ar
                    else:
                        fin_e, corun_e, pulled_e, ar_e = \
                            repl(fin), repl(corun), repl(pulled_at), repl(ar)
                        agg_carry = jax.tree.map(repl, agg_carry)
                    buf, agg_carry = lax.cond(
                        kfin > 0, _write_pushes,
                        lambda buf, agg_carry, *_: (buf, agg_carry),
                        events.rows, agg_carry, events.count, kfin, fin_e,
                        ar_e, pulled_e, corun_e, t, version)
                    if mesh is not None:
                        agg_carry = jax.tree.map(place, agg_carry)
                    events = PushBuffer(buf, events.count + kfin)

            with jax.named_scope("slot.train"):
                if policy.sync_rounds:
                    closed = round_open & (jnp.sum(mode == MODE_TRAIN) == 0)
                    version = version + closed
                    round_open = round_open & ~closed
                else:
                    version = version + kfin

            # energy (Eq. 10)
            with jax.named_scope("slot.energy"):
                training = mode == MODE_TRAIN
                p = jnp.where(training,
                              jnp.where(has_app, pcor_g, PT),
                              jnp.where(has_app, papp_g, PI))
                if overhead and policy.uses_online_queue:
                    p = jnp.where(mode == MODE_WAIT, p + (PS - PI), p)
                if dyn_active:     # a down device draws nothing
                    p = jnp.where(up, p, 0.0)
                # + fp_zero: round p*t_d before accumulating, as the host does
                # (fma contraction would skip it — see _jax_trace_v_norm)
                energy = energy + (p * t_d + fp_zero)

            # queues (Eqs. 15-16; departures extend Eq. 15 under churn)
            with jax.named_scope("slot.queues"):
                if dyn_active:
                    Q = jnp.maximum(Q - served - departures, 0.0) + arrivals
                else:
                    Q = jnp.maximum(Q - served, 0.0) + arrivals
                H = jnp.maximum(H + gap_sum - L_b, 0.0)
                s2 = EngineState(
                    mode=mode, cooldown=cooldown, app=app, app_rem=app_rem,
                    train_rem=train_rem, corun=corun, idle_gap=idle_gap,
                    pulled_at=pulled_at, energy=energy, updates=updates,
                    plan=plan, version=version, in_flight=in_flight,
                    round_open=round_open, Q=Q, H=H,
                    sum_Q=s.sum_Q + Q, sum_H=s.sum_H + H,
                    corun_updates=corun_updates, rng_key=rng_key,
                    carry=carry, agg_carry=agg_carry, dyn=dyn, events=events)
                if mesh is not None:
                    s2 = constrain_state(s2)
                return s2, (Q, H, jnp.sum(energy))

        return lax.scan(step, state, (sched_c, choice_c, ts))

    if batch:
        # the sweep path: one program advances `batch` stacked configs —
        # every operand carries a leading config axis except t0 (the
        # chunk cursor, shared by the whole batch)
        return jax.jit(jax.vmap(simulate,
                                in_axes=(0, 0, 0, 0, 0, 0, 0, None, 0)))
    return jax.jit(simulate)


def _state_to_np(es: EngineState, jax, f, i) -> EngineState:
    """Engine-dtype twin of a host EngineState with NUMPY leaves: floats
    to the run's float dtype (honors x64), ints to the default int
    dtype, bools and the uint32 rng key as-is; the policy carry pytree
    converts leaf-wise. The driver device-puts the whole pytree in one
    ``tree.map`` — the sweep path stacks B of these host-side first, so
    a 100-config batch costs one transfer per leaf, not 100."""
    def cast(x):
        a = np.asarray(x)
        if a.dtype == np.bool_ or a.dtype == np.uint32:
            return a
        if np.issubdtype(a.dtype, np.floating):
            return np.asarray(a, f)
        return np.asarray(a, i)

    return EngineState(
        mode=cast(es.mode), cooldown=cast(es.cooldown), app=cast(es.app),
        app_rem=cast(es.app_rem), train_rem=cast(es.train_rem),
        corun=cast(es.corun), idle_gap=cast(es.idle_gap),
        pulled_at=cast(es.pulled_at), energy=cast(es.energy),
        updates=cast(es.updates), plan=cast(es.plan),
        version=cast(es.version), in_flight=cast(es.in_flight),
        round_open=cast(es.round_open), Q=cast(es.Q), H=cast(es.H),
        sum_Q=cast(es.sum_Q), sum_H=cast(es.sum_H),
        corun_updates=cast(es.corun_updates), rng_key=cast(es.rng_key),
        carry=jax.tree.map(cast, es.carry),
        agg_carry=jax.tree.map(cast, es.agg_carry),
        dyn=jax.tree.map(cast, es.dyn), events=None)


def _state_to_host(state: EngineState, jax) -> EngineState:
    """Host (numpy) twin of the final device EngineState: arrays come
    back as numpy, scalars as python — so ``sim.state`` reads the same
    after a jax run as after a loop/vectorized one."""
    return EngineState(
        mode=np.asarray(state.mode), cooldown=np.asarray(state.cooldown),
        app=np.asarray(state.app), app_rem=np.asarray(state.app_rem),
        train_rem=np.asarray(state.train_rem),
        corun=np.asarray(state.corun), idle_gap=np.asarray(state.idle_gap),
        pulled_at=np.asarray(state.pulled_at),
        energy=np.asarray(state.energy), updates=np.asarray(state.updates),
        plan=np.asarray(state.plan),
        version=int(state.version), in_flight=int(state.in_flight),
        round_open=bool(state.round_open),
        Q=float(state.Q), H=float(state.H),
        sum_Q=float(state.sum_Q), sum_H=float(state.sum_H),
        corun_updates=int(state.corun_updates),
        rng_key=np.asarray(state.rng_key),
        carry=jax.tree.map(np.asarray, state.carry),
        agg_carry=jax.tree.map(np.asarray, state.agg_carry),
        dyn=jax.tree.map(np.asarray, state.dyn), events=None)


def _next_pow2(k: int) -> int:
    c = 1
    while c < k:
        c <<= 1
    return c


@dataclasses.dataclass(frozen=True)
class ScanChunk:
    """The scan chunk a jax run dispatched last (``sim.scan_chunk``): the
    jitted chunk, the shapes, dtypes and shardings of the operands it ran
    on (no device buffers), the chunk's length in slots and the push
    buffer's capacity it was built for."""

    fn: object
    operands: tuple
    chunk: int
    cap: int

    def hlo_text(self) -> str:
        """The optimized HLO text of that chunk, compiled again for those
        operands (the compile cache usually holds it)."""
        return self.fn.lower(*self.operands).compile().as_text()


def _abstract(tree, jax):
    """Each array leaf of ``tree`` as a ``ShapeDtypeStruct``."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=getattr(x, "sharding", None),
            weak_type=getattr(getattr(x, "aval", None), "weak_type",
                              False)), tree)


def _jax_run_setup(sim, jax, jnp, n_devices: int = 1):
    """HOST (numpy) operands + engine-dtype state for one sim, shared by
    the per-point path (`_run_jax`) and the batched sweep path
    (`run_jax_sweep`). Everything stays numpy here on purpose: the
    per-point path device-puts each leaf once via `_ops_to_device`,
    while the sweep path first np.stacks B of these along a config axis
    and THEN converts — so a B-config sweep pays one transfer per leaf,
    not B (host->device dispatch, not the vmapped scan, dominated sweep
    wall-clock before this). Arrivals are padded host-side to a whole
    number of ``jax_chunk`` chunks so an uneven horizon reuses the
    full-chunk executable — the scan skips padded slots (t >= T).
    ``jax_chunk=0`` resolves the chunk (and, for a sharded run without
    an explicit ``push_log_capacity``, the push-buffer size) against the
    per-device memory budget (core/autotune.py); ``n_devices`` is the
    LIVE mesh size the caller resolved, 1 for unsharded runs."""
    cfg = sim.cfg
    n = cfg.n_users
    T = n_slots(cfg)
    collect = cfg.collect_push_log
    f = jnp.zeros(0).dtype          # honors jax_enable_x64
    i = jnp.asarray(0).dtype        # (jax dtypes ARE numpy dtypes)
    tables = tuple(np.asarray(a, f) for a in _user_tables(sim))
    tune = None
    jax_chunk = cfg.jax_chunk
    if jax_chunk == 0 or (n_devices > 1 and collect
                          and not cfg.push_log_capacity):
        from .autotune import autotune_scan_params
        tune = autotune_scan_params(sim, n_devices=n_devices)
        if jax_chunk == 0:
            jax_chunk = tune.jax_chunk
    chunk = min(jax_chunk, T) if T else 0
    n_chunks = -(-T // chunk) if T else 0
    sched = np.asarray(sim.app_sched[:T])
    choice = np.asarray(sim.app_choice[:T], np.int32)
    T_pad = n_chunks * chunk
    if T_pad > T:
        sched = np.concatenate(
            [sched, np.zeros((T_pad - T, n), sched.dtype)])
        choice = np.concatenate(
            [choice, np.zeros((T_pad - T, n), choice.dtype)])
    # fp_zero: a runtime-opaque 0.0 the scan adds to products that the
    # host engines round before accumulating — defeats XLA's fma
    # contraction, which would skip that rounding (see _jax_trace_v_norm)
    scalars = tuple(np.asarray(s, f) for s in (
        cfg.V, cfg.L_b, cfg.epsilon, cfg.eta, cfg.beta, cfg.v_norm0,
        cfg.t_d)) + (np.asarray(cfg.ready_delay, i),) + tuple(
        np.asarray(s, f) for s in (cfg.offline_window,
                                   cfg.offline_resolution)) + (
        np.asarray(0.0, f),)
    pol_ops = tuple(np.asarray(v) for v in sim.policy.scan_operands(cfg))
    agg_ops = tuple(np.asarray(v) for v in sim.agg.scan_operands(cfg))
    # dynamics knobs: floats in the run's float dtype (f64 parity with
    # the host transition under x64), ints in the default int dtype
    dyn_ops = tuple(
        np.asarray(v, f) if isinstance(v, float) else np.asarray(v)
        for v in sim.dynamics.scan_operands(cfg)) \
        if sim.dynamics.active else ()
    # initial per-chunk event capacity; an overflowing chunk is re-run
    # from its saved entry state with a doubled buffer, so the guess
    # only costs (rare) recompiles, never correctness. The legacy
    # max(1024, 2n) guess is a ~960 MB replicated buffer at n=10M, so
    # sharded runs (and jax_chunk=0 runs) take the tuner's rate-based
    # capacity instead.
    if not collect:
        cap = 0
    elif cfg.push_log_capacity:
        cap = _next_pow2(cfg.push_log_capacity)
    elif tune is not None:
        cap = tune.push_capacity
    else:
        cap = _next_pow2(max(1024, 2 * n))
    return SimpleNamespace(
        n=n, T=T, chunk=chunk, n_chunks=n_chunks, collect=collect,
        f=f, i=i, tables=tables, app_sched=sched,
        app_choice=choice, scalars=scalars, pol_ops=pol_ops,
        agg_ops=agg_ops, dyn_ops=dyn_ops,
        statics=tuple(sim.policy.scan_statics(cfg)),
        overhead=cfg.include_scheduler_overhead, cap=cap,
        state=_state_to_np(sim.state, jax, f, i))


def _ops_to_device(rs, jax, jnp):
    """Device-put a `_jax_run_setup` namespace in place: exactly one
    transfer per operand leaf, whether the leaves are unbatched or
    already np.stacked along a config axis. jax canonicalizes dtypes on
    the way in (f64 -> f32 when x64 is off), matching what tracing the
    host values directly used to produce."""
    dev = lambda tree: jax.tree.map(jnp.asarray, tree)
    rs.tables = dev(rs.tables)
    rs.app_sched = jnp.asarray(rs.app_sched)
    rs.app_choice = jnp.asarray(rs.app_choice)
    rs.scalars = dev(rs.scalars)
    rs.pol_ops = dev(rs.pol_ops)
    rs.agg_ops = dev(rs.agg_ops)
    rs.dyn_ops = dev(rs.dyn_ops)
    rs.state = dev(rs.state)
    return rs


def _pad_setup(rs, n_arr, sim):
    """Host-pad a `_jax_run_setup` namespace from ``n`` to ``n_arr``
    users (a multiple of the mesh size) with INERT rows: zero catalog
    rows (zero idle power -> zero energy), all-False arrival columns,
    MODE_OFF state rows, and the dynamics' own ``pad_state`` rows
    (pinned up/on forever, so pads never enter the queues, never push,
    never draw energy — property-tested in tests/test_sharded_sim.py)."""
    n = rs.n
    if n_arr == n:
        return rs
    k = n_arr - n

    def pad_rows(a):
        a = np.asarray(a)
        return np.concatenate([a, np.zeros((k,) + a.shape[1:], a.dtype)])

    def pad_cols(a):
        a = np.asarray(a)
        return np.concatenate(
            [a, np.zeros(a.shape[:1] + (k,), a.dtype)], axis=1)

    rs.tables = tuple(pad_rows(t) for t in rs.tables)
    rs.app_sched = pad_cols(rs.app_sched)
    rs.app_choice = pad_cols(rs.app_choice)
    dyn_rows = sim.dynamics.pad_state(k) if sim.dynamics.active else None
    if sim.dynamics.active and dyn_rows is None:
        raise ValueError(
            f"{type(sim.dynamics).__name__} has no pad_state recipe; "
            "sharded runs need one when n_users is not a multiple of the "
            "mesh size (or pick n_users divisible by n_devices)")
    rs.state = pad_state_per_user(rs.state, n_arr, dyn_rows=dyn_rows)
    return rs


def _mesh_ops_to_device(rs, mesh, n_arr, jax, jnp):
    """Device-put a (padded) `_jax_run_setup` namespace onto the
    ``("users",)`` mesh: catalog tables shard along their leading user
    axis, arrival operands along their user COLUMN (axis 1), scheduler
    scalars and hook operand tuples replicate, and the EngineState
    pytree lands leaf-wise per ``state_shardings`` — one sharded
    transfer per leaf, so the first chunk starts with every operand
    already laid out and XLA inserts no resharding prologue."""
    from jax.sharding import NamedSharding, PartitionSpec

    x64 = jax.config.jax_enable_x64

    def canon(x):       # jnp.asarray's dtype canonicalization, host-side
        a = np.asarray(x)
        if not x64 and a.dtype.itemsize == 8 and a.dtype.kind in "fiu":
            a = a.astype({"f": np.float32, "i": np.int32,
                          "u": np.uint32}[a.dtype.kind])
        return a

    sh_users = NamedSharding(mesh, PartitionSpec("users"))
    sh_cols = NamedSharding(mesh, PartitionSpec(None, "users"))
    sh_repl = NamedSharding(mesh, PartitionSpec())

    def put(x, sh):
        return jax.device_put(canon(x), sh)

    def repl_tree(tree):
        return jax.tree.map(lambda x: put(x, sh_repl), tree)

    rs.tables = tuple(put(t, sh_users) for t in rs.tables)
    rs.app_sched = put(rs.app_sched, sh_cols)
    rs.app_choice = put(rs.app_choice, sh_cols)
    rs.scalars = repl_tree(rs.scalars)
    rs.pol_ops = repl_tree(rs.pol_ops)
    rs.agg_ops = repl_tree(rs.agg_ops)
    rs.dyn_ops = repl_tree(rs.dyn_ops)
    shardings = state_shardings(rs.state, mesh, n_arr)
    rs.state = jax.tree.map(lambda x, sh: put(x, sh),
                            rs.state, shardings)
    rs.repl_sharding = sh_repl
    return rs


def _run_jax(sim) -> SimResult:
    import jax
    import jax.numpy as jnp

    cfg = sim.cfg
    policy = sim.policy
    agg = sim.agg
    dynamics = sim.dynamics
    from .aggregation import aggregation_support
    from .dynamics import dynamics_support
    if not policy.supports_jax or \
            not dynamics_support(dynamics)["jax"] or \
            (cfg.collect_push_log and not aggregation_support(agg)["jax"]):
        # resolve_engine degrades such runs before they get here; running
        # the host engine under the jax name would hide the device
        raise ValueError(
            f"the jax scan cannot run policy {policy.name!r} with dynamics "
            f"{dynamics.name!r} and aggregation {agg.name!r}; "
            "sim.resolve_engine() picks the engine that can")
    # sharded run: resolve the ("users",) mesh first — the auto-tuner and
    # the user-axis padding both need the LIVE device count. A 1-device
    # mesh degenerates to the plain path (identical graph, no constraint
    # ops to trace through).
    mesh = None
    n_arr = 0
    if cfg.n_devices:
        from ..launch.mesh import make_sim_mesh
        mesh = make_sim_mesh(cfg.n_devices)
        if mesh.devices.size == 1:
            mesh = None
    with TraceAnnotation("scan.setup"):
        rs = _jax_run_setup(sim, jax, jnp,
                            n_devices=mesh.devices.size if mesh else 1)
        if mesh is not None:
            n_arr = pad_to_devices(rs.n, mesh.devices.size)
            rs = _pad_setup(rs, n_arr, sim)
    n, T, chunk, collect, f, i = rs.n, rs.T, rs.chunk, rs.collect, rs.f, rs.i
    cap = rs.cap
    K = _push_block(n_arr or n)

    def fresh_events(c):
        # K slack rows: a push block that starts at or past capacity
        # lands there, never on a row the drain reads
        ev = PushBuffer(jnp.zeros((c + K, 6), f), jnp.asarray(0, i))
        if mesh is not None:    # the buffer is a replicated carry leaf
            ev = PushBuffer(jax.device_put(ev.rows, rs.repl_sharding),
                            jax.device_put(ev.count, rs.repl_sharding))
        return ev

    with TraceAnnotation("scan.to_device"):
        if mesh is not None:
            rs = _mesh_ops_to_device(rs, mesh, n_arr, jax, jnp)
        else:
            rs = _ops_to_device(rs, jax, jnp)
        state = rs.state
        if collect:
            state = state.replace(events=fresh_events(cap))

    log = PushLog()
    qs_parts, hs_parts, e_parts = [], [], []
    ci = 0
    while ci < rs.n_chunks:
        t0 = ci * chunk
        m = min(chunk, T - t0)          # live slots (tail chunk is padded)
        with TraceAnnotation("scan.chunk", t0=t0, live_slots=m, cap=cap):
            fn = _jax_chunk_fn(n, chunk, T, policy, rs.overhead, collect,
                               cap, rs.statics, agg, dynamics, mesh=mesh,
                               n_arr=n_arr)
            prev = state
            operands = (rs.tables, rs.app_sched, rs.app_choice, rs.scalars,
                        rs.pol_ops, rs.agg_ops, rs.dyn_ops,
                        jnp.asarray(t0, i), state)
            state, (qs, hs, esum) = fn(*operands)
        # the host waits here while the device runs the chunk
        with TraceAnnotation("scan.wait"):
            if collect:
                cnt = int(state.events.count)
            else:
                qs.block_until_ready()
        if collect:
            if cnt > cap:
                # buffer overflow: double and re-run this chunk from its
                # saved entry state (count is exact, rows past cap dropped)
                cap = _next_pow2(cnt)
                with TraceAnnotation("scan.overflow", cap=cap):
                    state = prev.replace(events=fresh_events(cap))
                continue
            with TraceAnnotation("scan.drain", pushes=cnt) as span:
                rows = np.asarray(state.events.rows[:cnt]) if cnt \
                    else np.zeros((0, 6))
                log.extend_rows(rows)
                span.set_metadata(**_drain_counts([rows[:, 0]], K))
                cnt0 = jnp.asarray(0, i)
                if mesh is not None:
                    cnt0 = jax.device_put(cnt0, rs.repl_sharding)
                state = state.replace(events=PushBuffer(state.events.rows,
                                                        cnt0))
        with TraceAnnotation("scan.traces"):
            qs_parts.append(np.asarray(qs, dtype=float)[:m])
            hs_parts.append(np.asarray(hs, dtype=float)[:m])
            e_parts.append(np.asarray(esum, dtype=float)[:m])
        ci += 1

    with TraceAnnotation("scan.finish") as span:
        # where the scan's per-user carry and arrival operands actually lived
        placement = {
            name: (tuple(sorted(d.id for d in x.sharding.device_set)),
                   x.sharding.shard_shape(x.shape))
            for name, x in (("state.mode", state.mode),
                            ("state.energy", state.energy),
                            ("app_sched", rs.app_sched))}
        # the run's final state, readable on the host like the other engines'
        host = _state_to_host(state, jax)
        if mesh is not None and n_arr != n:
            host = unpad_state_per_user(host, n)     # pad rows are all-zero
        sim.state = host
        if mesh is None:
            energy_total = float(jnp.sum(state.energy))
        else:
            # device reduction order differs across shards anyway; sum the
            # unpadded host rows (pads contribute exact 0.0 either way)
            energy_total = float(np.sum(host.energy))
        updates_total = int(np.sum(host.updates))
        sum_Q, sum_H = float(state.sum_Q), float(state.sum_H)
        corun_updates = int(state.corun_updates)
        churn = dynamics.counters(host.dyn)
        if dynamics.active:
            span.set_metadata(**churn)
        if rs.n_chunks:
            sim.scan_chunk = ScanChunk(fn, _abstract(operands, jax), chunk,
                                       cap)
        idx = np.arange(0, T, cfg.trace_every)
        if qs_parts:
            qs = np.concatenate(qs_parts)
            hs = np.concatenate(hs_parts)
            es = np.concatenate(e_parts)
        else:
            qs = hs = es = np.zeros(0)
        return SimResult(
            energy_j=energy_total,
            updates=updates_total,
            trace_t=idx.copy(), trace_energy=es[idx],
            trace_Q=qs[idx], trace_H=hs[idx],
            push_log=log, accuracy=[],
            mean_Q=sum_Q / T if T else 0.0,
            mean_H=sum_H / T if T else 0.0,
            corun_fraction=corun_updates / max(updates_total, 1),
            placement=placement, **churn)


# ======================================================================
# Batched sweeps: one vmapped program advances B stacked scenarios
# ======================================================================
def sweep_bucket_key(sim):
    """Shared-executable bucket key for the batched sweep path, or None
    when this sim can't join a vmapped batch: real-ML hooks/backends, an
    explicit ``engine="loop"`` request, a policy or dynamics without jax
    + vmap support (the offline policy's host knapsack ``pure_callback``
    would fire for every config at every slot under vmapped ``cond``),
    or a push log wanted without a jax-capable aggregation rule. Sims
    with equal keys share ONE jitted program — the key mirrors
    ``_jax_chunk_fn``'s memo key, so everything per-config (V, L_b,
    ``scan_operands``, arrival draws, seeds) stays traced and batched."""
    from .aggregation import aggregation_support
    from .dynamics import dynamics_support
    cfg = sim.cfg
    policy, agg, dynamics = sim.policy, sim.agg, sim.dynamics
    if sim.ml or sim.ml_backend is not None or cfg.engine == "loop":
        return None
    if cfg.n_devices or cfg.jax_chunk == 0:
        # sharded sims run per-point — the mesh IS the parallelism, and
        # an auto-tuned chunk (jax_chunk=0) resolves against the live
        # device set at run time, not against a bucket
        return None
    if not (policy.supports_jax and getattr(policy, "supports_vmap", True)):
        return None
    if not (dynamics_support(dynamics)["jax"]
            and getattr(dynamics, "supports_vmap", True)):
        return None
    collect = cfg.collect_push_log
    if collect and not (aggregation_support(agg)["jax"]
                        and getattr(agg, "supports_vmap", True)):
        return None
    n = cfg.n_users
    T = n_slots(cfg)
    if not T:
        return None
    cap = _next_pow2(cfg.push_log_capacity or max(1024, 2 * n)) \
        if collect else 0
    return (n, min(cfg.jax_chunk, T), T, cfg.n_devices,
            policy.jax_cache_key(),
            cfg.include_scheduler_overhead, collect, cap,
            tuple(policy.scan_statics(cfg)),
            agg.jax_cache_key() if collect else None,
            dynamics.jax_cache_key() if dynamics.active else None)


def run_jax_sweep(sims) -> List[SimResult]:
    """Run constructed FederatedSims that share a ``sweep_bucket_key``
    as ONE vmapped jitted program: per-config operands and EngineStates
    stack along a leading config axis, the chunked scan advances all of
    them together, and each row decodes back to an unbatched
    ``SimResult`` (traces, push log, final host state) identical — bit
    for bit on discrete outputs, to float-sum reordering on energies —
    to its per-point ``_run_jax`` run. Push buffers are batched
    ``(B, cap + K, 6)``; if ANY config overflows a chunk, the chunk re-runs
    from its saved entry state with the buffer doubled for every row
    (per-config counts stay exact)."""
    import jax
    import jax.numpy as jnp

    sims = list(sims)
    if not sims:
        return []
    keys = {sweep_bucket_key(s) for s in sims}
    if None in keys or len(keys) != 1:
        raise ValueError(
            "run_jax_sweep needs sims sharing one sweep_bucket_key; got "
            f"{len(keys)} distinct keys (None = jax/vmap-ineligible). "
            "Use core.scenario.run_sweep for bucketing + fallback.")
    cfg = sims[0].cfg
    with TraceAnnotation("sim.run", engine="jax_sweep", n_users=cfg.n_users,
                         slots=n_slots(cfg), batch=len(sims)):
        if len(sims) == 1:
            return [_run_jax(sims[0])]
        return _run_jax_batch(sims, jax, jnp)


def _run_jax_batch(sims, jax, jnp) -> List[SimResult]:
    """``run_jax_sweep``'s vmapped path for two or more sims."""
    B = len(sims)
    policy, agg = sims[0].policy, sims[0].agg
    dynamics = sims[0].dynamics

    # stack HOST-side (the setups are numpy), then device-put the whole
    # batch in one pass — one transfer per leaf, independent of B
    def stack(parts):
        return jax.tree.map(lambda *xs: np.stack(xs), *parts)

    with TraceAnnotation("scan.setup"):
        preps = [_jax_run_setup(s, jax, jnp) for s in sims]
        rs = SimpleNamespace(
            tables=stack([p.tables for p in preps]),
            app_sched=np.stack([p.app_sched for p in preps]),
            app_choice=np.stack([p.app_choice for p in preps]),
            scalars=stack([p.scalars for p in preps]),
            pol_ops=stack([p.pol_ops for p in preps]),
            agg_ops=stack([p.agg_ops for p in preps]),
            dyn_ops=stack([p.dyn_ops for p in preps]),
            state=stack([p.state for p in preps]))
    p0 = preps[0]
    n, T, chunk, collect, f, i = p0.n, p0.T, p0.chunk, p0.collect, \
        p0.f, p0.i
    cap = p0.cap
    K = _push_block(n)
    with TraceAnnotation("scan.to_device"):
        rs = _ops_to_device(rs, jax, jnp)
        state = rs.state
        if collect:
            state = state.replace(events=PushBuffer(
                jnp.zeros((B, cap + K, 6), f), jnp.zeros((B,), i)))
    tables, app_sched, app_choice = rs.tables, rs.app_sched, rs.app_choice
    scalars, pol_ops, agg_ops, dyn_ops = \
        rs.scalars, rs.pol_ops, rs.agg_ops, rs.dyn_ops

    logs = [PushLog() for _ in range(B)]
    qs_parts, hs_parts, e_parts = [], [], []
    ci = 0
    while ci < p0.n_chunks:
        t0 = ci * chunk
        m = min(chunk, T - t0)          # live slots (tail chunk is padded)
        with TraceAnnotation("scan.chunk", t0=t0, live_slots=m, cap=cap):
            fn = _jax_chunk_fn(n, chunk, T, policy, p0.overhead, collect,
                               cap, p0.statics, agg, dynamics, batch=B)
            prev = state
            state, (qs, hs, esum) = fn(tables, app_sched, app_choice,
                                       scalars, pol_ops, agg_ops, dyn_ops,
                                       jnp.asarray(t0, i), state)
        with TraceAnnotation("scan.wait"):
            if collect:
                counts = np.asarray(state.events.count)
            else:
                qs.block_until_ready()
        if collect:
            if int(counts.max()) > cap:
                # any config overflowing re-runs the whole chunk with
                # the buffer doubled for every row (counts stay exact)
                cap = _next_pow2(int(counts.max()))
                with TraceAnnotation("scan.overflow", cap=cap):
                    state = prev.replace(events=PushBuffer(
                        jnp.zeros((B, cap + K, 6), f), jnp.zeros((B,), i)))
                continue
            with TraceAnnotation("scan.drain",
                                 pushes=int(counts.sum())) as span:
                rows = np.asarray(state.events.rows)
                drained = [rows[b, :counts[b]] for b in range(B)]
                for lg, r in zip(logs, drained):
                    lg.extend_rows(r)
                span.set_metadata(**_drain_counts(
                    [r[:, 0] for r in drained], K))
                state = state.replace(events=PushBuffer(
                    state.events.rows, jnp.zeros((B,), i)))
        with TraceAnnotation("scan.traces"):
            qs_parts.append(np.asarray(qs, dtype=float)[:, :m])
            hs_parts.append(np.asarray(hs, dtype=float)[:, :m])
            e_parts.append(np.asarray(esum, dtype=float)[:, :m])
        ci += 1

    with TraceAnnotation("scan.finish"):
        qs = np.concatenate(qs_parts, axis=1)
        hs = np.concatenate(hs_parts, axis=1)
        es = np.concatenate(e_parts, axis=1)
        # per-config energy reduced on device along the user axis, like the
        # per-point path's jnp.sum over (n,)
        energy_rows = np.asarray(jnp.sum(state.energy, axis=1), dtype=float)
        # one bulk device->host transfer for the whole batch, then numpy
        # slicing per row — per-row device slicing cost ~50x more here
        host_all = jax.tree.map(np.asarray, state.replace(events=None))
        results = []
        for b, sim in enumerate(sims):
            host = _state_to_host(jax.tree.map(lambda x: x[b], host_all), jax)
            sim.state = host
            sim._ran = True                 # Scenario.run() re-entrancy flag
            updates_total = int(host.updates.sum())
            idx = np.arange(0, T, sim.cfg.trace_every)
            results.append(SimResult(
                energy_j=float(energy_rows[b]),
                updates=updates_total,
                trace_t=idx.copy(), trace_energy=es[b, idx],
                trace_Q=qs[b, idx], trace_H=hs[b, idx],
                push_log=logs[b], accuracy=[],
                mean_Q=host.sum_Q / T, mean_H=host.sum_H / T,
                corun_fraction=host.corun_updates / max(updates_total, 1),
                **sim.dynamics.counters(host.dyn)))
        return results
