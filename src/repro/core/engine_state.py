"""Unified engine state: ONE explicit state container for all three engines.

Every simulator engine — the per-user loop oracle (``FederatedSim._run_loop``),
the struct-of-arrays numpy engine and the ``jax.lax.scan`` backend
(``core/vector_engine.py``) — threads the same ``EngineState``: the per-user
struct-of-arrays device state, the server/scheduler scalars (version,
in-flight count, the Eq. 15/16 queues Q and H and their running sums), an
RNG key for stochastic policies, the policy's declarative carry pytree
(``Policy.init_carry``), and — on the jax engine — the fixed-width push-event
buffer that streams the push log out of the scan.

``EngineState`` is a registered jax pytree, so the SAME object shape that the
numpy engine mutates in place is the ``lax.scan`` carry on the jax backend
(fields converted to device arrays by ``vector_engine``). ``FederatedSim``
builds one per run (``sim.state``); the loop oracle keeps its readable
per-user ``UserState`` objects as the working view and threads the scalar /
carry fields through this container.

The push log is no longer accumulated as per-push dicts: engines append
fixed-width blocks to a ``PushLog`` (six columns — slot, user, lag, gap,
corun, applied aggregation weight), and the ``SimResult.push_log`` dict
schema is decoded lazily on access, so fleet-scale runs never materialize
O(pushes) Python dicts unless the caller actually walks the log. Inside
the jax scan the same six columns live in a preallocated ``PushBuffer``
``(capacity + K, 6)`` array written in ``K``-row blocks;
``vector_engine`` drains it chunk-by-chunk over the horizon, so peak
memory stays O(chunk), never O(T * n).
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Optional

import numpy as np

# Shared state encodings of all engines (re-exported by core/policies.py).
# MODE_OFF is the device-dynamics parking state (core/dynamics.py): a user
# whose device churned off; it draws no power and re-enters the arrival
# process through cooldown when it comes back up.
MODE_WAIT, MODE_TRAIN, MODE_COOL, MODE_OFF = 0, 1, 2, 3
PLAN_HOLD, PLAN_CORUN, PLAN_SEP = 0, 1, 2

# Column order of the fixed-width push-event records (PushBuffer rows and
# PushLog blocks). ``weight`` is the aggregation rule's applied mixing
# weight (core/aggregation.py) — 1.0 under the paper's replace rule.
EVENT_FIELDS = ("t", "user", "lag", "gap", "corun", "weight")


class PushBuffer(NamedTuple):
    """Fixed-width in-scan event buffer: ``rows`` is ``(capacity + K, 6)``
    in ``EVENT_FIELDS`` order, ``count`` the number of pushes recorded so
    far (monotone within a chunk). The slot step writes its finishers at
    the cursor ``count`` in contiguous blocks of ``K`` rows; a block that
    starts at or past ``capacity`` lands in the ``K`` slack rows, never on
    a row below ``capacity``, and the host loop detects the overflow as
    ``count > capacity`` and retries the chunk with a doubled buffer.
    NamedTuple => a native jax pytree."""

    rows: Any
    count: Any


@dataclasses.dataclass
class EngineState:
    """The one state pytree threaded through every engine.

    Per-user struct-of-arrays (``(n_users,)`` each): ``mode`` (wait / train /
    cool), ``cooldown`` slots left, current ``app`` id (-1 = none), remaining
    app / training seconds, ``corun`` flag of the current/last training run,
    the accumulated Eq. (12) ``idle_gap``, the global ``pulled_at`` version,
    per-user ``energy`` (J) and ``updates``, and the offline policy's
    ``plan`` code.

    Scheduler / server scalars: global model ``version``, ``in_flight``
    trainer count, the sync-round ``round_open`` flag, the Lyapunov queues
    ``Q`` / ``H`` (Eqs. 15/16) plus their horizon sums, and the co-run
    update counter.

    ``rng_key`` is a raw ``(2,)`` uint32 counter-key (the jax PRNGKey
    layout) derived from ``SimConfig.seed`` — engines thread it untouched;
    stochastic policies may split it inside their carry protocol hooks.

    ``carry`` is the policy's declarative carry pytree
    (``Policy.init_carry``) — e.g. greedy's per-user wait counters or the
    offline policy's next plan slot. ``agg_carry`` is the aggregation
    rule's carry pytree (``AggregationRule.init_carry``,
    core/aggregation.py) — e.g. hetero_aware's per-user device-class
    scales. ``events`` is the jax engine's ``PushBuffer`` (None
    elsewhere).
    """

    # ---- per-user struct-of-arrays -----------------------------------
    mode: Any
    cooldown: Any
    app: Any
    app_rem: Any
    train_rem: Any
    corun: Any
    idle_gap: Any
    pulled_at: Any
    energy: Any
    updates: Any
    plan: Any
    # ---- scheduler / server scalars ----------------------------------
    version: Any = 0
    in_flight: Any = 0
    round_open: Any = False
    Q: Any = 0.0
    H: Any = 0.0
    sum_Q: Any = 0.0
    sum_H: Any = 0.0
    corun_updates: Any = 0
    # ---- rng / policy carry / event stream ---------------------------
    rng_key: Any = None
    carry: Any = None
    agg_carry: Any = None
    dyn: Any = None
    events: Optional[PushBuffer] = None

    @classmethod
    def init(cls, n: int, cfg, policy, agg=None, fleet=None,
             dynamics=None) -> "EngineState":
        """Fresh host-side (numpy) state for an ``n``-user run: everyone
        cooling with zero cooldown (first slot moves the fleet to waiting,
        like the historical engines), no apps, v0 model, empty queues.
        ``agg``/``fleet`` (the run's aggregation rule and FleetSpec)
        initialize the rule carry; ``None`` leaves it empty. ``dynamics``
        (a resolved DeviceDynamics, core/dynamics.py) initializes the
        per-user churn state ``dyn``; ``None`` or an inactive dynamics
        leaves it empty. All per-user arrays are shape-checked against
        ``n`` (mis-shaped carries fail HERE, not deep inside the scan)."""
        state = cls(
            mode=np.full(n, MODE_COOL, dtype=np.int8),
            cooldown=np.zeros(n, dtype=np.int64),
            app=np.full(n, -1, dtype=np.int64),
            app_rem=np.zeros(n),
            train_rem=np.zeros(n),
            corun=np.zeros(n, dtype=bool),
            idle_gap=np.zeros(n),
            pulled_at=np.zeros(n, dtype=np.int64),
            energy=np.zeros(n),
            updates=np.zeros(n, dtype=np.int64),
            plan=np.full(n, PLAN_HOLD, dtype=np.int8),
            rng_key=np.array([0, cfg.seed & 0xFFFFFFFF], dtype=np.uint32),
            carry=policy.init_carry(n, cfg),
            agg_carry=None if agg is None
            else agg.init_carry(n, cfg, fleet),
            dyn=None if dynamics is None or not dynamics.active
            else dynamics.init_state(n, cfg, fleet),
        )
        _check_shapes(state, n)
        return state

    def replace(self, **kw) -> "EngineState":
        new = dataclasses.replace(self, **kw)
        if _PER_USER_FIELDS.intersection(kw) or "dyn" in kw:
            # n comes from the PRE-replace state: replacing mode itself
            # with a mis-sized array must fail too
            n = np.shape(self.mode)[0] if np.ndim(self.mode) else None
            if n is not None:
                _check_shapes(new, int(n), only=set(kw))
        return new


# Fields that must be (n,)-leading per-user arrays in every engine.
_PER_USER_FIELDS = frozenset(
    ("mode", "cooldown", "app", "app_rem", "train_rem", "corun",
     "idle_gap", "pulled_at", "energy", "updates", "plan"))


def _leaves(tree):
    """Pytree leaves without requiring jax (dyn carries are dict/array)."""
    if tree is None:
        return
    if isinstance(tree, dict):
        for v in tree.values():
            yield from _leaves(v)
    elif isinstance(tree, (list, tuple)):
        for v in tree:
            yield from _leaves(v)
    else:
        yield tree


def _check_shapes(state: "EngineState", n: int, only=None) -> None:
    """Every per-user array must be ``(n,)``-leading; every ``dyn`` leaf
    with a leading axis must share it. Shape-only (never reads values),
    so it is trace-safe and cheap; raises ValueError naming the offender
    at construction instead of a reshape error deep inside the scan."""
    for f in _PER_USER_FIELDS if only is None \
            else _PER_USER_FIELDS.intersection(only):
        v = getattr(state, f)
        shape = np.shape(v)
        if not shape or shape[0] != n:
            raise ValueError(
                f"EngineState.{f} must be an ({n},)-leading per-user "
                f"array, got shape {shape}")
    if only is None or "dyn" in only:
        for leaf in _leaves(state.dyn):
            shape = np.shape(leaf)
            if len(shape) >= 1 and shape[0] != n:
                raise ValueError(
                    f"EngineState.dyn leaf has leading dim {shape[0]}, "
                    f"expected the run's n_users={n} (shape {shape}); "
                    "dynamics init_state must return (n,)-leading arrays")


# ---------------------------------------------------------------------------
# Sharded-scan support (core/vector_engine.py, ``SimConfig.n_devices``): pad
# the user axis to a multiple of the mesh size with INERT rows and build the
# matching pytree of shardings for ``jax.device_put``. Padded users park in
# MODE_OFF with no app and a zeroed catalog row (the driver zero-pads the
# table gathers), so they draw no energy, never enter the waiting queue and
# never push — the scheduler scalars evolve exactly as at the live n.
# ---------------------------------------------------------------------------
def pad_to_devices(n: int, n_devices: int) -> int:
    """Smallest multiple of ``n_devices`` >= ``n`` (the padded user-axis
    length ``n_arr`` of a sharded run)."""
    d = max(int(n_devices), 1)
    return -(-int(n) // d) * d


def _map_tree(fn, tree):
    """Structure-preserving map without requiring jax (carries are
    dict/list/tuple/array pytrees; ``None`` passes through)."""
    if tree is None:
        return None
    if isinstance(tree, dict):
        return {k: _map_tree(fn, v) for k, v in tree.items()}
    if isinstance(tree, (list, tuple)):
        return type(tree)(_map_tree(fn, v) for v in tree)
    return fn(tree)


def _map_tree2(fn, tree, other):
    """Two-tree ``_map_tree`` (leaf-wise zip; structures must match)."""
    if tree is None:
        return None
    if isinstance(tree, dict):
        return {k: _map_tree2(fn, v, other[k]) for k, v in tree.items()}
    if isinstance(tree, (list, tuple)):
        return type(tree)(_map_tree2(fn, v, o)
                          for v, o in zip(tree, other))
    return fn(tree, other)


# inert fill values of the per-user fields; everything not named is 0/False
_PAD_FILLS = {"mode": MODE_OFF, "app": -1, "plan": PLAN_HOLD}


def pad_state_per_user(state: EngineState, n_arr: int,
                       dyn_rows=None) -> EngineState:
    """Host-side copy of ``state`` with every per-user leaf extended to
    ``n_arr`` rows of INERT users: MODE_OFF, no app, zero
    energy/updates/cooldown. Policy and aggregation carries zero-pad
    their ``(n,)``-leading leaves (the registry carries — greedy wait
    counters, hetero scales — initialize pad-equivalently at any n).
    ``dyn_rows`` is the dynamics' ``pad_state(k)`` pytree of inert rows
    (required when ``state.dyn`` is populated); its leaves are cast to
    the state leaf dtypes. Shape-checked at ``n_arr`` on the way out."""
    n = int(np.shape(state.mode)[0])
    k = int(n_arr) - n
    if k < 0:
        raise ValueError(f"n_arr={n_arr} is below the live n={n}")
    if k == 0:
        return state

    def pad(x, fill=0):
        x = np.asarray(x)
        return np.concatenate(
            [x, np.full((k,) + x.shape[1:], fill, dtype=x.dtype)])

    def pad_carry_leaf(x):
        a = np.asarray(x)
        if a.ndim >= 1 and a.shape[0] == n:
            return pad(a)
        return x

    kw = {f: pad(getattr(state, f), _PAD_FILLS.get(f, 0))
          for f in _PER_USER_FIELDS}
    kw["carry"] = _map_tree(pad_carry_leaf, state.carry)
    kw["agg_carry"] = _map_tree(pad_carry_leaf, state.agg_carry)
    if state.dyn is not None:
        if dyn_rows is None:
            raise ValueError(
                "pad_state_per_user needs the dynamics' pad_state(k) rows "
                "to pad a populated EngineState.dyn")
        kw["dyn"] = _map_tree2(
            lambda leaf, rows: np.concatenate(
                [np.asarray(leaf),
                 np.asarray(rows, np.asarray(leaf).dtype)])
            if np.ndim(leaf) >= 1 and np.shape(leaf)[0] == n else leaf,
            state.dyn, dyn_rows)
    new = dataclasses.replace(state, **kw)
    _check_shapes(new, int(n_arr))
    return new


def unpad_state_per_user(state: EngineState, n: int) -> EngineState:
    """Drop the pad rows again: every ``(n_arr,)``-leading per-user /
    carry / dyn leaf sliced back to the live ``n`` (numpy or device
    arrays — slicing works on both)."""
    n_arr = int(np.shape(state.mode)[0])
    if n_arr == n:
        return state

    def cut(x):
        if np.ndim(x) >= 1 and np.shape(x)[0] == n_arr:
            return x[:n]
        return x

    kw = {f: cut(getattr(state, f)) for f in _PER_USER_FIELDS}
    kw["carry"] = _map_tree(cut, state.carry)
    kw["agg_carry"] = _map_tree(cut, state.agg_carry)
    kw["dyn"] = _map_tree(cut, state.dyn)
    return dataclasses.replace(state, **kw)


def state_shardings(state: EngineState, mesh, n_arr: int) -> EngineState:
    """EngineState-shaped pytree of ``NamedSharding``s for
    ``jax.device_put``: per-user leaves (and any ``(n_arr,)``-leading
    carry/dyn leaf) partitioned over the mesh's ``users`` axis,
    scheduler scalars / rng key / scalar carry leaves replicated."""
    from jax.sharding import NamedSharding, PartitionSpec

    sh_u = NamedSharding(mesh, PartitionSpec("users"))
    sh_r = NamedSharding(mesh, PartitionSpec())

    def leaf_sharding(x):
        if np.ndim(x) >= 1 and np.shape(x)[0] == int(n_arr):
            return sh_u
        return sh_r

    kw = {}
    for f in _FIELDS:
        v = getattr(state, f)
        if f in _PER_USER_FIELDS:
            kw[f] = sh_u
        elif f in ("carry", "agg_carry", "dyn"):
            kw[f] = _map_tree(leaf_sharding, v)
        elif f == "events":
            kw[f] = None        # the driver builds the buffer separately
        else:
            kw[f] = sh_r
    return EngineState(**kw)


_FIELDS = tuple(f.name for f in dataclasses.fields(EngineState))


def _flatten(s: EngineState):
    return tuple(getattr(s, f) for f in _FIELDS), None


def _unflatten(_, children) -> EngineState:
    return EngineState(**dict(zip(_FIELDS, children)))


try:  # register as a jax pytree so EngineState IS the lax.scan carry
    from jax import tree_util as _jtu

    _jtu.register_pytree_node(EngineState, _flatten, _unflatten)
except ImportError:  # pragma: no cover - jax is a hard dep of repro.core
    pass


class PushLog:
    """Fixed-width push-log accumulator with the historical dict schema.

    Engines append columnar blocks (``extend``) or single events
    (``append``); the jax driver feeds decoded ``(k, 6)`` buffer slices
    (``extend_rows``). The sequence interface decodes per-event dicts
    ``{"t", "user", "lag", "gap", "corun", "weight"}`` lazily, so holding
    a fleet-scale log costs six flat arrays, not O(pushes) dicts;
    iteration and ``log == [...]`` behave exactly like the historical
    list of dicts.
    """

    __slots__ = ("_parts", "_n", "_cache")

    def __init__(self):
        self._parts = []   # (t, user, lag, gap, corun, weight) blocks
        self._n = 0
        self._cache = None

    # ------------------------------------------------------------- builders
    def append(self, t, user, lag, gap, corun, weight=1.0) -> None:
        """One event (the loop oracle's per-push path)."""
        self._parts.append((np.asarray([t], np.int64),
                            np.asarray([user], np.int64),
                            np.asarray([lag], np.int64),
                            np.asarray([gap], np.float64),
                            np.asarray([corun], bool),
                            np.asarray([weight], np.float64)))
        self._n += 1
        self._cache = None

    def extend(self, t, users, lags, gaps, corun, weights=None) -> None:
        """One slot's finisher cohort (the numpy engine's path); ``t`` is
        the scalar slot, the rest ``(k,)`` arrays in user order.
        ``weights=None`` means full-weight (replace) pushes."""
        users = np.asarray(users, np.int64)
        k = len(users)
        if not k:
            return
        self._parts.append((np.full(k, t, np.int64), users,
                            np.asarray(lags, np.int64),
                            np.asarray(gaps, np.float64),
                            np.asarray(corun, bool),
                            np.ones(k, np.float64) if weights is None
                            else np.asarray(weights, np.float64)))
        self._n += k
        self._cache = None

    def extend_rows(self, rows) -> None:
        """Decode a drained ``PushBuffer`` slice: ``rows`` is ``(k, 6)``
        float in ``EVENT_FIELDS`` order (the jax engine's path)."""
        rows = np.asarray(rows)
        if not len(rows):
            return
        self._parts.append((rows[:, 0].astype(np.int64),
                            rows[:, 1].astype(np.int64),
                            rows[:, 2].astype(np.int64),
                            rows[:, 3].astype(np.float64),
                            rows[:, 4] != 0,
                            rows[:, 5].astype(np.float64)))
        self._n += len(rows)
        self._cache = None

    # ------------------------------------------------------------- readers
    def arrays(self):
        """The six concatenated columns, ``EVENT_FIELDS`` order."""
        if self._cache is None:
            if self._parts:
                cols = tuple(np.concatenate([p[j] for p in self._parts])
                             for j in range(6))
            else:
                cols = (np.zeros(0, np.int64), np.zeros(0, np.int64),
                        np.zeros(0, np.int64), np.zeros(0, np.float64),
                        np.zeros(0, bool), np.zeros(0, np.float64))
            self._cache = cols
        return self._cache

    def field(self, name: str) -> np.ndarray:
        return self.arrays()[EVENT_FIELDS.index(name)]

    def __len__(self) -> int:
        return self._n

    def __bool__(self) -> bool:
        return self._n > 0

    def _event(self, i: int) -> dict:
        t, u, l, g, c, w = self.arrays()
        # python scalars on purpose: digests/reprs must match the
        # historical dict-of-python-scalars schema byte for byte
        return {"t": int(t[i]), "user": int(u[i]), "lag": int(l[i]),
                "gap": float(g[i]), "corun": bool(c[i]),
                "weight": float(w[i])}

    def __getitem__(self, i):
        if isinstance(i, slice):
            return [self._event(j) for j in range(*i.indices(self._n))]
        if i < 0:
            i += self._n
        if not 0 <= i < self._n:
            raise IndexError(i)
        return self._event(i)

    def __iter__(self):
        for i in range(self._n):
            yield self._event(i)

    def __eq__(self, other):
        if isinstance(other, PushLog):
            return list(self) == list(other)
        if isinstance(other, (list, tuple)):
            return list(self) == list(other)
        return NotImplemented

    def __repr__(self):
        return f"PushLog(n={self._n})"
