"""Real-ML coupling for the simulator (Fig. 5): real models on cifarlike
data, momentum SGD (Eq. 1), async parameter server vs FedAvg.

Two ways to couple a schedule to actual JAX training:

* ``BatchedMLBackend`` — the first-class protocol. A backend owns the
  server, the per-client shards and the in-flight (pulled) parameter
  snapshots, and exposes *batched* entry points the vectorized engine
  dispatches once per slot cohort instead of n Python callbacks (cohort
  indices, pull versions and lags all come from the engine's shared
  ``EngineState`` — core/engine_state.py):
  ``pull_batch`` -> ``local_train_batch`` (one ``jax.vmap``'d masked epoch
  over the whole finisher cohort, jit-compiled once per cohort shape) ->
  ``push_batch``/``submit_batch`` (sequential server application in user
  order, preserving the loop oracle's push ordering exactly).
* ``make_ml_hooks`` — the historical per-user callback dict for the loop
  engine, now a thin adapter over ``LeNetBackend.hooks()``. Same
  construction order, same rng stream, same jitted per-client epoch, so
  pre-existing seeded loop runs reproduce bit-for-bit.

The batched protocol is model-agnostic: ``ImageClassifierBackend`` holds
all the cohort batching / fused-scan machinery parameterized by three
module-level model functions (init / loss / logits), and ``LeNetBackend``
(the paper's workload) and ``MLPBackend`` (models/mlp.py) are thin
subclasses — the jitted cohort programs key on the loss function as a
static argument, so each model compiles its own executables while sharing
every line of driver code. The push-apply side is kernel-switchable
(``kernel="pallas"|"reference"|"auto"``): under ``"pallas"`` the fused
finish scan flattens the model once and applies every push with the
single-HBM-pass ``fused_apply_2d`` Pallas kernel — the per-push momentum
norm chains through the scan carry as a scalar instead of re-traversing
the pytree.

Reset contract: ``FederatedSim.run()`` calls ``reset()`` on its backend
before every run after the first, and a backend's ``reset()`` puts it back
where its constructor left it — initial parameters, zero momentum, server
version 0, no in-flight pulls, every client's key chain and permutation
bank from the start. Two runs of one simulator are then identical (push
log, accuracy trace, final parameters) and the second compiles nothing:
every jitted program here, the accuracy one included, is module-level and
keyed on shapes and static model functions, never on a backend instance.

Equivalence contract (pinned by tests/test_real_mode.py): under the
paper's queue regime (L_b large enough that H stays 0, where the online
decision is independent of the momentum norm) the batched path reproduces
the loop oracle's schedule — update counts, lags, push order — exactly;
accuracy/energy/gap trajectories match within float tolerance (vmap'd XLA
programs are not bit-identical to their per-client counterparts).
"""
from __future__ import annotations

import functools
from types import SimpleNamespace
from typing import Dict, Optional, Type, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.aggregation import (AggregationRule, ReplaceRule,
                                    aggregation_support)
from repro.core.client import Client
from repro.core.policies import _jax_gradient_gap
from repro.core.server import AsyncParameterServer, SyncServer
from repro.core.staleness import gradient_gap
from repro.data.synthetic import cifarlike_dataset, dirichlet_partition
from repro.kernels.fused_update import kernel_interpret, resolve_kernel_mode
from repro.kernels.fused_update.kernel import LANES, fused_apply_2d
from repro.kernels.fused_update.ops import clamp_block_rows
from repro.models.lenet import init_lenet, lenet_logits, lenet_loss
from repro.models.mlp import init_mlp, mlp_logits, mlp_loss


class BatchedMLBackend:
    """Protocol for batched real-ML coupling (vectorized-engine capable).

    A backend instance owns the parameter server, the per-client data, and
    the pulled-parameter snapshots of every in-flight user. The vectorized
    engine drives it with whole cohorts; the loop oracle drives the same
    instance through ``hooks()``. A run consumes the server state;
    ``reset()`` restores it, and ``FederatedSim.run()`` calls it before
    every run after the first.

    Attributes engines rely on: ``n_users`` (validated against
    ``SimConfig.n_users``), ``sync`` (FedAvg lock-step vs async parameter
    server — must match the policy's ``sync_rounds``), ``eval_every``
    (slots between accuracy samples).
    """

    name: str = ""
    n_users: int = 0
    sync: bool = False
    eval_every: int = 600

    # ------------------------------------------------------------ loop adapter
    def hooks(self) -> dict:
        """Per-user callback dict for ``FederatedSim``'s loop engine —
        the same backend state behind the historical hook protocol."""
        raise NotImplementedError

    def bind_fleet(self, fleet_spec, cfg=None) -> None:
        """Receive the run's ``FleetSpec`` and ``SimConfig``
        (``FederatedSim`` calls this at construction). Fleet-conditioned
        aggregation rules (core/aggregation.py ``hetero_aware``) need
        the fleet to derive device-class scales, and the config is
        forwarded to the rule's ``scan_operands``/``init_carry`` on the
        fused push-scan path; the default is a no-op."""

    def reset(self) -> None:
        """Return to the state the constructor left: the initial global
        parameters and momentum, version 0, nothing in flight, and every
        random stream from its start, so that the next run repeats the
        first. A backend that cannot do this runs once per simulator."""
        raise NotImplementedError(
            f"{type(self).__name__} has no reset(); build a new simulator "
            "for each run")

    # ------------------------------------------------------------ batched path
    def pull_batch(self, uids: np.ndarray, version: int) -> None:
        """Snapshot the current global parameters for every uid starting
        training this slot. ``version`` is the engine's global model
        version at pull time — ``EngineState.version``, the same counter
        every engine threads (core/engine_state.py) — for staleness-aware
        backends."""
        raise NotImplementedError

    def local_train_batch(self, uids: np.ndarray, versions: np.ndarray):
        """One local epoch for the whole finisher cohort at once; returns
        the trained parameters stacked on a leading ``len(uids)`` axis.
        ``versions`` are the per-uid pull versions the engine recorded in
        ``EngineState.pulled_at``."""
        raise NotImplementedError

    def push_batch(self, uids: np.ndarray, trained, lags: np.ndarray,
                   eta: float, beta: float):
        """Apply the cohort's pushes to the async server sequentially in
        ``uids`` order (the loop oracle's ordering), returning
        ``(gaps, weights)``: the Eq. (4) gap of each push evaluated
        against the momentum norm *before* that push was applied —
        exactly what the loop's per-user finish does — and the
        aggregation rule's applied mixing weight per push."""
        raise NotImplementedError

    def submit_batch(self, uids: np.ndarray, trained, lags: np.ndarray,
                     eta: float, beta: float):
        """Sync-mode twin of ``push_batch``: submit the cohort's results
        to the FedAvg server (aggregation happens at round close).
        Returns ``(gaps, weights)`` with unit weights (FedAvg averages;
        there is no per-push weight)."""
        raise NotImplementedError

    def finish_async_batch(self, uids: np.ndarray, versions: np.ndarray,
                           lags: np.ndarray, eta: float, beta: float,
                           need_gaps: bool = True):
        """Whole async finish for a cohort: local_train_batch followed by
        push_batch; returns ``(gaps, weights)``. Backends may override
        with a fused implementation (one device dispatch for train +
        weighted ordered pushes). With ``need_gaps=False`` (no push log
        collected) the return value is ignored and backends may skip the
        gap/weight read-back — and with it any host-device
        synchronization."""
        trained = self.local_train_batch(uids, versions)
        return self.push_batch(uids, trained, lags, eta, beta)

    def sync_aggregate(self) -> None:
        """Close a FedAvg round (sync backends only)."""
        raise NotImplementedError

    def v_norm(self) -> float:
        """Current global momentum-norm estimate (0.0 for sync)."""
        raise NotImplementedError

    def evaluate(self) -> float:
        """Test accuracy of the current global model."""
        raise NotImplementedError


# ---------------------------------------------------------------------------
# Backend registry (Scenario's ml="lenet" resolves here)
# ---------------------------------------------------------------------------
ML_BACKENDS: Dict[str, Type[BatchedMLBackend]] = {}


def register_ml_backend(cls: Type[BatchedMLBackend]) -> Type[BatchedMLBackend]:
    if not cls.name:
        raise ValueError(f"{cls.__name__} must set a registry name")
    ML_BACKENDS[cls.name] = cls
    return cls


def registered_ml_backends() -> tuple:
    return tuple(ML_BACKENDS)


def make_backend(ml: Union[str, BatchedMLBackend], n_users: int, *,
                 sync: bool = False, seed: int = 0,
                 **kwargs) -> BatchedMLBackend:
    """Resolve ``ml`` to a fresh backend instance. Strings go through the
    registry; instances pass through as-is (their constructor already fixed
    n_users/sync/seed)."""
    if isinstance(ml, BatchedMLBackend):
        return ml
    if isinstance(ml, str):
        if ml not in ML_BACKENDS:
            raise ValueError(f"unknown ML backend {ml!r}; expected one of "
                             f"{registered_ml_backends()} or a "
                             "BatchedMLBackend instance")
        return ML_BACKENDS[ml](n_users, sync=sync, seed=seed, **kwargs)
    raise ValueError(f"ml must be a name or BatchedMLBackend instance, "
                     f"got {type(ml).__name__}")


# ---------------------------------------------------------------------------
# Jitted cohort programs (module-level so every backend instance with the
# same data shapes and hyperparameters shares one compiled executable).
# ---------------------------------------------------------------------------
def _masked_epoch(params, idx, mask, flat_x, flat_y, eta, beta, loss_fn):
    """One local momentum-SGD epoch (Eq. 1, the Client._epoch step rule)
    over minibatches ``flat_x[idx]``; masked steps are no-ops (ragged
    shards / padding lanes). ``loss_fn`` is the backend's model loss
    (a module-level function — the jit static-arg key)."""
    bx = flat_x[idx]                       # (S, B, H, W, C)
    by = flat_y[idx]                       # (S, B)
    v0 = jax.tree.map(jnp.zeros_like, params)

    def step(carry, xs):
        p, v = carry
        x, y, m = xs
        with jax.named_scope("ml.local_step"):
            grads, _ = jax.grad(
                lambda q: loss_fn(q, {"images": x, "labels": y}),
                has_aux=True)(p)
            v2 = jax.tree.map(lambda vv, g: beta * vv + (1 - beta) * g,
                              v, grads)
            p2 = jax.tree.map(lambda pp, vv: pp - eta * vv, p, v2)
            p = jax.tree.map(lambda a, b: jnp.where(m, a, b), p2, p)
            v = jax.tree.map(lambda a, b: jnp.where(m, a, b), v2, v)
        return (p, v), None

    (params, _), _ = jax.lax.scan(step, (params, v0), (bx, by, mask))
    return params


def _tree_l2_norm_traced(tree):
    """staleness.tree_l2_norm, usable under jit (same accumulation order:
    Python sum over tree.leaves, f32)."""
    sq = sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
             for l in jax.tree.leaves(tree))
    return jnp.sqrt(sq)


def _lanes(params, idx, shared):
    """The chunk's per-lane parameter stack. ``shared=True`` means every
    lane pulled the SAME global snapshot (lock-step cohorts under replace
    aggregation — the common case), so the caller passed one tree and the
    lanes are a free in-device broadcast. Otherwise ``params`` is a tuple
    of per-lane trees and the stack happens HERE, inside the jit — eager
    per-leaf stacking costs milliseconds per op on CPU."""
    if not shared:
        return jax.tree.map(lambda *xs: jnp.stack(xs), *params)
    C = idx.shape[0]
    return jax.tree.map(
        lambda a: jnp.broadcast_to(a[None], (C,) + a.shape), params)


@functools.partial(jax.jit, static_argnames=("n_epochs", "n_i"))
def _perm_bank(key, n_epochs, n_i):
    """``n_epochs`` iterations of the Client.local_train key protocol —
    ``key, sub = split(key)`` then ``permutation(sub, n_i)`` — in one
    dispatch. The scanned split chain is bit-identical to sequential
    eager splits, so banked draws equal the loop engine's."""
    def step(k, _):
        k2, sub = jax.random.split(k)
        return k2, sub

    key, subs = jax.lax.scan(step, key, None, length=n_epochs)
    perms = jax.vmap(lambda s: jax.random.permutation(s, n_i))(subs)
    return key, perms


@functools.partial(jax.jit,
                   static_argnames=("eta", "beta", "shared", "loss_fn"))
def _train_chunk(params, idx, mask, flat_x, flat_y, eta, beta, shared,
                 loss_fn):
    """vmap'd masked epoch over one cohort chunk."""
    return jax.vmap(
        lambda p, i, m: _masked_epoch(p, i, m, flat_x, flat_y, eta, beta,
                                      loss_fn)
    )(_lanes(params, idx, shared), idx, mask)


@functools.partial(jax.jit, static_argnames=("logits_fn",))
def _accuracy(params, test_x, test_y, logits_fn):
    """Test accuracy of ``params``: one program per model (``logits_fn``,
    a module-level function) and test-set shape, shared by every backend
    and every run."""
    logits = logits_fn(params, test_x)
    return jnp.mean((jnp.argmax(logits, -1) == test_y).astype(jnp.float32))


_FINISH_FN_CACHE: dict = {}
_FINISH_FN_CACHE_MAX = 16


def _finish_chunk_fn(rule, eta, beta, shared, need_gaps, loss_fn, kernel):
    """The fused-finish executable for one (rule, hyperparams, layout,
    model, kernel) combination, memoized on ``rule.jax_cache_key()`` — the
    same keying the trace engine's scan cache uses, so fresh
    knob-configured instances of operand-driven rules (knobs ride the
    traced ``agg_ops``) share ONE compiled program instead of retracing
    the most expensive jit in the repo per instance. ``loss_fn`` (the
    model, a module-level function) and ``kernel`` (a resolved
    "pallas"/"reference") key alongside."""
    key = (rule.jax_cache_key(), eta, beta, shared, need_gaps, loss_fn,
           kernel)
    fn = _FINISH_FN_CACHE.pop(key, None)    # pop+reinsert = LRU order
    if fn is None:
        fn = _build_finish_chunk(rule, eta, beta, shared, need_gaps,
                                 loss_fn, kernel)
        if len(_FINISH_FN_CACHE) >= _FINISH_FN_CACHE_MAX:
            _FINISH_FN_CACHE.pop(next(iter(_FINISH_FN_CACHE)))
    _FINISH_FN_CACHE[key] = fn
    return fn


def _build_finish_chunk(rule, eta, beta, shared, need_gaps, loss_fn,
                        kernel):
    """Fused async finish: train the whole chunk (vmap) then apply the
    pushes sequentially in lane order (lax.scan) with the aggregation
    rule's mixing weight (core/aggregation.py — the rule's traced
    ``scan_weight`` hook runs IN the scan, so weighted rules cost zero
    per-push host round-trips) and the server momentum recursion of
    ``AsyncParameterServer.push``:

        w       = rule.scan_weight(lag_j, gap_j, ||v||_pre)
        params <- w * trained_j + (1 - w) * params
        s       = (params_old - params_new) / eta
        v      <- beta * v + (1 - beta) * s

    Under the paper's "replace" rule the weight math is skipped entirely
    (``params <- trained_j``, the historical op sequence, kept
    bit-identical for the golden oracle). Emits ``||v||`` and the
    applied weight at each step *start* — the momentum norm each push's
    Eq. (4) gap is evaluated against in the loop oracle (the norm left
    by the previous finisher). Invalid (padding) lanes leave the carry
    untouched.

    ``kernel="pallas"`` swaps the per-push pytree traversals for the
    single-HBM-pass ``fused_apply_2d`` kernel: the global params/momentum
    ride the scan carry as one padded (rows, 128) f32 matrix (flattened
    ONCE per chunk, not per push), each push is one kernel dispatch
    (mix + momentum + Sum(v'^2)), and the pre-push Eq. (4) norm is
    ``sqrt`` of the carried sum-of-squares scalar — no
    ``_tree_l2_norm_traced`` traversals anywhere in the scan.
    """
    replace = isinstance(rule, ReplaceRule)
    # per-step pre-push norms feed the push-log gaps AND gap-reading
    # rule weights; without either they are dead weight (10 tree
    # reductions per push)
    need_norms = need_gaps or rule.needs_gap
    eta_s = max(eta, 1e-12)
    if kernel == "pallas":
        return _build_finish_chunk_pallas(rule, eta, beta, shared,
                                          need_norms, loss_fn, replace,
                                          eta_s)

    @jax.jit
    def finish(params, idx, mask, valid, lags, uids, agg_carry, agg_ops,
               server_params, server_v, flat_x, flat_y):
        trained = jax.vmap(
            lambda p, i, m: _masked_epoch(p, i, m, flat_x, flat_y, eta,
                                          beta, loss_fn)
        )(_lanes(params, idx, shared), idx, mask)

        def push_step(carry, xs):
            p, v = carry
            t_j, ok, lag_j, uid_j = xs
            vnorm_pre = _tree_l2_norm_traced(v) if need_norms \
                else jnp.asarray(0.0, jnp.float32)
            if replace:
                w = jnp.asarray(1.0, jnp.float32)
                p_new = t_j
            else:
                # Eq. (4) gap against the pre-push norm, the value the
                # server's host path feeds the rule — the same traced
                # twin the jax trace engine uses
                gap_j = _jax_gradient_gap(vnorm_pre, lag_j, eta, beta)
                pv = SimpleNamespace(jnp=jnp, lag=lag_j, gap=gap_j,
                                     v_norm=vnorm_pre, users=uid_j,
                                     consts=agg_ops,
                                     float_dtype=vnorm_pre.dtype)
                _, w = rule.scan_weight(agg_carry, pv)
                p_new = jax.tree.map(lambda n_, o: w * n_ + (1 - w) * o,
                                     t_j, p)
            s = jax.tree.map(lambda o, n_: (o - n_) / eta_s, p, p_new)
            v2 = jax.tree.map(lambda vv, g: beta * vv + (1 - beta) * g,
                              v, s)
            p = jax.tree.map(lambda a, b: jnp.where(ok, a, b), p_new, p)
            v = jax.tree.map(lambda a, b: jnp.where(ok, a, b), v2, v)
            return (p, v), (vnorm_pre, w)

        with jax.named_scope("ml.apply"):
            (p_out, v_out), (vnorms, ws) = jax.lax.scan(
                push_step, (server_params, server_v),
                (trained, valid, lags, uids))
            vn_out = _tree_l2_norm_traced(v_out)
        return p_out, v_out, vnorms, ws, vn_out

    return finish


def _build_finish_chunk_pallas(rule, eta, beta, shared, need_norms,
                               loss_fn, replace, eta_s):
    """The Pallas twin of ``_build_finish_chunk``'s push scan (same
    signature, same outputs to rtol): train the chunk, flatten the global
    (params, momentum) to one padded (rows, 128) f32 carry, then apply
    each push as ONE ``fused_apply_2d`` dispatch. The post-push
    sum-of-squares chains through the carry, so each push's pre-norm
    (Eq. 4) is a scalar ``sqrt`` and the final ``||v||`` costs nothing —
    the reference path's 10-leaf tree reductions per push disappear.
    Replace degenerates to w=1 through the same kernel (mixed == t_j)."""
    interpret = kernel_interpret()

    @jax.jit
    def finish(params, idx, mask, valid, lags, uids, agg_carry, agg_ops,
               server_params, server_v, flat_x, flat_y):
        trained = jax.vmap(
            lambda p, i, m: _masked_epoch(p, i, m, flat_x, flat_y, eta,
                                          beta, loss_fn)
        )(_lanes(params, idx, shared), idx, mask)

        with jax.named_scope("ml.apply"):
            # ---- flatten ONCE per chunk to the kernel's (rows, 128) layout
            leaves = jax.tree.leaves(server_params)
            treedef = jax.tree.structure(server_params)
            shapes = [l.shape for l in leaves]
            sizes = [l.size for l in leaves]
            n_tot = sum(sizes)
            block_rows = clamp_block_rows(n_tot)
            per_block = block_rows * LANES
            padded = -(-n_tot // per_block) * per_block
            rows = padded // LANES

            def flat2d(tree):
                f = jnp.concatenate([l.reshape(-1).astype(jnp.float32)
                                     for l in jax.tree.leaves(tree)])
                return jnp.pad(f, (0, padded - n_tot)).reshape(rows, LANES)

            p2 = flat2d(server_params)
            v2 = flat2d(server_v)
            # trained lanes: (C, rows, 128), padded along the flat axis —
            # padding lanes mix 0 with 0 and add 0 to the norm
            t2 = jnp.concatenate(
                [l.reshape(l.shape[0], -1).astype(jnp.float32)
                 for l in jax.tree.leaves(trained)], axis=1)
            t2 = jnp.pad(t2, ((0, 0), (0, padded - n_tot)))
            t2 = t2.reshape(t2.shape[0], rows, LANES)
            # entry sum-of-squares: one reduction per CHUNK; every in-scan
            # pre-norm after this is carried forward by the kernel
            sumsq0 = jnp.sum(v2 * v2)
            inv_eta = 1.0 / eta_s

            def push_step(carry, xs):
                p, v, sq = carry
                t_j, ok, lag_j, uid_j = xs
                vnorm_pre = jnp.sqrt(sq) if need_norms \
                    else jnp.asarray(0.0, jnp.float32)
                if replace:
                    w = jnp.asarray(1.0, jnp.float32)
                else:
                    gap_j = _jax_gradient_gap(vnorm_pre, lag_j, eta, beta)
                    pv = SimpleNamespace(jnp=jnp, lag=lag_j, gap=gap_j,
                                         v_norm=vnorm_pre, users=uid_j,
                                         consts=agg_ops,
                                         float_dtype=vnorm_pre.dtype)
                    _, w = rule.scan_weight(agg_carry, pv)
                mixed, v_new, sq_new = fused_apply_2d(
                    p, v, t_j, w, inv_eta, beta, block_rows=block_rows,
                    interpret=interpret)
                p = jnp.where(ok, mixed, p)
                v = jnp.where(ok, v_new, v)
                sq = jnp.where(ok, sq_new, sq)
                return (p, v, sq), (vnorm_pre, w)

            (p2, v2, sq), (vnorms, ws) = jax.lax.scan(
                push_step, (p2, v2, sumsq0), (t2, valid, lags, uids))

            def unflat(f2):
                f = f2.reshape(-1)[:n_tot]
                out, off = [], 0
                for shp, sz in zip(shapes, sizes):
                    out.append(f[off:off + sz].reshape(shp))
                    off += sz
                return treedef.unflatten(out)

            return unflat(p2), unflat(v2), vnorms, ws, jnp.sqrt(sq)

    return finish


class ImageClassifierBackend(BatchedMLBackend):
    """Model-agnostic batched backend: any image classifier on cifarlike
    shards. Subclasses bind three module-level model functions
    (``model_init`` / ``model_loss`` / ``model_logits``) and a registry
    ``name`` — everything else (cohort batching, permutation banks, the
    fused train+push scan, kernel dispatch) lives here once. The model
    functions are staticmethods of MODULE-LEVEL functions on purpose:
    their identity is the jit static-arg and finish-cache key, so every
    instance of a subclass shares one set of compiled executables.

    Per-client pulled parameters are pytree REFERENCES (``_inflight``),
    so a pull costs zero device work; at train time a cohort whose lanes
    all share one snapshot (lock-step pulls under replace aggregation,
    the common case) is broadcast in-device, and ragged cohorts stack
    their lanes inside the jit (tuple-of-trees argument — never eagerly).
    Cohorts are processed in chunks padded to the next power of FOUR
    (capped at ``cohort_pad`` lanes, padding lanes masked out, up to ~4x
    masked waste on the smallest cohorts), so the vmap'd epoch and the
    fused train+push program compile O(log4 cohort_pad) distinct shapes
    per run — not once per ragged cohort size — and the executables are
    shared across backend instances (module-level jit). Per-event host
    work is plain numpy: minibatch permutations come from precomputed
    per-client banks (same key chain as ``Client.local_train``), so the
    hot path issues one or two stable-shape device dispatches per chunk
    and never blocks. Shards are ragged
    (Dirichlet split): every lane runs ``S_max`` scan steps with per-step
    masks, where ``S_max`` is the fleet-wide maximum steps-per-epoch, and
    masked steps leave (params, momentum) untouched. The whole finish —
    cohort epoch + ordered weighted sequential pushes + per-push momentum
    norms — is one device dispatch (``_finish_chunk_fn``) for EVERY
    aggregation rule with a traced ``scan_weight`` hook (all registered
    rules: replace, fedasync_poly, gap_aware, hetero_aware —
    core/aggregation.py), the weights mixed inside the push scan with no
    per-push host round-trips; only custom numpy-only rules fall back to
    per-push server calls. ``kernel="pallas"`` routes every push apply —
    the server's and the fused scan's — through the single-HBM-pass
    Pallas kernel (``kernels/fused_update``); the default ``"auto"``
    keeps the bit-stable reference path off-TPU.

    ``reset()`` restores the constructor's state (the initial parameters
    it keeps, zero momentum, version 0, each client's key chain from
    ``PRNGKey(client_id)`` with empty permutation banks) without
    rebuilding the data, so a simulator repeats its run exactly.
    ``push_v_norms()`` gives the server momentum norm after each push of
    the run, as the finish chunk computed it for the next push's gap.
    """

    # bound by subclasses: module-level (init, loss, logits) functions
    model_init: staticmethod
    model_loss: staticmethod
    model_logits: staticmethod

    def __init__(self, n_users: int, *, sync: bool = False,
                 eta: float = 0.01, beta: float = 0.9,
                 n_train: int = 10000, n_test: int = 2000,
                 alpha: float = 100.0, batch_size: int = 20,
                 aggregation: Union[str, AggregationRule] = "replace",
                 noise: float = 8.0,
                 seed: int = 0, eval_every: int = 600,
                 cohort_pad: int = 16, partition: str = "dirichlet",
                 kernel: str = "auto"):
        # construction order (data -> shards -> clients -> params -> server)
        # is pinned: it is the historical make_ml_hooks rng stream, and the
        # loop-oracle golden (tests/data/real_mode_golden.json) depends on it
        images, labels = cifarlike_dataset(n_train, seed=seed, noise=noise)
        test_x, test_y = cifarlike_dataset(n_test, seed=seed + 1, noise=noise)
        if partition == "dirichlet":       # the paper's non-IID split
            shards = dirichlet_partition(labels, n_users, alpha=alpha,
                                         seed=seed)
        elif partition == "uniform":
            # IID near-equal shards (exactly equal when n_users divides
            # n_train): uniform step counts mean one jit shape for the
            # loop's per-client epoch and minimal masked-step waste in
            # the batched cohort epoch
            shards = np.array_split(np.arange(n_train, dtype=np.int64),
                                    n_users)
        else:
            raise ValueError(f"unknown partition {partition!r}; expected "
                             "'dirichlet' or 'uniform'")
        self.clients = [
            Client(i, jnp.asarray(images[s]), jnp.asarray(labels[s]),
                   self.model_loss, batch_size=batch_size, eta=eta,
                   beta=beta)
            for i, s in enumerate(shards)]
        params0 = self.model_init(jax.random.PRNGKey(seed))
        self._params0 = params0
        self._keys0 = [c._key for c in self.clients]
        self.server: object
        if sync:
            self.server = SyncServer(params0)
        else:
            self.server = AsyncParameterServer(params0, eta=eta, beta=beta,
                                               aggregation=aggregation,
                                               kernel=kernel)
        self.kernel = resolve_kernel_mode(kernel)
        self.n_users = n_users
        self.sync = sync
        self.eta = eta
        self.beta = beta
        self.batch_size = batch_size
        self.eval_every = eval_every
        self.cohort_pad = max(int(cohort_pad), 1)
        # the run's FleetSpec/SimConfig and the aggregation rule's carry
        # (device arrays for the fused push scan), set by bind_fleet
        self.fleet_spec = None
        self._sim_cfg = None
        self._agg_carry = None

        # ---- batched-training layout ---------------------------------
        # client shards concatenated flat; per-epoch minibatch gathers are
        # one fancy-index into these (offset + client-local permutation)
        self._offsets = np.zeros(n_users, dtype=np.int64)
        off = 0
        for i, s in enumerate(shards):
            self._offsets[i] = off
            off += len(s)
        self._shard_sizes = np.array([len(s) for s in shards], np.int64)
        self._flat_x = jnp.asarray(np.concatenate(
            [images[s] for s in shards], axis=0))
        self._flat_y = jnp.asarray(np.concatenate(
            [labels[s] for s in shards], axis=0))
        self._steps = self._shard_sizes // batch_size
        self._s_max = int(self._steps.max()) if n_users else 0
        # pulled-parameter snapshot per in-flight uid: pytree REFERENCES
        # (immutable), so a pull costs zero device work. Cohorts whose
        # lanes all share one snapshot (lock-step pulls under replace
        # aggregation) are broadcast in-device at train time; ragged
        # cohorts pay one host-side stack.
        self._inflight: list = [params0] * n_users
        # per-client minibatch-permutation banks: epochs of
        # jax.random.permutation draws precomputed in batches so the hot
        # path never touches the device RNG (parity: identical key chain
        # and draws as Client.local_train, verified by the golden tests)
        self._perm_bank: list = [None] * n_users
        self._bank_pos = np.zeros(n_users, dtype=np.int64)
        self._bank_epochs = 16
        # momentum norm after each push (host arrays and lazy device
        # scalars, in push order), kept where the push log needs the gaps
        self._norm_parts: list = []
        self._test_x = jnp.asarray(test_x)
        self._test_y = jnp.asarray(test_y)

    def reset(self) -> None:
        with TraceAnnotation("ml.reset"):
            p0 = self._params0
            self.server.reset(p0)
            for c, key in zip(self.clients, self._keys0):
                c._key = key
            self._inflight = [p0] * self.n_users
            self._perm_bank = [None] * self.n_users
            self._bank_pos[:] = 0
            self._norm_parts = []

    def push_v_norms(self) -> np.ndarray:
        """The server momentum norm after each push since the last reset,
        in push order; empty where the gaps were not asked for."""
        if not self._norm_parts:
            return np.zeros(0)
        return np.concatenate([np.asarray(x, np.float64).reshape(-1)
                               for x in self._norm_parts])

    # ------------------------------------------------------------ loop adapter
    def hooks(self) -> dict:
        """The historical per-user hook dict over this backend's state."""
        hooks = {
            "pull": lambda uid: self.server.pull(uid)[0],
            "local_train":
                lambda uid, params: self.clients[uid].local_train(params)[0],
            "evaluate": self.evaluate,
            "v_norm": self.v_norm,
            "eval_every": self.eval_every,
        }
        if self.sync:
            hooks["sync_submit"] = self.server.submit
            hooks["sync_aggregate"] = self.server.aggregate
        else:
            hooks["push"] = lambda uid, params: self.server.push(uid, params)
        return hooks

    def bind_fleet(self, fleet_spec, cfg=None) -> None:
        """Bind the run's FleetSpec + SimConfig (FederatedSim calls
        this): the fleet is forwarded to the async server for
        fleet-conditioned host-path weights, the rule carry (e.g.
        hetero_aware's per-user scales) is gathered once as device
        arrays for the fused push scan, and the config is kept so the
        rule's ``scan_operands`` sees the same cfg the trace engines
        pass."""
        self.fleet_spec = fleet_spec
        self._sim_cfg = cfg
        if isinstance(self.server, AsyncParameterServer):
            self.server.fleet_spec = fleet_spec
            carry = self.server.rule.init_carry(self.n_users, cfg,
                                                fleet_spec)
            self._agg_carry = jax.tree.map(jnp.asarray, carry)

    # ------------------------------------------------------------ batched path
    def _next_perm(self, uid: int) -> np.ndarray:
        """The client's next epoch permutation, from its precomputed
        bank. Banks are filled ``_bank_epochs`` at a time by consuming
        the client's key stream exactly like ``Client.local_train`` (one
        split per epoch), so loop and batched runs draw identical
        per-client minibatch permutations in epoch order."""
        bank = self._perm_bank[uid]
        pos = int(self._bank_pos[uid])
        if bank is None or pos >= len(bank):
            c = self.clients[uid]
            n_i = int(self._shard_sizes[uid])
            if n_i:
                # one dispatch per refill; bit-identical to per-epoch
                # jax.random.permutation calls (pinned by the golden tests)
                c._key, perms = _perm_bank(c._key, self._bank_epochs, n_i)
                bank = np.asarray(perms, dtype=np.int64)
            else:
                # zero-shard straggler: advance the key chain anyway
                for _ in range(self._bank_epochs):
                    c._key, _ = jax.random.split(c._key)
                bank = np.zeros((self._bank_epochs, 0), np.int64)
            self._perm_bank[uid] = bank
            pos = 0
        self._bank_pos[uid] = pos + 1
        return bank[pos]

    @staticmethod
    def _bucket(k: int) -> int:
        """Smallest power of four >= k: lane-count buckets keep the jit
        shape count at O(log_4 cohort_pad) per run while wasting at most
        ~4x the smallest cohort's (masked-out) compute."""
        c = 1
        while c < k:
            c <<= 2
        return c

    def _cohort_chunks(self, uids):
        """Yield ``(params, shared, idx, mask, valid, k)`` chunks for a
        finisher cohort: at most ``cohort_pad`` lanes per chunk, lane
        count padded to a power of four, scan depth fixed at the
        fleet-wide max steps-per-epoch — so the fused programs compile a
        handful of stable shapes per run, not one per ragged cohort.
        ``shared=True`` means all lanes pulled one snapshot and ``params``
        is that single tree (broadcast in-device); otherwise ``params``
        is a host-stacked ``(C, ...)`` tree. Per-event host work is plain
        numpy (permutation banks, index arithmetic)."""
        B, S = self.batch_size, self._s_max
        uids = np.asarray(uids)
        for c0 in range(0, len(uids), self.cohort_pad):
            chunk = uids[c0:c0 + self.cohort_pad]
            k = len(chunk)
            C = self._bucket(k)
            idx = np.zeros((C, S, B), np.int64)
            mask = np.zeros((C, S), bool)
            valid = np.zeros(C, bool)
            valid[:k] = True
            for j, uid in enumerate(chunk):
                uid = int(uid)
                steps = int(self._steps[uid])
                perm = self._next_perm(uid)      # consume even if 0 steps
                if steps:
                    idx[j, :steps] = (self._offsets[uid]
                                      + perm[:steps * B]).reshape(steps, B)
                    mask[j, :steps] = True
            lanes = [self._inflight[int(u)] for u in chunk]
            first = lanes[0]
            if all(l is first for l in lanes):
                yield first, True, idx, mask, valid, k
            else:
                lanes.extend([first] * (C - k))  # padding lanes
                yield tuple(lanes), False, idx, mask, valid, k

    def pull_batch(self, uids, version):
        for uid in np.asarray(uids):
            params, _ = self.server.pull(int(uid))
            self._inflight[int(uid)] = params

    def local_train_batch(self, uids, versions=None):
        uids = np.asarray(uids)
        if len(uids) == 0:
            return None
        parts = []
        for params, shared, idx, mask, valid, k in self._cohort_chunks(uids):
            with self._train_span(mask, k):
                out = _train_chunk(params, idx, mask,
                                   self._flat_x, self._flat_y,
                                   self.eta, self.beta, shared,
                                   self.model_loss)
            parts.append(jax.tree.map(lambda a: a[:k], out))
        if len(parts) == 1:
            return parts[0]
        return jax.tree.map(lambda *xs: jnp.concatenate(xs), *parts)

    def finish_async_batch(self, uids, versions, lags, eta, beta,
                           need_gaps=True):
        """Fused finish: each chunk is ONE device dispatch covering the
        vmap'd cohort epoch and the ordered weighted sequential pushes
        (the aggregation rule's ``scan_weight`` runs IN the scan — no
        per-push host round-trips for any registered rule); the host
        only updates server bookkeeping and never blocks — with
        ``need_gaps=False`` the whole finish is async dispatch (the
        momentum norm stays a lazy device scalar). Custom numpy-only
        rules (no traced hook) take the generic local_train_batch +
        push_batch path."""
        rule = self.server.rule
        if not aggregation_support(rule)["jax"] or \
                (type(rule).init_carry is not AggregationRule.init_carry
                 and self._agg_carry is None):
            # no traced weight hook (or a carry-needing rule without a
            # bound fleet): per-push server calls
            return super().finish_async_batch(uids, versions, lags,
                                              eta, beta, need_gaps)
        uids = np.asarray(uids)
        lags = np.asarray(lags)
        agg_ops = tuple(jnp.asarray(x)
                        for x in rule.scan_operands(self._sim_cfg))
        vnorms, weights = [], []
        p, v = self.server.params, self.server._v
        vn_out = None
        pos = 0
        for params, shared, idx, mask, valid, k in self._cohort_chunks(uids):
            C = len(valid)
            lag_c = np.zeros(C, np.int64)
            lag_c[:k] = lags[pos:pos + k]
            uid_c = np.zeros(C, np.int64)
            uid_c[:k] = uids[pos:pos + k]
            pos += k
            fn = _finish_chunk_fn(rule, self.eta, self.beta, shared,
                                  need_gaps, self.model_loss, self.kernel)
            with self._train_span(mask, k):
                p, v, vn, ws, vn_out = fn(
                    params, idx, mask, valid, jnp.asarray(lag_c),
                    jnp.asarray(uid_c), self._agg_carry, agg_ops, p, v,
                    self._flat_x, self._flat_y)
            if need_gaps:
                vn_k = np.asarray(vn, dtype=np.float64)[:k]
                vnorms.append(vn_k)
                weights.append(np.asarray(ws, dtype=np.float64)[:k])
                # after push j the norm is push j+1's pre-push norm; after
                # the chunk's last valid push it is the chunk's final norm
                self._norm_parts += [vn_k[1:], vn_out]
        self.server.params = p
        self.server._v = v
        # lazy: a 0-d device scalar; v_norm() converts on demand so
        # policies that never read it (immediate/sync) never block on it
        self.server.v_norm = vn_out
        for uid in uids:
            self.server.lag_tracker.on_push(int(uid))
            self.server.in_flight.discard(int(uid))
        if not need_gaps:
            return None, None
        # Eq. (4) gaps against the pre-push momentum norms (loop ordering)
        return (np.asarray(gradient_gap(np.concatenate(vnorms), lags,
                                        eta, beta), dtype=float),
                np.concatenate(weights))

    def push_batch(self, uids, trained, lags, eta, beta):
        gaps = np.empty(len(uids))
        weights = np.empty(len(uids))
        for j, uid in enumerate(np.asarray(uids)):
            uid = int(uid)
            # loop-oracle order: the gap uses the momentum norm *before*
            # this push (but after every earlier finisher's in this slot)
            gaps[j] = gradient_gap(self.v_norm(), int(lags[j]), eta, beta)
            res = self.server.push(uid,
                                   jax.tree.map(lambda a: a[j], trained))
            weights[j] = res.applied_weight
            self._norm_parts.append(np.asarray(self.v_norm()))
        return gaps, weights

    def submit_batch(self, uids, trained, lags, eta, beta):
        gaps = np.empty(len(uids))
        for j, uid in enumerate(np.asarray(uids)):
            uid = int(uid)
            gaps[j] = gradient_gap(self.v_norm(), int(lags[j]), eta, beta)
            self.server.submit(jax.tree.map(lambda a: a[j], trained))
        return gaps, np.ones(len(uids))

    def sync_aggregate(self):
        self.server.aggregate()

    def v_norm(self) -> float:
        # float() realizes the lazy device scalar the fused finish leaves
        # behind; a plain float (eager loop pushes) passes through
        return 0.0 if self.sync else float(self.server.v_norm)

    def _train_span(self, mask, k):
        """The host span of one chunk's training dispatch: its live lanes,
        its padded lane count and the samples its live lanes train."""
        return TraceAnnotation("ml.train", cohort=k, bucket=len(mask),
                               samples=int(np.count_nonzero(mask))
                               * self.batch_size)

    def _acc(self, params) -> jax.Array:
        return _accuracy(params, self._test_x, self._test_y,
                         self.model_logits)

    def evaluate(self) -> float:
        return float(self._acc(self.server.params))


@register_ml_backend
class LeNetBackend(ImageClassifierBackend):
    """The paper's workload: LeNet-5 (Sec. VI, ~62k params) on cifarlike
    shards. Construction order and rng stream are pinned by the loop
    oracle's golden (tests/data/real_mode_golden.json) — the model
    functions are the only thing this subclass adds.

    noise=8.0 calibrates cifarlike difficulty so LeNet accuracy climbs
    gradually over many local epochs (CIFAR-10-like convergence dynamics)
    rather than saturating after one epoch.
    """

    name = "lenet"
    model_init = staticmethod(init_lenet)
    model_loss = staticmethod(lenet_loss)
    model_logits = staticmethod(lenet_logits)


@register_ml_backend
class MLPBackend(ImageClassifierBackend):
    """Second real model (``Scenario(ml="mlp")``): a dense MLP
    (models/mlp.py) with a different pytree structure than LeNet (no conv
    leaves) through the identical fused train+push scan — the proof that
    the batched protocol and the Pallas apply path are not LeNet-shaped.
    Pinned by its own golden (tests/data/mlp_golden.json)."""

    name = "mlp"
    model_init = staticmethod(init_mlp)
    model_loss = staticmethod(mlp_loss)
    model_logits = staticmethod(mlp_logits)


def make_ml_hooks(n_users: int, *, sync: bool = False, eta: float = 0.01,
                  beta: float = 0.9, n_train: int = 10000,
                  n_test: int = 2000, alpha: float = 100.0,
                  batch_size: int = 20, aggregation: str = "replace",
                  noise: float = 8.0, seed: int = 0):
    """Returns (hooks dict, state dict with server/clients/eval/backend).

    Historical loop-engine entry point, now an adapter over
    ``LeNetBackend`` (same construction order, same rng stream, same
    jitted per-client epoch — seeded loop runs reproduce bit-for-bit)."""
    backend = LeNetBackend(n_users, sync=sync, eta=eta, beta=beta,
                           n_train=n_train, n_test=n_test, alpha=alpha,
                           batch_size=batch_size, aggregation=aggregation,
                           noise=noise, seed=seed)
    return backend.hooks(), {"server": backend.server,
                             "clients": backend.clients,
                             "accuracy": backend._acc,
                             "backend": backend}
