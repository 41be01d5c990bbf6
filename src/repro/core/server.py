"""Parameter servers: asynchronous (paper Sec. VI) and synchronous (FedAvg).

The async server implements the paper's protocol: clients pull the current
global model, train locally with momentum SGD (Eq. 1), and push; the server
applies the push immediately (lock-free) and advances the version counter.
HOW a push is applied is delegated to a first-class ``AggregationRule``
(core/aggregation.py): ``aggregation="replace"`` reproduces the paper,
while ``fedasync_poly`` / ``gap_aware`` / ``hetero_aware`` mix stale
pushes at reduced weight — the same registry the simulator engines thread
(``SimConfig.aggregation``), so the loop oracle and the batched engines
see one rule implementation.

The server also maintains the global momentum-norm estimate that drives the
Eq. (4) gradient-gap predictions: v <- beta * v + (1-beta) * s with
s = (theta_old - theta_new) / eta, so only ||v||2 (a scalar) ever travels to
clients — the paper's O(1)-per-client distributed implementation.

``kernel="pallas"`` routes the entire apply (mix + momentum + post-update
norm) through the single-HBM-pass Pallas kernel
(``kernels/fused_update.fused_weighted_apply_pallas``) instead of the
three-traversal reference; ``"auto"`` (the default) picks Pallas on TPU and
the bit-stable reference elsewhere.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.fused_update import (fused_weighted_apply_pallas,
                                    kernel_interpret, resolve_kernel_mode)
from .aggregation import AggregationRule, configure_aggregation
from .staleness import LagTracker, gradient_gap, tree_l2_norm


def _tree_sub(a, b):
    return jax.tree.map(lambda x, y: x - y, a, b)


def _tree_axpy(alpha, x, y):
    """alpha*x + y"""
    return jax.tree.map(lambda a_, b_: alpha * a_ + b_, x, y)


def _tree_mix(new, old, alpha):
    return jax.tree.map(lambda n, o: alpha * n + (1 - alpha) * o, new, old)


@dataclasses.dataclass
class PushResult:
    lag: int
    gap_estimate: float     # Eq. (4) gap at push ARRIVAL (pre-application)
    applied_weight: float   # the rule's mixing weight, 1.0 under replace
    version: int


class AsyncParameterServer:
    def __init__(self, params: Any, eta: float, beta: float,
                 aggregation: Union[str, AggregationRule] = "replace",
                 fedasync_alpha: float = 0.6, fedasync_a: float = 0.5,
                 gap_ref: float = 1.0, fleet=None, kernel: str = "auto"):
        """``aggregation`` is a registry name or ``AggregationRule``
        instance (core/aggregation.py). The legacy knob kwargs
        (``fedasync_alpha``/``fedasync_a``/``gap_ref``) still construct
        the matching rule when a name is given with non-default values;
        new code should pass a configured rule instance. ``fleet`` binds
        the run's ``FleetSpec`` for fleet-conditioned rules
        (``hetero_aware``) — ``FederatedSim`` binds it automatically.
        ``kernel`` selects the push-apply implementation:
        ``"pallas"`` fuses mix + momentum + norm into one kernel pass,
        ``"reference"`` keeps the multi-traversal jnp path (bit-stable),
        ``"auto"`` = Pallas on TPU, reference elsewhere."""
        self.eta = eta
        self.beta = beta
        self.rule: AggregationRule = configure_aggregation(
            aggregation, fedasync_alpha=fedasync_alpha,
            fedasync_a=fedasync_a, gap_ref=gap_ref)
        self.aggregation = self.rule.name
        self.fleet_spec = fleet
        self.kernel = resolve_kernel_mode(kernel)
        self.reset(params)

    def reset(self, params: Any) -> None:
        """Start over from ``params``: zero momentum, version 0, nothing in
        flight. The rule and the bound fleet stay."""
        self.params = params
        self.lag_tracker = LagTracker()
        self._v = jax.tree.map(jnp.zeros_like, params)
        self.v_norm = 0.0
        self.in_flight: set = set()

    # ------------------------------------------------------------------ pull
    def pull(self, client_id) -> tuple[Any, int]:
        self.lag_tracker.on_pull(client_id)
        self.in_flight.add(client_id)
        return self.params, self.lag_tracker.version

    def lag_estimate(self, client_id) -> int:
        """Alg. 2 line 4: server-side lag estimate = concurrent tasks."""
        return max(len(self.in_flight) - (1 if client_id in self.in_flight else 0), 0)

    # ------------------------------------------------------------------ push
    def push(self, client_id, new_params: Any) -> PushResult:
        lag = self.lag_tracker.on_push(client_id)
        self.in_flight.discard(client_id)
        old = self.params

        # Eq. (4) gap at push arrival — the momentum norm BEFORE this
        # push is applied (the norm the loop oracle's push log records).
        # Computed once: the rule's weight and the returned gap_estimate
        # share it.
        gap = gradient_gap(self.v_norm, lag, self.eta, self.beta)
        weight = float(self.rule.weight(lag, gap, self.v_norm,
                                        fleet=self.fleet_spec,
                                        users=client_id))
        if self.kernel == "pallas":
            # one fused dispatch over the whole model: mix, server momentum,
            # and ||v'||_2 come out of a single HBM pass — no tree_l2_norm
            # re-traversal
            self.params, self._v, v_norm = fused_weighted_apply_pallas(
                old, self._v, new_params, w=weight, eta=self.eta,
                beta=self.beta, interpret=kernel_interpret())
            self.v_norm = float(v_norm)
        else:
            self.params = _tree_mix(new_params, old, weight)

            # server momentum for Eq. (4): s = (theta_old - theta_new)/eta
            s = jax.tree.map(lambda o, n: (o - n) / max(self.eta, 1e-12),
                             old, self.params)
            self._v = jax.tree.map(
                lambda v, g_: self.beta * v + (1 - self.beta) * g_,
                self._v, s)
            self.v_norm = tree_l2_norm(self._v)
        return PushResult(lag=lag, gap_estimate=gap, applied_weight=weight,
                          version=self.lag_tracker.version)


class SyncServer:
    """FedAvg (McMahan et al.): lock-step rounds, average over the cohort."""

    def __init__(self, params: Any):
        self.reset(params)

    def reset(self, params: Any) -> None:
        """Start over from ``params`` at round 0 with nothing submitted."""
        self.params = params
        self.round = 0
        self._pending: list[Any] = []

    def pull(self, client_id=None):
        return self.params, self.round

    def submit(self, new_params: Any):
        self._pending.append(new_params)

    def aggregate(self) -> int:
        if not self._pending:
            return self.round
        n = len(self._pending)
        stacked = jax.tree.map(lambda *xs: sum(xs) / n, *self._pending)
        self.params = stacked
        self._pending = []
        self.round += 1
        return self.round
