"""First-class aggregation rules: how the server APPLIES a push.

The paper's Sec. VI server uses the plain "replace" rule, but the
gradient-gap machinery (Eqs. 2-4) exists precisely because stale pushes
should not count at full weight. This module turns the application rule
into a registry object mirroring the ``Policy`` carry protocol
(core/policies.py), so staleness-aware aggregation is visible to EVERY
layer of the push path — the loop oracle's ``AsyncParameterServer``, the
vectorized engine's in-slot push replay, the jax engine's ``lax.scan``
push-log write, and the fused train+push scan of
``realml.BatchedMLBackend`` — instead of living as an if/elif ladder
inside the server.

An ``AggregationRule`` exposes three paths:

``weight(lag, gap, v_norm, fleet=None, users=None)``
    The host (numpy) path: the applied mixing weight in ``[0, 1]`` for a
    push (or a whole finisher cohort — ``lag``/``gap`` broadcast as
    arrays). ``fleet`` is the run's ``FleetSpec`` and ``users`` the
    pushing user id(s); fleet-conditioned rules read device classes from
    them. The server applies ``theta <- w * theta_push + (1-w) * theta``
    (``w == 1`` is the paper's replace rule).
``init_carry(n, cfg, fleet=None)``
    One pytree of per-run rule state threaded by every engine
    (``EngineState.agg_carry``) — e.g. ``hetero_aware``'s per-user
    device-class scale vector, gathered once at run start. ``None`` for
    stateless rules. The carry is RUN-CONSTANT lookup state, not an
    evolving accumulator: the host ``weight()`` path never sees it
    (fleet-derived values must be recomputable from ``fleet``/``users``)
    and the fused real-ML push scan reads one snapshot per cohort, so a
    carry that ``scan_weight`` mutated per push would diverge across
    engines — return it unchanged.
``scan_weight(carry, pv)``
    The traced twin, called inside the jax engines' scans: ``pv`` is a
    push view (``jnp``, ``lag``, ``gap``, ``v_norm``, ``users``,
    ``consts`` from ``scan_operands``, ``float_dtype``; arrays over the
    fleet in the trace scan, per-push scalars in the fused real-ML
    scan — write rules to broadcast). Must return ``(carry, weight)``
    with the carry unchanged (see ``init_carry``). Instance knobs must
    flow through ``scan_operands`` (traced), never be closed over —
    compiled scans are cached per ``jax_cache_key()``.

Equivalence contract: for a given push the three paths must produce the
same weight — tests/test_engine_matrix.py pins loop/vectorized/jax weight
parity for every registered rule, and tests/test_aggregation.py holds the
property ``0 <= weight <= 1`` plus ``fedasync_poly``'s monotone
non-increase in lag.

Ships: ``replace`` (the paper, weight 1), ``fedasync_poly`` (Xie et al.
[30]: ``alpha * (1+lag)^-a``), ``gap_aware`` (dampen by the Eq. 4 gap
estimate: ``1 / (1 + gap/gap_ref)``), and ``hetero_aware`` (AutoFL-style
fleet conditioning: the staleness polynomial scaled per device class by
relative training speed, so slow — stale-prone — classes contribute
less). Strings resolve through the registry (``resolve_aggregation``);
instances carry custom knobs.
"""
from __future__ import annotations

from typing import Dict, Tuple, Type

import numpy as np

__all__ = ["AggregationRule", "ReplaceRule", "FedAsyncPolyRule",
           "GapAwareRule", "HeteroAwareRule", "register_aggregation",
           "registered_aggregations", "resolve_aggregation",
           "configure_aggregation", "aggregation_support", "hetero_scales"]


class AggregationRule:
    """Base aggregation rule. Subclass, set ``name``, implement the
    paths, and decorate with ``@register_aggregation``.

    Class attributes engines dispatch on:

    - ``needs_gap``: the weight reads the Eq. (4) gap / momentum norm, so
      the fused real-ML push scan must materialize the per-push norm even
      when no push log is collected.
    - ``supports_jax``: a traced ``scan_weight`` exists, so the rule can
      run inside the jax engine's scan and the fused real-ML push scan.
      ``SimConfig`` validates the flag against the actual hook at
      construction; rules without it degrade the jax engine to the numpy
      path (and the fused real-ML finish to per-push server calls).
    """

    name: str = ""
    needs_gap: bool = False
    supports_jax: bool = True

    # ------------------------------------------------------------ host path
    def weight(self, lag, gap, v_norm, fleet=None, users=None):
        """Applied mixing weight(s) in ``[0, 1]``; ``lag``/``gap``
        broadcast (scalars from the loop server, arrays from the
        vectorized engine's finisher cohorts)."""
        raise NotImplementedError(
            f"aggregation rule {self.name!r} implements no weight()")

    # ------------------------------------------------------------ carry
    def init_carry(self, n: int, cfg=None, fleet=None):
        """Per-run rule state as ONE pytree (``EngineState.agg_carry``);
        ``None`` for stateless rules."""
        return None

    def scan_operands(self, cfg) -> tuple:
        """Instance knobs the traced hook needs, as a flat scalar tuple
        (traced operands — ``pv.consts`` — so knob sweeps share one
        compiled scan). ``cfg`` is the run's SimConfig when an engine
        calls this, but may be ``None`` outside a run (a backend that
        was never bound to a sim) — keep knobs on the instance rather
        than reading cfg where possible."""
        return ()

    def jax_cache_key(self):
        """Hashable token identifying this rule's ``scan_weight``
        behavior (same contract as ``Policy.jax_cache_key``): class-keyed
        when provably safe, else instance-keyed."""
        if not vars(self) or \
                type(self).scan_operands is not AggregationRule.scan_operands:
            return type(self)
        return self


    # ------------------------------------------------------------ traced path
    def scan_weight(self, carry, pv):
        """Traced weight inside a scan step. ``pv`` carries ``jnp``,
        ``lag``, ``gap``, ``v_norm``, ``users``, ``consts``,
        ``float_dtype``; return ``(carry, weight)`` with ``weight``
        broadcastable against ``pv.lag``. Only called when
        ``supports_jax``."""
        raise TypeError(
            f"aggregation rule {self.name!r} sets supports_jax but "
            "inherits the base scan_weight; implement the hook or clear "
            "the flag to degrade to the numpy engines")


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------
_REGISTRY: Dict[str, Type[AggregationRule]] = {}
_INSTANCES: Dict[str, AggregationRule] = {}     # singletons for strings


def register_aggregation(cls: Type[AggregationRule]) -> Type[AggregationRule]:
    """Class decorator: make ``cls`` resolvable as ``cls.name``."""
    if not cls.name:
        raise ValueError(f"{cls.__name__} must set a registry name")
    _REGISTRY[cls.name] = cls
    _INSTANCES.pop(cls.name, None)              # re-registration wins
    return cls


def registered_aggregations() -> Tuple[str, ...]:
    return tuple(_REGISTRY)


def resolve_aggregation(rule) -> AggregationRule:
    """String -> registered singleton; AggregationRule instance -> itself."""
    if isinstance(rule, AggregationRule):
        return rule
    if isinstance(rule, str):
        if rule not in _REGISTRY:
            raise ValueError(
                f"unknown aggregation {rule!r}; expected one of "
                f"{registered_aggregations()} or an AggregationRule "
                "instance")
        if rule not in _INSTANCES:
            _INSTANCES[rule] = _REGISTRY[rule]()
        return _INSTANCES[rule]
    raise ValueError(f"aggregation must be a name or AggregationRule "
                     f"instance, got {type(rule).__name__}")


def configure_aggregation(rule, *, fedasync_alpha: float = 0.6,
                          fedasync_a: float = 0.5,
                          gap_ref: float = 1.0) -> AggregationRule:
    """``resolve_aggregation`` plus the legacy knob kwargs both servers
    accept: a registry NAME given with non-default knob values constructs
    the matching configured rule instead of the shared singleton. Rule
    instances pass through untouched (their own knobs win). One home for
    the ladder so ``AsyncParameterServer`` and the sharded serving tier
    cannot drift."""
    if isinstance(rule, str) and rule == "fedasync_poly" \
            and (fedasync_alpha != 0.6 or fedasync_a != 0.5):
        return FedAsyncPolyRule(fedasync_alpha, fedasync_a)
    if isinstance(rule, str) and rule == "gap_aware" and gap_ref != 1.0:
        return GapAwareRule(gap_ref)
    return resolve_aggregation(rule)


def aggregation_support(rule: AggregationRule) -> Dict[str, bool]:
    """Which paths ``rule`` GENUINELY implements (flag set AND the base
    stub overridden) — the SimConfig-validation twin of
    ``policies.engine_support``."""
    cls = type(rule)
    return {
        "host": cls.weight is not AggregationRule.weight,
        "jax": (rule.supports_jax and
                cls.scan_weight is not AggregationRule.scan_weight),
    }


# ---------------------------------------------------------------------------
# Shipped rules
# ---------------------------------------------------------------------------
@register_aggregation
class ReplaceRule(AggregationRule):
    """The paper's Sec. VI rule: every push lands at full weight."""

    name = "replace"

    def weight(self, lag, gap, v_norm, fleet=None, users=None):
        lag = np.asarray(lag)
        return np.ones(lag.shape) if lag.ndim else 1.0

    def scan_weight(self, carry, pv):
        jnp = pv.jnp
        return carry, jnp.ones(jnp.shape(pv.lag), pv.float_dtype)


@register_aggregation
class FedAsyncPolyRule(AggregationRule):
    """FedAsync polynomial staleness weighting (Xie et al. [30]):
    ``w = alpha * (1 + lag)^-a`` — monotone non-increasing in lag,
    bounded by ``alpha <= 1``."""

    name = "fedasync_poly"

    def __init__(self, alpha: float = 0.6, a: float = 0.5):
        if not 0.0 <= alpha <= 1.0:
            raise ValueError(f"alpha must be in [0, 1], got {alpha}")
        if a < 0.0:
            raise ValueError(f"a must be non-negative, got {a}")
        self.alpha = float(alpha)
        self.a = float(a)

    def scan_operands(self, cfg):
        return (self.alpha, self.a)

    def weight(self, lag, gap, v_norm, fleet=None, users=None):
        # np.power on the scalar path too: python ** disagrees with the
        # np.power ufunc by an ulp for some bases, and the loop server
        # (scalar) must produce the vectorized engine's (array) bits —
        # same trick as staleness.momentum_scale
        return self.alpha * np.power(1.0 + lag, -self.a)

    def scan_weight(self, carry, pv):
        alpha, a = pv.consts
        return carry, alpha * (1.0 + pv.lag) ** (-a)


@register_aggregation
class GapAwareRule(AggregationRule):
    """Dampen by the Eq. (4) gradient-gap estimate: a push predicted to
    land ``gap`` away from the current model mixes at
    ``w = 1 / (1 + gap / gap_ref)`` — fresh pushes (gap 0) at full
    weight, weight halved at ``gap == gap_ref``."""

    name = "gap_aware"
    needs_gap = True

    def __init__(self, gap_ref: float = 1.0):
        if gap_ref <= 0.0:
            raise ValueError(f"gap_ref must be positive, got {gap_ref}")
        self.gap_ref = float(gap_ref)

    def scan_operands(self, cfg):
        return (self.gap_ref,)

    def weight(self, lag, gap, v_norm, fleet=None, users=None):
        # no clamp: the constructor guarantees gap_ref > 0, and a host
        # clamp the traced path lacks would break three-path equivalence
        return 1.0 / (1.0 + gap / self.gap_ref)

    def scan_weight(self, carry, pv):
        (gap_ref,) = pv.consts
        return carry, 1.0 / (1.0 + pv.gap / gap_ref)


_SCALE_CACHE: dict = {}      # id(fleet) -> (fleet strong ref, scales)
_SCALE_CACHE_MAX = 8


def hetero_scales(fleet) -> np.ndarray:
    """Per-catalog-row device-class scale in ``(0, 1]``: relative
    training speed ``min(t_train) / t_train`` — the fastest class scores
    1.0, a class twice as slow 0.5. Slow classes hold the global model
    longest (Lemma 1 couples lag to training duration), so AutoFL-style
    conditioning downweights exactly the stale-prone contributions.

    Fleet-constant, but called per push on the loop-server path — a
    small keep-alive cache (the strong ref pins the id) makes repeat
    lookups O(1) instead of O(catalog) per push."""
    hit = _SCALE_CACHE.pop(id(fleet), None)    # pop+reinsert = LRU order
    if hit is not None and hit[0] is fleet:
        _SCALE_CACHE[id(fleet)] = hit
        return hit[1]
    tt = np.asarray(fleet.tables.t_train, dtype=np.float64)
    scales = tt.min() / tt
    if len(_SCALE_CACHE) >= _SCALE_CACHE_MAX:
        _SCALE_CACHE.pop(next(iter(_SCALE_CACHE)))  # evict LRU
    _SCALE_CACHE[id(fleet)] = (fleet, scales)
    return scales


@register_aggregation
class HeteroAwareRule(AggregationRule):
    """Fleet-conditioned staleness weighting (AutoFL-style: Kim & Wu
    '21 motivate conditioning on device-class heterogeneity, DEAL (Zou
    et al. '21) energy-aware client weighting): the FedAsync polynomial
    scaled per device class by ``hetero_scales`` —
    ``w = scale(class(u)) * (1 + lag)^-a``.

    The per-user scale vector is the rule's carry
    (``init_carry(fleet=...)`` gathers it once from ``FleetSpec``); the
    host path reads it from the ``FleetSpec`` directly, so a bound fleet
    is REQUIRED — the rule refuses to silently ignore heterogeneity."""

    name = "hetero_aware"

    def __init__(self, a: float = 0.5):
        if a < 0.0:
            raise ValueError(f"a must be non-negative, got {a}")
        self.a = float(a)

    def scan_operands(self, cfg):
        return (self.a,)

    def init_carry(self, n, cfg=None, fleet=None):
        if fleet is None:
            raise ValueError(
                "hetero_aware needs the run's FleetSpec to derive "
                "device-class scales; engines pass it automatically")
        return {"scale": hetero_scales(fleet)[fleet.device_ids]}

    def weight(self, lag, gap, v_norm, fleet=None, users=None):
        if fleet is None or users is None:
            raise ValueError(
                "hetero_aware weights are fleet-conditioned: pass the "
                "run's FleetSpec and the pushing user id(s) (bind the "
                "server to a fleet, or run through an engine)")
        scale = hetero_scales(fleet)[fleet.device_ids[users]]
        # np.power for scalar-vs-array bit identity (see FedAsyncPolyRule)
        return scale * np.power(1.0 + lag, -self.a)

    def scan_weight(self, carry, pv):
        (a,) = pv.consts
        scale = carry["scale"][pv.users]
        return carry, scale * (1.0 + pv.lag) ** (-a)
