"""Pure-jnp oracles for the fused momentum update and the server apply."""
from __future__ import annotations

import jax.numpy as jnp


def _f32(x):
    """The kernels read their scalars as f32 (SMEM operands), so a Python
    float is rounded before ``1 - beta`` or ``1 - w`` is formed."""
    return jnp.asarray(x, jnp.float32)


def fused_update_flat_ref(theta, v, g, eta, beta):
    """theta/v/g: flat (or 2-D) f32 arrays.

    Returns (theta', v', sumsq):
        v'     = beta * v + (1 - beta) * g
        theta' = theta - eta * v'
        sumsq  = Sum(v'^2)
    """
    eta, beta = _f32(eta), _f32(beta)
    v_new = beta * v + (1.0 - beta) * g
    theta_new = theta - eta * v_new
    return theta_new, v_new, jnp.sum(jnp.square(v_new))


def fused_apply_flat_ref(cur, v, new, w, inv_eta, beta):
    """cur/v/new: flat (or 2-D) f32 arrays; the server push-apply contract
    (``AsyncParameterServer.push`` / ``serve.server._apply_shard``).

    Returns (mixed, v', sumsq):
        mixed = w * new + (1 - w) * cur
        s     = (cur - mixed) * inv_eta
        v'    = beta * v + (1 - beta) * s
        sumsq = Sum(v'^2)
    """
    w, inv_eta, beta = _f32(w), _f32(inv_eta), _f32(beta)
    mixed = w * new + (1.0 - w) * cur
    s = (cur - mixed) * inv_eta
    v_new = beta * v + (1.0 - beta) * s
    return mixed, v_new, jnp.sum(jnp.square(v_new))
