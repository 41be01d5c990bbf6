"""jit'd public wrappers: flat-array and whole-pytree fused updates.

``fused_momentum_gap_update_pallas`` is the drop-in Pallas version of
``repro.optim.gap.fused_momentum_gap_update`` (its oracle): it flattens the
parameter pytree once, runs the single-pass kernel, and unflattens — the
gap norm (Eq. 4) comes out of the same HBM pass as the update.

``fused_weighted_apply_pallas`` / ``fused_apply_flat`` are the server-push
twins (mix + momentum + post-update norm — the aggregation hot path), the
Pallas versions of ``repro.optim.gap.fused_weighted_apply``.

``resolve_kernel_mode`` is the one dispatch rule every apply site shares
(``SimConfig.kernel`` / the servers' ``kernel=`` knob): ``"pallas"`` and
``"reference"`` are explicit, ``"auto"`` picks Pallas on TPU and the
reference path elsewhere — interpret mode exists for validation, not
production CPU dispatch — so CI and the loop oracle stay bit-stable.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from .kernel import (DEFAULT_BLOCK_ROWS, LANES, fused_apply_2d,
                     fused_update_2d)

# the knob every apply site accepts; "auto" = Pallas iff the default
# backend is a TPU (elsewhere the kernels only run in interpret mode,
# which validates, not accelerates)
KERNEL_MODES = ("auto", "pallas", "reference")

# smallest grid block: (8, 128) f32 = the TPU f32 tile — going lower
# would just re-pad inside the hardware tile
MIN_BLOCK_ROWS = 8


def resolve_kernel_mode(mode: str) -> str:
    """``"auto"|"pallas"|"reference"`` -> ``"pallas"|"reference"``."""
    if mode not in KERNEL_MODES:
        raise ValueError(f"unknown kernel mode {mode!r}; expected one of "
                         f"{KERNEL_MODES}")
    if mode == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "reference"
    return mode


def kernel_interpret() -> bool:
    """Whether a forced-Pallas run uses interpret mode: on the CPU
    backend only. Any accelerator compiles the kernel (and a non-TPU one
    refuses it loudly rather than running the interpreter)."""
    return jax.default_backend() == "cpu"


def clamp_block_rows(n: int, block_rows: int = DEFAULT_BLOCK_ROWS) -> int:
    """Largest power-of-two block (in rows of 128 lanes) that is <=
    ``block_rows`` and not wastefully larger than the ``n``-element
    payload: for tiny params / sub-block shards the default 1024-row
    block would pad 512 KiB around a few KiB of data (pad waste > payload).
    The clamp halves the block until one block covers the payload (floored
    at the (8, 128) f32 hardware tile), so pad waste is bounded by one
    block and grids of multi-block payloads keep the requested block."""
    if block_rows <= MIN_BLOCK_ROWS:
        return MIN_BLOCK_ROWS
    rows = max(-(-n // LANES), MIN_BLOCK_ROWS)
    while block_rows > MIN_BLOCK_ROWS and block_rows // 2 >= rows:
        block_rows //= 2
    return block_rows


def _pad_to_grid(x, block_rows):
    n = x.size
    per_block = block_rows * LANES
    padded = ((n + per_block - 1) // per_block) * per_block
    x = jnp.pad(x.reshape(-1), (0, padded - n))
    return x.reshape(padded // LANES, LANES), n


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def fused_update_flat(theta, v, g, eta, beta, *,
                      block_rows: int = DEFAULT_BLOCK_ROWS,
                      interpret: bool = False):
    """Flat f32 arrays of any (identical) size; zero-pads to the block grid.

    Returns (theta', v', sumsq). Padding is zeros in v and g, so v' padding
    stays zero and contributes nothing to sumsq. ``block_rows`` is clamped
    to the payload (``clamp_block_rows``); empty arrays short-circuit."""
    shape = theta.shape
    if theta.size == 0:
        return (theta.astype(jnp.float32), v.astype(jnp.float32),
                jnp.zeros((), jnp.float32))
    block_rows = clamp_block_rows(theta.size, block_rows)
    t2, n = _pad_to_grid(theta.astype(jnp.float32), block_rows)
    v2, _ = _pad_to_grid(v.astype(jnp.float32), block_rows)
    g2, _ = _pad_to_grid(g.astype(jnp.float32), block_rows)
    t_o, v_o, sumsq = fused_update_2d(t2, v2, g2, eta, beta,
                                      block_rows=block_rows, interpret=interpret)
    return (t_o.reshape(-1)[:n].reshape(shape),
            v_o.reshape(-1)[:n].reshape(shape), sumsq)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def fused_apply_flat(cur, v, new, w, inv_eta, beta, *,
                     block_rows: int = DEFAULT_BLOCK_ROWS,
                     interpret: bool = False):
    """Server push apply on flat f32 arrays (mix + momentum + sq-norm in
    one pass); zero-pads to the block grid. Padding mixes 0 with 0, so the
    padded lanes of ``mixed``/``v'`` stay zero and contribute nothing to
    sumsq. ``w``/``inv_eta``/``beta`` are traced scalars — one executable
    per shape, shared across rules and knob values.

    Returns (mixed, v', sumsq)."""
    shape = cur.shape
    if cur.size == 0:
        return (cur.astype(jnp.float32), v.astype(jnp.float32),
                jnp.zeros((), jnp.float32))
    block_rows = clamp_block_rows(cur.size, block_rows)
    c2, n = _pad_to_grid(cur.astype(jnp.float32), block_rows)
    v2, _ = _pad_to_grid(v.astype(jnp.float32), block_rows)
    n2, _ = _pad_to_grid(new.astype(jnp.float32), block_rows)
    m_o, v_o, sumsq = fused_apply_2d(c2, v2, n2, w, inv_eta, beta,
                                     block_rows=block_rows,
                                     interpret=interpret)
    return (m_o.reshape(-1)[:n].reshape(shape),
            v_o.reshape(-1)[:n].reshape(shape), sumsq)


def _flatten_concat(tree):
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.concatenate([l.reshape(-1).astype(jnp.float32)
                            for l in leaves])


def _split_back(flat, leaves, treedef, keep_dtype: bool):
    offs = [0]
    for l in leaves:
        offs.append(offs[-1] + l.size)
    out = []
    for i, l in enumerate(leaves):
        piece = flat[offs[i]:offs[i + 1]].reshape(l.shape)
        out.append(piece.astype(l.dtype) if keep_dtype else piece)
    return treedef.unflatten(out)


def fused_momentum_gap_update_pallas(params: Any, v: Any, grads: Any, *,
                                     eta: float, beta: float, lag,
                                     block_rows: int = DEFAULT_BLOCK_ROWS,
                                     interpret: bool = False):
    """Pytree version; same contract as optim.gap.fused_momentum_gap_update.

    Returns (new_params, new_v, gap_norm) with
    gap_norm = eta * (1 - beta^lag) / (1 - beta) * ||v'||_2."""
    leaves, treedef = jax.tree_util.tree_flatten(params)
    flat_p = _flatten_concat(params)
    flat_v = _flatten_concat(v)
    flat_g = _flatten_concat(grads)
    p_o, v_o, sumsq = fused_update_flat(flat_p, flat_v, flat_g, eta, beta,
                                        block_rows=block_rows, interpret=interpret)
    scale = eta * (1.0 - beta ** jnp.asarray(lag, jnp.float32)) / (1.0 - beta)
    return (_split_back(p_o, leaves, treedef, keep_dtype=True),
            _split_back(v_o, leaves, treedef, keep_dtype=False),
            scale * jnp.sqrt(sumsq))


def fused_weighted_apply_pallas(params: Any, v: Any, new_params: Any, *,
                                w, eta: float, beta: float,
                                block_rows: int = DEFAULT_BLOCK_ROWS,
                                interpret: bool = False):
    """Pytree version of the server push apply; same contract as
    optim.gap.fused_weighted_apply (its oracle): one flatten, ONE kernel
    pass over the whole model for the weighted mix + server momentum
    recursion + post-update norm, one unflatten — no separate
    ``tree_l2_norm`` traversal.

    Returns (mixed_params, new_v, v_norm) with v_norm = ||v'||_2 (a 0-d
    f32 scalar — callers float() it on demand)."""
    leaves, treedef = jax.tree_util.tree_flatten(params)
    flat_p = _flatten_concat(params)
    flat_v = _flatten_concat(v)
    flat_n = _flatten_concat(new_params)
    inv_eta = 1.0 / max(eta, 1e-12)
    m_o, v_o, sumsq = fused_apply_flat(flat_p, flat_v, flat_n, w, inv_eta,
                                       beta, block_rows=block_rows,
                                       interpret=interpret)
    return (_split_back(m_o, leaves, treedef, keep_dtype=True),
            _split_back(v_o, leaves, treedef, keep_dtype=False),
            jnp.sqrt(sumsq))
