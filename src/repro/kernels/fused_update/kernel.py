"""Pallas TPU kernels: fused momentum update / server apply + gap norm.

Two kernels share one layout and one motivation. The paper's per-push
work over every parameter is HBM-bound either way — the arithmetic
intensity is so low (~4 FLOPs / 20 bytes) that memory traffic IS the
cost — so each fuses its multi-pass naive schedule into ONE pass with
the sum-of-squares reduction accumulated on-chip.

``_kernel`` (the CLIENT step, Eq. 1 + Eq. 4):

    v'     = beta * v + (1 - beta) * g          (read v, g; write v')
    theta' = theta - eta * v'                   (read theta, v'; write theta')
    gap    = scale * ||v'||_2                   (read v')

i.e. 5 reads + 2 writes of N floats naively; fused: 3 reads + 2 writes —
a ~7/5 = 1.4x traffic cut vs. the best 2-pass schedule, ~2x vs. naive.

``_apply_kernel`` (the SERVER push apply — the aggregation hot path of
``core/server.py`` / ``serve/server.py`` / the fused real-ML push scan):

    mixed = w * new + (1 - w) * cur             (read new, cur; write mixed)
    s     = (cur - mixed) / eta                 (re-read cur, mixed)
    v'    = beta * v + (1 - beta) * s           (read v; write v')
    norm  = ||v'||_2                            (re-read v')

i.e. 7 array passes naively (what ``AsyncParameterServer.push`` +
``tree_l2_norm`` dispatch); fused: 3 reads (cur, v, new) + 2 writes
(mixed, v') = the same 1.4x/2x traffic cut, per push.

Layout: the parameter pytree is flattened and concatenated to a single f32
vector, padded and viewed as (rows, 128) — the last dim matches the TPU
lane width, rows are tiled in VMEM-sized blocks. Grid is 1-D over row
blocks; each step folds its block's v'^2 into one (8, 128) f32 tile of a
VMEM ``(nblk, 8, 128)`` partial output — elementwise adds only, and a block
shape Mosaic accepts at any ``nblk`` — which the XLA epilogue sums.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
DEFAULT_BLOCK_ROWS = 1024   # (1024, 128) f32 = 512 KiB per operand in VMEM
SUBLANES = 8                # f32 sublanes of one (8, 128) vreg tile


def _fold_sumsq(x):
    """(block_rows, 128) -> (1, 8, 128): Sum(x^2) folded onto one tile."""
    sq = x * x
    return jnp.sum(sq.reshape(-1, SUBLANES, LANES), axis=0)[None]


def _partial_spec_shape(nblk):
    """Block spec and shape of the per-block partial sums."""
    spec = pl.BlockSpec((1, SUBLANES, LANES), lambda i: (i, 0, 0))
    return spec, jax.ShapeDtypeStruct((nblk, SUBLANES, LANES), jnp.float32)


def _kernel(theta_ref, v_ref, g_ref, eta_ref, beta_ref,
            theta_out_ref, v_out_ref, partial_ref):
    eta = eta_ref[0]
    beta = beta_ref[0]
    v_new = beta * v_ref[...] + (1.0 - beta) * g_ref[...]
    theta_out_ref[...] = theta_ref[...] - eta * v_new
    v_out_ref[...] = v_new
    partial_ref[...] = _fold_sumsq(v_new)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def fused_update_2d(theta, v, g, eta, beta, *, block_rows: int = DEFAULT_BLOCK_ROWS,
                    interpret: bool = False):
    """theta/v/g: (rows, 128) f32, rows % block_rows == 0, block_rows % 8 == 0.

    Returns (theta', v', sumsq) with sumsq = Sum(v'^2) (f32 scalar)."""
    rows, lanes = theta.shape
    assert lanes == LANES and rows % block_rows == 0 \
        and block_rows % SUBLANES == 0, (rows, lanes, block_rows)
    nblk = rows // block_rows
    eta = jnp.asarray([eta], jnp.float32)
    beta = jnp.asarray([beta], jnp.float32)

    block = pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))
    scalar = pl.BlockSpec(memory_space=pltpu.SMEM)
    partial_spec, partial_shape = _partial_spec_shape(nblk)
    theta_o, v_o, partials = pl.pallas_call(
        _kernel,
        grid=(nblk,),
        in_specs=[block, block, block, scalar, scalar],
        out_specs=[block, block, partial_spec],
        out_shape=[
            jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
            jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
            partial_shape,
        ],
        interpret=interpret,
        name="fused_momentum_gap_update",
    )(theta, v, g, eta, beta)
    return theta_o, v_o, jnp.sum(partials)


def _apply_kernel(cur_ref, v_ref, new_ref, w_ref, inv_eta_ref, beta_ref,
                  mixed_ref, v_out_ref, partial_ref):
    w = w_ref[0]
    inv_eta = inv_eta_ref[0]
    beta = beta_ref[0]
    # a traced zero keeps each product rounded on its own: contracted to
    # an FMA, `mixed` moves an ulp, and `cur - mixed` scaled by inv_eta
    # carries that ulp into v' up to 1/eta-fold. The interpreter on a CPU
    # contracts some lanes and not others; the chip adds zeros exactly.
    zero = w - w
    mixed = (w * new_ref[...] + zero) + ((1.0 - w) * cur_ref[...] + zero)
    s = (cur_ref[...] - mixed) * inv_eta
    v_new = beta * v_ref[...] + (1.0 - beta) * s
    mixed_ref[...] = mixed
    v_out_ref[...] = v_new
    partial_ref[...] = _fold_sumsq(v_new)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def fused_apply_2d(cur, v, new, w, inv_eta, beta, *,
                   block_rows: int = DEFAULT_BLOCK_ROWS,
                   interpret: bool = False):
    """Server push apply. cur/v/new: (rows, 128) f32, rows % block_rows == 0,
    block_rows % 8 == 0;
    ``w``/``inv_eta``/``beta`` are traced scalars (SMEM operands), so every
    push of a given shape shares one executable regardless of rule/knobs.

    Returns (mixed, v', sumsq) with sumsq = Sum(v'^2) (f32 scalar)."""
    rows, lanes = cur.shape
    assert lanes == LANES and rows % block_rows == 0 \
        and block_rows % SUBLANES == 0, (rows, lanes, block_rows)
    nblk = rows // block_rows
    w = jnp.asarray(w, jnp.float32).reshape(1)
    inv_eta = jnp.asarray(inv_eta, jnp.float32).reshape(1)
    beta = jnp.asarray(beta, jnp.float32).reshape(1)

    block = pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))
    scalar = pl.BlockSpec(memory_space=pltpu.SMEM)
    partial_spec, partial_shape = _partial_spec_shape(nblk)
    mixed, v_o, partials = pl.pallas_call(
        _apply_kernel,
        grid=(nblk,),
        in_specs=[block, block, block, scalar, scalar, scalar],
        out_specs=[block, block, partial_spec],
        out_shape=[
            jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
            jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
            partial_shape,
        ],
        interpret=interpret,
        name="fused_weighted_apply",
    )(cur, v, new, w, inv_eta, beta)
    return mixed, v_o, jnp.sum(partials)
