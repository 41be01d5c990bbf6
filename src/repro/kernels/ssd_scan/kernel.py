"""Pallas TPU kernel: Mamba2 SSD intra-chunk scan (state-space duality).

TPU adaptation of the SSD algorithm (arXiv:2405.21060). The GPU reference
implementation leans on warp-level primitives for the intra-chunk cumsum;
on TPU we restate the whole intra-chunk computation as dense MXU matmuls
over (chunk x chunk) and (chunk x state) tiles held in VMEM:

    per (batch*head, chunk) grid step, with Q = chunk length:
      cum   = cumsum(dt * A)                       (Q,)      VPU
      M     = tril(exp(cum_i - cum_j))             (Q, Q)    VPU
      S     = (C @ B^T) * M                        (Q, Q)    MXU
      Yin   = S @ (dt * X)                         (Q, ph)   MXU
      Sc    = (B * dt * exp(cum_Q - cum))^T @ X    (s, ph)   MXU  (chunk state)

    outputs: Yin, Sc, exp(cum) and exp(cum_Q) — the cheap inter-chunk
    recurrence (a length-S/Q scan over (s, ph) states) and the Y_inter
    correction are XLA-side in ops.py.

VMEM budget per step (Q=256, s=128, ph=64, f32): X 64 KiB, B/C 128 KiB each,
M + S 256 KiB each — well under the ~16 MiB VMEM arena; all tile dims are
multiples of (8, 128) after the (Q, s/ph) layouts.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref,
            y_ref, state_ref, expcum_ref, decay_ref, *, chunk: int):
    A = a_ref[0]                                         # scalar for this head
    dt = dt_ref[0].astype(jnp.float32)                   # (Q,)
    l = dt * A                                           # (Q,) <= 0
    cum = jnp.cumsum(l)                                  # (Q,)

    X = x_ref[0].astype(jnp.float32)                     # (Q, ph)
    Bm = b_ref[0].astype(jnp.float32)                    # (Q, s)
    Cm = c_ref[0].astype(jnp.float32)                    # (Q, s)

    diff = cum[:, None] - cum[None, :]                   # (Q, Q)
    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    M = jnp.where(cols <= rows, jnp.exp(diff), 0.0)      # causal decay mask

    scores = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * M
    dX = dt[:, None] * X                                 # (Q, ph)
    y_ref[0] = jax.lax.dot_general(scores, dX, (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)

    decay_end = jnp.exp(cum[-1] - cum)                   # (Q,)
    Bw = Bm * (dt * decay_end)[:, None]                  # (Q, s)
    state_ref[0, 0] = jax.lax.dot_general(Bw, X, (((0,), (0,)), ((), ())),
                                          preferred_element_type=jnp.float32)
    expcum_ref[0] = jnp.exp(cum)
    decay_ref[0, 0] = jnp.exp(cum[-1])


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_intra_chunk(X, dtv, A, Bh, Ch, *, chunk: int, interpret: bool = False):
    """X: (BH, S, ph); dtv: (BH, S); A: (BH,); Bh/Ch: (BH, S, s). S % chunk == 0.

    Returns (Y_intra (BH,S,ph) f32, S_chunk (BH,nc,s,ph) f32,
             expcum (BH,S) f32, chunk_decay (BH,nc) f32)."""
    BH, S, ph = X.shape
    s = Bh.shape[-1]
    assert S % chunk == 0
    nc = S // chunk

    grid = (BH, nc)
    kernel = functools.partial(_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, ph), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, chunk), lambda i, c: (i, c)),
            pl.BlockSpec((1,), lambda i, c: (i,)),
            pl.BlockSpec((1, chunk, s), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, chunk, s), lambda i, c: (i, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, ph), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, 1, s, ph), lambda i, c: (i, c, 0, 0)),
            pl.BlockSpec((1, chunk), lambda i, c: (i, c)),
            pl.BlockSpec((1, 1), lambda i, c: (i, c)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, ph), jnp.float32),
            jax.ShapeDtypeStruct((BH, nc, s, ph), jnp.float32),
            jax.ShapeDtypeStruct((BH, S), jnp.float32),
            jax.ShapeDtypeStruct((BH, nc), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="ssd_intra_chunk",
    )(X, dtv, A, Bh, Ch)
