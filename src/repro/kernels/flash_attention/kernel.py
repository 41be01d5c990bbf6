"""Pallas TPU flash attention: blocked causal online-softmax with GQA.

TPU adaptation of the FlashAttention schedule: instead of the CUDA
shared-memory/warp formulation, blocks of Q stay resident in VMEM while the
grid's innermost dimension streams K/V blocks HBM->VMEM; the online-softmax
running max/denominator live in VMEM scratch that persists across the
innermost grid steps (Mosaic revisits the same output block). MXU work is
the two (block_q x d) @ (d x block_k) / (block_q x block_k) @ (block_k x d)
matmuls per step; block sizes default to 512x512 so both matmul operands and
the f32 accumulator fit VMEM (~(512*128 + 512*128 + 512*512)*4B ~ 1.5 MiB)
with dims multiples of the 128-lane / 8-sublane tiling.

Causal skipping: grid steps with block_k_start > block_q_end contribute
nothing and exit early via pl.when (Mosaic still schedules the step, but no
DMA compute is issued) — the standard ~2x saving for causal masks comes from
the index-map never mapping those blocks... they are mapped but skipped;
on-TPU the bandwidth win comes from the compute predicate.

GQA: query head h reads KV head h * KV // H via the k/v index_maps —
no repeat/broadcast materialization.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512
NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
            *, scale: float, causal: bool, block_q: int, block_k: int,
            num_kv_blocks: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * block_q
    k_start = ki * block_k
    needed = (not causal) or (k_start <= q_start + block_q - 1)

    def compute():
        q = q_ref[0, 0].astype(jnp.float32)               # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)               # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)               # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(cols <= rows, s, NEG_INF)
        m_prev = m_ref[...]                               # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                            # (bq, bk)
        alpha = jnp.exp(m_prev - m_new)                   # (bq, 1)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    if causal:
        pl.when(needed)(compute)
    else:
        compute()

    @pl.when(ki == num_kv_blocks - 1)
    def _finalize():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "scale", "block_q",
                                             "block_k", "interpret"))
def flash_attention_kernel(q, k, v, *, causal: bool = True,
                           scale: float | None = None,
                           block_q: int = DEFAULT_BLOCK_Q,
                           block_k: int = DEFAULT_BLOCK_K,
                           interpret: bool = False):
    """q: (B, H, Sq, d); k/v: (B, KV, Sk, d). Sq % block_q == Sk % block_k == 0."""
    B, H, Sq, d = q.shape
    KV, Sk = k.shape[1], k.shape[2]
    assert H % KV == 0 and Sq % block_q == 0 and Sk % block_k == 0
    grid = (B, H, Sq // block_q, Sk // block_k)
    s = (scale if scale is not None else d ** -0.5)

    kernel = functools.partial(
        _kernel, scale=s, causal=causal, block_q=block_q, block_k=block_k,
        num_kv_blocks=Sk // block_k)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b, h, qi, ki: (b, h * KV // H, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b, h, qi, ki: (b, h * KV // H, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),     # running max m
            pltpu.VMEM((block_q, 1), jnp.float32),     # running denom l
            pltpu.VMEM((block_q, d), jnp.float32),     # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)
