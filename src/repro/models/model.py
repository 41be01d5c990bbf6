"""Model assembly: embeddings, scanned layer stacks, LM / enc-dec heads.

Every architecture family exposes the same functional API via ``build_model``:

    model.init(key)                          -> params pytree
    model.loss(params, batch)                -> (scalar, metrics)
    model.prefill(params, batch)             -> (last_logits, cache)
    model.decode_step(params, cache, batch)  -> (logits, cache)
    model.init_cache(batch, max_seq)         -> cache pytree (zeros)

Layer stacks are ``lax.scan`` over stacked parameters so the HLO size is
independent of depth — essential for compiling 80-layer configs quickly.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from .attention import attention, cross_kv, init_attention
from .common import apply_norm, dense_init, embed_init, norm_params
from .config import ModelConfig
from .moe import init_mlp, init_moe, mlp, moe
from .ssm import init_ssm, init_ssm_state, ssm_block, ssm_decode_step


class Model(NamedTuple):
    cfg: ModelConfig
    init: Callable[..., Any]
    loss: Callable[..., Any]
    prefill: Callable[..., Any]
    decode_step: Callable[..., Any]
    init_cache: Callable[..., Any]


# ------------------------------------------------------------------ blocks
def transformer_block(x, p, cfg, positions=None, mask=None, kv_cache=None,
                      cache_pos=None, cross=None):
    """Pre-norm residual block. Returns (x, new_kv_cache, aux)."""
    h = apply_norm(x, p["ln1"], cfg)
    a, new_cache = attention(h, p["attn"], cfg, positions=positions, mask=mask,
                             kv_cache=kv_cache, cache_pos=cache_pos)
    x = x + a
    if cross is not None:  # whisper decoder cross-attention
        h = apply_norm(x, p["ln_x"], cfg)
        a, _ = attention(h, p["cross_attn"], cfg, positions=None, mask=None,
                         kv_override=cross)
        x = x + a
    h = apply_norm(x, p["ln2"], cfg)
    aux = jnp.float32(0.0)
    if cfg.family == "moe":
        m, aux = moe(h, p["moe"], cfg)
    else:
        m = mlp(h, p["mlp"], cfg)
    return x + m, new_cache, aux


def init_transformer_block(key, cfg, cross: bool = False):
    ks = jax.random.split(key, 4)
    p = {"ln1": norm_params(cfg.d_model, cfg),
         "attn": init_attention(ks[0], cfg),
         "ln2": norm_params(cfg.d_model, cfg)}
    if cross:
        p["ln_x"] = norm_params(cfg.d_model, cfg)
        p["cross_attn"] = init_attention(ks[1], cfg)
    if cfg.family == "moe":
        p["moe"] = init_moe(ks[2], cfg)
    else:
        p["mlp"] = init_mlp(ks[3], cfg)
    return p


def mamba_layer(x, p, cfg, state=None):
    h = apply_norm(x, p["ln1"], cfg)
    out, new_state = ssm_block(h, p["ssm"], cfg, state=state)
    return x + out, new_state


def init_mamba_layer(key, cfg):
    return {"ln1": norm_params(cfg.d_model, cfg), "ssm": init_ssm(key, cfg)}


def _maybe_remat(fn, cfg):
    return jax.checkpoint(fn) if cfg.remat == "full" else fn


def _shard_seq(x, cfg):
    """Layer-boundary sharding constraint on the residual stream (B, S, D).

    Two jobs:
    1. PIN GSPMD's propagation: without an anchor at every layer boundary,
       the partitioner may pick different strategies for different depths
       (observed: a 1-layer unrolled variant costing MORE per device than a
       2-layer one) and insert resharding all-gather/permute churn between
       layers. Pinned boundaries make per-layer cost uniform, so cost
       extrapolates linearly in depth.
    2. Sequence parallelism (cfg.shard_activations): put S on "model"
       between layers — norms are elementwise over D so SP is free, the
       remat stack shrinks by the TP degree, and GSPMD gathers S only in
       front of attention (Megatron-SP pattern).

    No-op outside a mesh context (host tests)."""
    from repro.launch.mesh import get_abstract_mesh
    mesh = get_abstract_mesh()
    if mesh is None or "model" not in (mesh.axis_names or ()):
        return x
    from jax.sharding import PartitionSpec as P
    if cfg.parallel_layout == "dp":
        dp = tuple(mesh.axis_names)
    else:
        dp = tuple(a for a in mesh.axis_names if a != "model")
    dpn = 1
    for a in dp:
        dpn *= mesh.shape[a]
    batch_ax = dp if (x.shape[0] % max(dpn, 1) == 0 and dpn > 1) else None
    seq_ax = "model" if (cfg.shard_activations
                         and x.shape[1] % mesh.shape["model"] == 0) else None
    return jax.lax.with_sharding_constraint(x, P(batch_ax, seq_ax, None))


def _scan(body, init, xs, cfg):
    """lax.scan, or a python-unrolled equivalent when cfg.scan_layers=False.

    The unrolled path consumes the SAME stacked params (slicing the leading
    layer dim) so shardings/init are identical; it exists because XLA's CPU
    cost analysis counts while-loop bodies once, so only an unrolled stack
    gives exact per-layer flop/byte/collective counts."""
    if cfg.scan_layers:
        return jax.lax.scan(body, init, xs)
    n = jax.tree.leaves(xs)[0].shape[0]
    carry, ys = init, []
    for i in range(n):
        carry, y = body(carry, jax.tree.map(lambda t: t[i], xs))
        ys.append(y)
    if ys and jax.tree.structure(ys[0]).num_leaves:
        ys = jax.tree.map(lambda *a: jnp.stack(a), *ys)
    else:
        ys = None
    return carry, ys


# ------------------------------------------------------------------ stacks
def _stacked_init(init_one, key, n):
    return jax.vmap(init_one)(jax.random.split(key, n))


def _read_layer(cache, idx):
    return jax.tree.map(
        lambda t: jax.lax.dynamic_index_in_dim(t, idx, 0, keepdims=False),
        cache)


def _write_layer(cache, new_layer, idx):
    return jax.tree.map(
        lambda t, n: jax.lax.dynamic_update_index_in_dim(
            t, n.astype(t.dtype), idx, 0), cache, new_layer)


def dense_stack(x, layers_p, cfg, positions=None, cache=None, cache_pos=None):
    """Scan over transformer layers. cache: None or {"k","v"} with leading L.

    The cache travels in the scan CARRY (updated via dynamic-update-slice at
    the layer index) rather than as scan xs/ys — the while-loop state is
    aliased in place by XLA, so together with jit donation the serving
    cache exists exactly once in HBM."""

    if cache is None:
        def body(carry, p):
            x = _shard_seq(carry, cfg)
            x, _, aux = transformer_block(x, p, cfg, positions=positions)
            return x, aux

        body = _maybe_remat(body, cfg)
        x, auxs = _scan(body, x, layers_p, cfg)
        return x, None, jnp.sum(auxs)

    L = jax.tree.leaves(layers_p)[0].shape[0]

    def body(carry, xs):
        x, cache_all = carry
        x = _shard_seq(x, cfg)
        p, idx = xs
        x, new_layer, aux = transformer_block(
            x, p, cfg, positions=positions,
            kv_cache=_read_layer(cache_all, idx), cache_pos=cache_pos)
        return (x, _write_layer(cache_all, new_layer, idx)), aux

    body = _maybe_remat(body, cfg)
    (x, new_cache), auxs = _scan(body, (x, cache),
                                 (layers_p, jnp.arange(L)), cfg)
    return x, new_cache, jnp.sum(auxs)


def ssm_stack(x, layers_p, cfg, states=None):
    if states is None:
        def body(carry, p):
            x = _shard_seq(carry, cfg)
            x, _ = mamba_layer(x, p, cfg, state=None)
            return x, jnp.float32(0.0)

        body = _maybe_remat(body, cfg)
        x, _ = _scan(body, x, layers_p, cfg)
        return x, None

    L = jax.tree.leaves(layers_p)[0].shape[0]

    def body(carry, xs):
        x, states_all = carry
        p, idx = xs
        x, new_st = mamba_layer(_shard_seq(x, cfg), p, cfg,
                                state=_read_layer(states_all, idx))
        return (x, _write_layer(states_all, new_st, idx)), None

    body = _maybe_remat(body, cfg)
    (x, new_states), _ = _scan(body, (x, states),
                               (layers_p, jnp.arange(L)), cfg)
    return x, new_states


def ssm_decode_stack(x, layers_p, cfg, states):
    L = jax.tree.leaves(layers_p)[0].shape[0]

    def body(carry, xs):
        x, states_all = carry
        x = _shard_seq(x, cfg)
        p, idx = xs
        h = apply_norm(x, p["ln1"], cfg)
        out, new_st = ssm_decode_step(h, p["ssm"], cfg,
                                      _read_layer(states_all, idx))
        return (x + out, _write_layer(states_all, new_st, idx)), None

    (x, new_states), _ = _scan(body, (x, states),
                               (layers_p, jnp.arange(L)), cfg)
    return x, new_states


def hybrid_stack(x, params, cfg, positions=None, ssm_states=None,
                 attn_cache=None, cache_pos=None, decode=False):
    """zamba2-style: groups of `hybrid_period` mamba layers, each followed by
    one of `num_shared_blocks` shared attention blocks (cycled)."""
    L, P = cfg.num_layers, cfg.hybrid_period
    G = L // P
    grp = lambda t: t.reshape((G, P) + t.shape[1:])
    mamba_p = jax.tree.map(grp, params["mamba"])

    ssm_grouped = jax.tree.map(grp, ssm_states) if ssm_states is not None \
        else None

    def group_body(carry, xs):
        x, ssm_all, attn_all = carry
        x = _shard_seq(x, cfg)
        gi = xs["idx"]
        if decode:
            x, new_g = ssm_decode_stack(x, xs["mamba"], cfg,
                                        _read_layer(ssm_all, gi))
            ssm_all = _write_layer(ssm_all, new_g, gi)
        elif ssm_all is not None:
            x, new_g = ssm_stack(x, xs["mamba"], cfg,
                                 states=_read_layer(ssm_all, gi))
            ssm_all = _write_layer(ssm_all, new_g, gi)
        else:
            x, _ = ssm_stack(x, xs["mamba"], cfg, states=None)
        shared_p = jax.tree.map(
            lambda v: jax.lax.dynamic_index_in_dim(v, gi % cfg.num_shared_blocks,
                                                   keepdims=False),
            params["shared"])
        kv = _read_layer(attn_all, gi) if attn_all is not None else None
        x, new_kv, _ = transformer_block(x, shared_p, cfg, positions=positions,
                                         kv_cache=kv, cache_pos=cache_pos)
        if attn_all is not None:
            attn_all = _write_layer(attn_all, new_kv, gi)
        return (x, ssm_all, attn_all), None

    group_body = _maybe_remat(group_body, cfg)
    xs = {"idx": jnp.arange(G), "mamba": mamba_p}
    (x, new_ssm_g, new_attn), _ = _scan(
        group_body, (x, ssm_grouped, attn_cache), xs, cfg)
    new_ssm = None
    if new_ssm_g is not None:
        new_ssm = jax.tree.map(lambda t: t.reshape((G * P,) + t.shape[2:]),
                               new_ssm_g)
    return x, new_ssm, new_attn


# ------------------------------------------------------------------ LM heads
def _lm_logits(x, params, cfg):
    x = apply_norm(x, params["final_norm"], cfg)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = x @ head.astype(x.dtype)
    if cfg.logits_softcap:
        logits = cfg.logits_softcap * jnp.tanh(logits / cfg.logits_softcap)
    return logits
    # (a vocab-sharding constraint on the logits was tried here and REVERTED:
    # the measured HBM term got worse — GSPMD resharding around the pinned
    # logits outweighed the replication it removed; see EXPERIMENTS.md §Perf)


def cross_entropy(logits, labels, mask=None):
    """logits (B,S,V) any dtype; labels (B,S) int. Returns mean NLL (f32).

    take_along_axis (a gather) picks the true logit — materializing a
    (B,S,V) f32 one-hot costs a full extra logits-sized HBM round-trip,
    which dominated the memory roofline of small-model train cells."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    true_logit = jnp.take_along_axis(
        logits, labels[..., None].astype(jnp.int32), axis=-1)[..., 0]
    nll = lse - true_logit
    if mask is None:
        mask = (labels >= 0).astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
