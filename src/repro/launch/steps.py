"""LM step functions: the islands driver (``launch/train.py``) jits the
train step with ``param_shardings``.

``make_train_step(cfg)``  -> (params, v, batch, lag) -> (params', v', metrics)
    One island-local update of the paper's system at LM scale: microbatched
    grad accumulation (f32, param-sharded) + the paper's fused momentum
    update (Eq. 1) + gradient-gap norm (Eq. 4) — the scalar each island
    reports to the Lyapunov scheduler.

``make_decode_step(cfg)``  -> (params, cache, batch) -> (logits, cache')

``param_shardings``: NamedShardings for the parameters, built from the
models.sharding rules (+FSDP post-pass for the >=20B archs).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import build_model, sharding
from repro.optim.gap import fused_momentum_gap_update

from .shapes import FSDP_ARCHS


# ------------------------------------------------------------------- steps
def make_train_step(cfg, *, eta: float = 1e-2, beta: float = 0.9,
                    microbatches: int = 1):
    """Microbatched momentum-SGD train step with the paper's gap norm."""
    model = build_model(cfg)

    def loss_grads(params, mb):
        (l, met), grads = jax.value_and_grad(model.loss, has_aux=True)(params, mb)
        return grads, l, met

    def train_step(params, v, batch, lag):
        if microbatches == 1:
            grads, loss, _ = loss_grads(params, batch)
        else:
            accum0 = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)

            def body(acc, mb):
                grads, l, _ = loss_grads(params, mb)
                acc = jax.tree.map(lambda a, g: a + g.astype(jnp.float32),
                                   acc, grads)
                return acc, l

            accum, losses = jax.lax.scan(body, accum0, batch)
            grads = jax.tree.map(lambda a: a / microbatches, accum)
            loss = jnp.mean(losses)
        new_params, new_v, gap = fused_momentum_gap_update(
            params, v, grads, eta=eta, beta=beta, lag=lag)
        return new_params, new_v, {"loss": loss, "gap": gap}

    return train_step


def make_decode_step(cfg, *, greedy: bool = True):
    model = build_model(cfg)

    def decode_step(params, cache, batch):
        logits, new_cache = model.decode_step(params, cache, batch)
        out = jnp.argmax(logits, axis=-1) if greedy else logits
        return out, new_cache

    return decode_step


# --------------------------------------------------------------- shardings
def param_shardings(cfg, mesh, *, fsdp: bool | None = None):
    """cfg.parallel_layout == "tp": weights sharded over "model" (+optional
    FSDP). "dp": weights replicated (or ZeRO-sharded over every axis with
    fsdp=True), batch over EVERY mesh axis — the right layout for models
    whose TP activation psums dominate (sub-1B archs on a 256-chip pod)."""
    from jax.sharding import PartitionSpec as P

    model = build_model(cfg)
    pshape = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    if cfg.parallel_layout == "dp":
        specs = jax.tree.map(lambda l: P(*([None] * len(l.shape))), pshape)
        # embedding/lm_head stay vocab-sharded over "model": the (B,S,V)
        # logits and the tied-embedding grads are vocab-wide tensors whose
        # replication dominated the dp layout's memory roofline.
        m = mesh.shape["model"]
        if isinstance(specs, dict) and "embed" in specs \
                and pshape["embed"].shape[0] % m == 0:
            specs = dict(specs)
            specs["embed"] = P("model", None)
            if "lm_head" in specs and pshape["lm_head"].shape[1] % m == 0:
                specs["lm_head"] = P(None, "model")
        if fsdp:
            specs = sharding.apply_fsdp(specs, pshape, mesh)
        return pshape, sharding.named(specs, mesh)
    specs = sharding.param_pspecs(cfg, pshape, mesh)
    if fsdp is None:
        fsdp = cfg.name in FSDP_ARCHS
    if fsdp:
        specs = sharding.apply_fsdp(specs, pshape, mesh)
    return pshape, sharding.named(specs, mesh)
