"""Sharded step functions: the units the dry-run lowers and the drivers jit.

``make_train_step(cfg)``  -> (params, v, batch, lag) -> (params', v', metrics)
    One island-local update of the paper's system at LM scale: microbatched
    grad accumulation (f32, param-sharded) + the paper's fused momentum
    update (Eq. 1) + gradient-gap norm (Eq. 4) — the scalar each island
    reports to the Lyapunov scheduler.

``make_prefill_step(cfg)`` -> (params, batch, cache) -> (logits, cache')
``make_decode_step(cfg)``  -> (params, cache, batch) -> (logits, cache')

``step_shardings``: NamedShardings for every argument/output, built from the
models.sharding rules (+FSDP post-pass for the >=20B archs).
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.models import build_model, sharding
from repro.optim.gap import fused_momentum_gap_update

from .shapes import (FSDP_ARCHS, FSDP_SERVE_ARCHS, SHAPES, batch_specs,
                     cache_specs, input_specs)


# ------------------------------------------------------------------- steps
def make_train_step(cfg, *, eta: float = 1e-2, beta: float = 0.9,
                    microbatches: int = 1, unroll_microbatches: bool = False):
    """Microbatched momentum-SGD train step with the paper's gap norm.

    unroll_microbatches: python-loop the grad-accumulation instead of
    lax.scan — used only by the dry-run flop calibration (see dryrun.py)."""
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

            if unroll_microbatches:
                accum, ls = accum0, []
                for i in range(microbatches):
                    accum, l = body(accum, jax.tree.map(lambda t: t[i], batch))
                    ls.append(l)
                losses = jnp.stack(ls)
            else:
                accum, losses = jax.lax.scan(body, accum0, batch)
            grads = jax.tree.map(lambda a: a / microbatches, accum)
            loss = jnp.mean(losses)
        new_params, new_v, gap = fused_momentum_gap_update(
            params, v, grads, eta=eta, beta=beta, lag=lag)
        return new_params, new_v, {"loss": loss, "gap": gap}

    return train_step


def make_update_step(cfg, *, eta: float = 1e-2, beta: float = 0.9):
    """The fused-update epilogue alone (dry-run calibration baseline)."""

    def upd_step(params, v, grads, lag):
        return fused_momentum_gap_update(params, v, grads, eta=eta, beta=beta,
                                         lag=lag)

    return upd_step


def make_prefill_step(cfg):
    model = build_model(cfg)

    def prefill_step(params, batch, cache):
        logits, new_cache = model.prefill(params, batch, cache)
        return logits, new_cache

    return prefill_step


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


def step_shardings(cfg, shape, mesh, *, fsdp: bool | None = None,
                   microbatches: int | None = None):
    """(kind, kwargs_specs, in_shardings tuple, out_shardings) for the cell."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    kind, kwargs = input_specs(cfg, shape, microbatches=microbatches)
    if fsdp is None:
        fsdp = (cfg.name in FSDP_ARCHS if kind == "train"
                else cfg.name in FSDP_SERVE_ARCHS)
    pshape, pshard = param_shardings(cfg, mesh, fsdp=fsdp)
    repl = NamedSharding(mesh, P())

    bspec = sharding.named(
        sharding.batch_pspecs(cfg, kwargs["batch"], mesh), mesh)
    if kind == "train":
        vshard = pshard  # momentum tree mirrors the params
        in_sh = (pshard, vshard, bspec, repl)
        out_sh = (pshard, vshard, jax.tree.map(lambda _: repl,
                                               {"loss": 0, "gap": 0}))
    else:
        cshard = sharding.named(
            sharding.cache_pspecs(cfg, kwargs["cache"], mesh), mesh)
        if kind == "prefill":
            in_sh = (pshard, bspec, cshard)
        else:
            in_sh = (pshard, cshard, bspec)
        out_sh = None  # let GSPMD choose logits/cache output layout
    return kind, kwargs, pshape, in_sh, out_sh


def lower_cell(cfg, shape: str, mesh, *, eta: float = 1e-2, beta: float = 0.9,
               fsdp: bool | None = None, microbatches: int | None = None,
               batch_div: int = 1):
    """jit().lower() the step for one (arch x shape x mesh) cell.

    batch_div scales the global batch down (dry-run calibration lowers a
    single microbatch of global_batch / TRAIN_MICROBATCHES sequences)."""
    import dataclasses

    from . import shapes as shapes_mod
    from .shapes import TRAIN_MICROBATCHES

    spec = SHAPES[shape]
    M = (TRAIN_MICROBATCHES if microbatches is None else microbatches) \
        if spec.kind == "train" else 1
    if batch_div > 1:
        spec = dataclasses.replace(spec,
                                   global_batch=spec.global_batch // batch_div)
    kind, kwargs, pshape, in_sh, out_sh = step_shardings(
        cfg, spec, mesh, fsdp=fsdp, microbatches=M)

    if kind == "train":
        step = make_train_step(cfg, eta=eta, beta=beta, microbatches=M)
        vshape = jax.tree.map(
            lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32), pshape)
        args = (pshape, vshape, kwargs["batch"],
                jax.ShapeDtypeStruct((), jnp.int32))
    else:
        # serving holds bf16 weights (the model casts per-use anyway);
        # f32 serving params would double the per-device HBM footprint.
        pshape = jax.tree.map(
            lambda p: jax.ShapeDtypeStruct(
                p.shape, jnp.bfloat16 if p.dtype == jnp.float32 else p.dtype),
            pshape)
        if kind == "prefill":
            step = make_prefill_step(cfg)
            args = (pshape, kwargs["batch"], kwargs["cache"])
        else:
            step = make_decode_step(cfg)
            args = (pshape, kwargs["cache"], kwargs["batch"])

    # donation: params/momentum update in place for train; KV/SSM cache in
    # place for serving — without it XLA allocates a second copy of the
    # largest state (31 GiB/dev observed for the 76B decode cell).
    donate = {"train": (0, 1), "prefill": (2,), "decode": (1,)}[kind]
    with jax.set_mesh(mesh):
        jitted = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh,
                         donate_argnums=donate)
        lowered = jitted.lower(*args)
    return lowered, kind
