"""Assigned input-shape sets and ShapeDtypeStruct stand-ins per (arch, shape).

Shapes (LM family, seq_len x global_batch):
    train_4k     4,096 x 256   -> train_step
    prefill_32k  32,768 x 32   -> prefill_step (serve)
    decode_32k   32,768 x 128  -> decode_step (1 new token, KV cache of 32k)
    long_500k    524,288 x 1   -> decode_step; ONLY for sub-quadratic archs
                                  (mamba2, zamba2) — skipped for the 8 pure
                                  full-attention archs (DESIGN.md §6).

``input_specs`` returns weak-type-correct, shardable ShapeDtypeStructs — no
device allocation.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    name: str
    seq_len: int
    global_batch: int
    kind: str                 # train | prefill | decode


SHAPES: Dict[str, ShapeSpec] = {
    "train_4k": ShapeSpec("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524288, 1, "decode"),
}

# train_4k microbatch count per arch family size: keeps per-microbatch
# activations within HBM (DESIGN.md §4). global_batch 256 / M must stay
# divisible by the dp-axis product (32 on the multi-pod mesh) -> M=8.
TRAIN_MICROBATCHES = 8

# archs whose params+opt state (or MoE dispatch buffers, or model-axis-
# indivisible replicated attention weights) exceed single-axis sharding:
# FSDP on for TRAIN cells. Serving keeps TP-only params (per-token FSDP
# gathers would dominate decode).
FSDP_ARCHS = {"qwen3-moe-30b-a3b", "internlm2-20b", "internvl2-76b",
              "granite-moe-1b-a400m", "phi4-mini-3.8b", "whisper-large-v3"}


def applicable(cfg, shape: str) -> bool:
    """long_500k only for sub-quadratic (O(1)-state decode) archs."""
    if shape == "long_500k":
        return cfg.sub_quadratic
    return True


def production_config(cfg, shape: str):
    """Per-cell production overrides that make the cell fit HBM: chunked
    attention for 4k+ sequence work (einsum attention materializes (Sq, Sk)
    scores — 100s of GB/device at 32k), and sequence-parallel activations
    for the wide (d_model >= 3k) train cells (the remat stack L x (B, S, D)
    dominates otherwise)."""
    spec = SHAPES[shape]
    over = {}
    if cfg.num_heads and spec.kind in ("train", "prefill") \
            and spec.seq_len >= 4096:
        over["attention_impl"] = "chunked"
    if spec.kind == "train" and cfg.d_model >= 3072:
        over["shard_activations"] = True
    return (dataclasses.replace(cfg, **over) if over else cfg), over


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))


def batch_specs(cfg, spec: ShapeSpec, *, microbatches: int = 1) -> Dict[str, Any]:
    """The token batch a step consumes (train/prefill); decode uses 1 token."""
    B, S = spec.global_batch, spec.seq_len
    if spec.kind == "train":
        M = microbatches
        assert B % M == 0
        lead = (M, B // M) if M > 1 else (B,)
    else:
        lead = (B,)
    if spec.kind == "decode":
        batch = {"tokens": _sds(lead + (1,), jnp.int32)}
    else:
        batch = {"tokens": _sds(lead + (S,), jnp.int32)}
        if spec.kind == "train":
            batch["labels"] = _sds(lead + (S,), jnp.int32)
    # modality frontends are STUBS: precomputed frame/patch embeddings
    if cfg.family == "audio" and spec.kind != "decode":
        batch["audio_embeds"] = _sds(lead + (cfg.encoder_seq, cfg.d_model),
                                     jnp.float32)
    if cfg.family == "vlm" and spec.kind != "decode":
        batch["vision_embeds"] = _sds(lead + (cfg.num_vision_tokens, cfg.d_model),
                                      jnp.float32)
    return batch


def cache_specs(cfg, spec: ShapeSpec):
    """ShapeDtypeStructs of the decode cache (KV / SSM state) at seq_len."""
    from repro.models import build_model
    model = build_model(cfg)
    return jax.eval_shape(
        lambda: model.init_cache(spec.global_batch, spec.seq_len))


def input_specs(cfg, shape, *, microbatches: int | None = None):
    """Returns (kind, kwargs-dict of ShapeDtypeStructs) for the step fn.
    ``shape`` is a shape name or a ShapeSpec."""
    spec = SHAPES[shape] if isinstance(shape, str) else shape
    if spec.kind == "train":
        M = TRAIN_MICROBATCHES if microbatches is None else microbatches
        return "train", {"batch": batch_specs(cfg, spec, microbatches=M)}
    if spec.kind == "prefill":
        return "prefill", {"batch": batch_specs(cfg, spec),
                           "cache": cache_specs(cfg, spec)}
    return "decode", {"cache": cache_specs(cfg, spec),
                      "batch": batch_specs(cfg, spec)}
