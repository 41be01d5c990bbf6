"""Batched serving driver: continuous-batching decode loop with KV cache.

Prefill a batch of prompts, then greedy-decode with the jitted decode step.
This driver runs the smoke configs end-to-end on the host and reports
tokens/s.

    python -m repro.launch.serve --arch qwen3-0.6b --batch 4 --prompt-len 32 \
        --gen 32
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.synthetic import synthetic_tokens
from repro.models import build_model


class BatchedServer:
    """Greedy batched decode over a fixed cohort of requests."""

    def __init__(self, cfg, params=None, seed: int = 0):
        self.cfg = cfg
        self.model = build_model(cfg)
        self.params = params if params is not None else \
            self.model.init(jax.random.PRNGKey(seed))
        self._prefill = jax.jit(self.model.prefill)
        self._decode = jax.jit(self.model.decode_step)

    def _extra_inputs(self, batch_size: int):
        extra = {}
        if self.cfg.family == "audio":
            extra["audio_embeds"] = jnp.zeros(
                (batch_size, self.cfg.encoder_seq, self.cfg.d_model),
                jnp.float32)
        if self.cfg.family == "vlm":
            extra["vision_embeds"] = jnp.zeros(
                (batch_size, self.cfg.num_vision_tokens, self.cfg.d_model),
                jnp.float32)
        return extra

    def generate(self, prompts: np.ndarray, max_new_tokens: int):
        """prompts: (B, S) int32. Returns (B, max_new_tokens) int32."""
        B, S = prompts.shape
        cache = self.model.init_cache(B, S + max_new_tokens)
        batch = {"tokens": jnp.asarray(prompts), **self._extra_inputs(B)}
        logits, cache = self._prefill(self.params, batch, cache)
        tok = jnp.argmax(logits[:, -1, :], axis=-1)[:, None]
        out = [tok]
        for _ in range(max_new_tokens - 1):
            logits, cache = self._decode(self.params, cache, {"tokens": tok})
            tok = jnp.argmax(logits[:, -1, :], axis=-1)[:, None]
            out.append(tok)
        return np.asarray(jnp.concatenate(out, axis=1))


def main():
    from repro.configs import get_smoke_config

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    args = ap.parse_args()

    cfg = get_smoke_config(args.arch)
    srv = BatchedServer(cfg)
    stream = synthetic_tokens(args.batch * args.prompt_len + 1,
                              cfg.vocab_size, seed=3)
    prompts = stream[:args.batch * args.prompt_len].reshape(
        args.batch, args.prompt_len)

    t0 = time.time()
    toks = srv.generate(prompts, args.gen)
    dt = time.time() - t0
    print(f"arch={cfg.name} batch={args.batch} prompt={args.prompt_len} "
          f"gen={args.gen}: {toks.size / dt:.1f} tok/s  "
          f"first row: {toks[0][:10].tolist()}")


if __name__ == "__main__":
    main()
