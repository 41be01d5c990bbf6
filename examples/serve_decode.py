"""Batched serving: prefill + greedy decode with a KV/SSM cache for any
assigned architecture (smoke size on CPU).

    PYTHONPATH=src python examples/serve_decode.py --arch mamba2-370m
"""
import argparse
import time

import _bootstrap  # noqa: F401  (makes `repro` importable from a checkout)

import numpy as np

from repro.configs import get_smoke_config
from repro.data.synthetic import synthetic_tokens
from repro.launch.serve import BatchedServer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=48)
    args = ap.parse_args()

    cfg = get_smoke_config(args.arch)
    srv = BatchedServer(cfg)
    stream = synthetic_tokens(args.batch * args.prompt_len + 1,
                              cfg.vocab_size, seed=3)
    prompts = stream[: args.batch * args.prompt_len].reshape(
        args.batch, args.prompt_len)

    t0 = time.time()
    toks = srv.generate(prompts, args.gen)
    dt = time.time() - t0
    print(f"arch={cfg.name}  batch={args.batch}  prompt={args.prompt_len}  "
          f"gen={args.gen}")
    print(f"throughput: {toks.size / dt:.1f} tok/s (host CPU, smoke config)")
    print(f"sample continuation: {toks[0][:16].tolist()}")


if __name__ == "__main__":
    main()
