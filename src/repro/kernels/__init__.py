"""Pallas TPU kernels for the compute hot-spots (each with ops.py wrapper and
ref.py pure-jnp oracle, validated via interpret=True on CPU):

* ``fused_update``    — the paper's per-push mechanism at LM scale: momentum
                        update (Eq. 1) + parameter step + gradient-gap norm
                        (Eq. 4) in ONE HBM pass.
* ``flash_attention`` — blocked causal online-softmax attention (GQA); makes
                        prefill_32k memory-feasible on TPU.
* ``ssd_scan``        — Mamba2 SSD intra-chunk scan as MXU matmuls; used by
                        the ssm/hybrid archs.
"""
from . import flash_attention, fused_update, ssd_scan

__all__ = ["flash_attention", "fused_update", "ssd_scan"]
