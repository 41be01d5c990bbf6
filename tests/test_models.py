"""Per-architecture smoke tests: reduced configs, one forward + one train
step on CPU, output shapes + no NaNs; prefill/decode consistency."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config, get_smoke_config
from repro.models import build_model

LM_ARCHS = [a for a in ARCHS]


def _batch(cfg, key, B=2, S=16):
    ks = jax.random.split(key, 3)
    batch = {
        "tokens": jax.random.randint(ks[0], (B, S), 0, cfg.vocab_size),
        "labels": jax.random.randint(ks[1], (B, S), 0, cfg.vocab_size),
    }
    if cfg.family == "audio":
        batch["audio_embeds"] = 0.1 * jax.random.normal(
            ks[2], (B, cfg.encoder_seq, cfg.d_model))
    if cfg.family == "vlm":
        batch["vision_embeds"] = 0.1 * jax.random.normal(
            ks[2], (B, cfg.num_vision_tokens, cfg.d_model))
    return batch


@pytest.mark.parametrize("arch", LM_ARCHS)
class TestSmoke:
    def test_forward_no_nan(self, arch):
        cfg = get_smoke_config(arch)
        m = build_model(cfg)
        params = m.init(jax.random.PRNGKey(0))
        loss, metrics = m.loss(params, _batch(cfg, jax.random.PRNGKey(1)))
        assert np.isfinite(float(loss))
        assert float(loss) >= 0

    def test_one_train_step_reduces_loss_shape_stable(self, arch):
        cfg = get_smoke_config(arch)
        m = build_model(cfg)
        params = m.init(jax.random.PRNGKey(0))
        batch = _batch(cfg, jax.random.PRNGKey(1))

        def loss_fn(p):
            return m.loss(p, batch)[0]

        l0, grads = jax.value_and_grad(loss_fn)(params)
        # shapes preserved, grads finite
        for g, p in zip(jax.tree.leaves(grads), jax.tree.leaves(params)):
            assert g.shape == p.shape
            assert np.isfinite(np.asarray(g)).all()
        new = jax.tree.map(lambda p, g: p - 0.1 * g, params, grads)
        l1 = loss_fn(new)
        assert np.isfinite(float(l1))
        assert float(l1) < float(l0)   # one step on the same batch descends

    def test_full_config_matches_assignment(self, arch):
        """The full (non-smoke) config carries the assigned dimensions."""
        cfg = get_config(arch)
        assigned = {
            "mamba2_370m": dict(num_layers=48, d_model=1024, vocab_size=50280,
                                ssm_state=128),
            "qwen3_moe_30b_a3b": dict(num_layers=48, d_model=2048,
                                      num_heads=32, num_kv_heads=4,
                                      d_ff=768, vocab_size=151936,
                                      num_experts=128, num_experts_per_tok=8),
            "granite_moe_1b_a400m": dict(num_layers=24, d_model=1024,
                                         num_heads=16, num_kv_heads=8,
                                         d_ff=512, vocab_size=49155,
                                         num_experts=32,
                                         num_experts_per_tok=8),
            "internlm2_20b": dict(num_layers=48, d_model=6144, num_heads=48,
                                  num_kv_heads=8, d_ff=16384,
                                  vocab_size=92544),
            "qwen3_0_6b": dict(num_layers=28, d_model=1024, num_heads=16,
                               num_kv_heads=8, d_ff=3072, vocab_size=151936,
                               qk_norm=True),
            "qwen2_5_3b": dict(num_layers=36, d_model=2048, num_heads=16,
                               num_kv_heads=2, d_ff=11008,
                               vocab_size=151936, qkv_bias=True),
            "phi4_mini_3_8b": dict(num_layers=32, d_model=3072, num_heads=24,
                                   num_kv_heads=8, d_ff=8192,
                                   vocab_size=200064),
            "whisper_large_v3": dict(num_layers=32, d_model=1280,
                                     num_heads=20, num_kv_heads=20,
                                     d_ff=5120, vocab_size=51866,
                                     encoder_layers=32),
            "zamba2_2_7b": dict(num_layers=54, d_model=2560, num_heads=32,
                                num_kv_heads=32, d_ff=10240,
                                vocab_size=32000, ssm_state=64),
            "internvl2_76b": dict(num_layers=80, d_model=8192, num_heads=64,
                                  num_kv_heads=8, d_ff=28672,
                                  vocab_size=128256),
        }[arch]
        for k, v in assigned.items():
            assert getattr(cfg, k) == v, (arch, k, getattr(cfg, k), v)


@pytest.mark.parametrize("arch", LM_ARCHS)
def test_prefill_decode_matches_full_forward(arch):
    """Teacher-forcing consistency: prefill(prompt) + decode_step(tok_i)
    reproduces the full-sequence forward logits."""
    cfg = get_smoke_config(arch)
    if cfg.family == "moe":
        # dispatch-impl equivalence is covered separately; the sorted path
        # legitimately drops tokens at tiny T, breaking exact consistency
        cfg = dataclasses.replace(cfg, moe_impl="dense")
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    B, S = 2, 12
    batch = _batch(cfg, jax.random.PRNGKey(1), B=B, S=S)
    tokens = batch["tokens"]

    # full forward logits via loss-path model internals: use prefill over the
    # whole sequence, then compare last-token logits with prefill of S-1 +
    # one decode step.
    cache_full = m.init_cache(B, S)
    full_logits, _ = m.prefill(params, batch, cache_full)

    prompt = dict(batch)
    prompt["tokens"] = tokens[:, : S - 1]
    if "labels" in prompt:
        prompt["labels"] = prompt["labels"][:, : S - 1]
    cache = m.init_cache(B, S)
    _, cache = m.prefill(params, prompt, cache)
    step_logits, cache = m.decode_step(params, cache,
                                       {"tokens": tokens[:, S - 1:]})
    tol = 6e-2 if cfg.sub_quadratic else 2e-2   # f32 ssd state round-trip
    np.testing.assert_allclose(np.asarray(full_logits[:, -1]),
                               np.asarray(step_logits[:, -1]),
                               rtol=tol, atol=tol)


def test_param_count_analytic_matches_actual():
    for arch in LM_ARCHS:
        cfg = get_smoke_config(arch)
        m = build_model(cfg)
        params = m.init(jax.random.PRNGKey(0))
        actual = sum(int(np.prod(p.shape))
                     for p in jax.tree.leaves(params))
        assert cfg.param_count() == actual, arch


def test_moe_sorted_equals_dense_dispatch():
    cfg = get_smoke_config("granite_moe_1b_a400m")
    cfg_d = dataclasses.replace(cfg, moe_impl="dense")
    cfg_s = dataclasses.replace(cfg, moe_impl="sorted",
                                moe_capacity_factor=8.0)  # no drops
    m_d, m_s = build_model(cfg_d), build_model(cfg_s)
    params = m_d.init(jax.random.PRNGKey(0))
    batch = _batch(cfg, jax.random.PRNGKey(1))
    l_d, _ = m_d.loss(params, batch)
    l_s, _ = m_s.loss(params, batch)
    assert float(l_d) == pytest.approx(float(l_s), rel=2e-3)


def test_scan_equals_unrolled():
    """cfg.scan_layers=False is semantically identical."""
    for arch in ("qwen3_0_6b", "mamba2_370m", "zamba2_2_7b",
                 "whisper_large_v3"):
        cfg = get_smoke_config(arch)
        cfg_u = dataclasses.replace(cfg, scan_layers=False)
        m, mu = build_model(cfg), build_model(cfg_u)
        params = m.init(jax.random.PRNGKey(0))
        batch = _batch(cfg, jax.random.PRNGKey(1))
        l, _ = m.loss(params, batch)
        lu, _ = mu.loss(params, batch)
        # bf16 accumulation order differs between scan and unrolled
        assert float(l) == pytest.approx(float(lu), rel=3e-3), arch
