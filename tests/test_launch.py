"""Launch layer: shapes/specs, step functions on a small host mesh,
train and serve drivers end-to-end (small scale)."""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_smoke_config
from repro.launch import shapes as shp
from repro.launch.mesh import make_host_mesh, make_mesh


class TestShapes:
    def test_cells_skip_long500k_for_attention(self):
        assert shp.applicable(get_config("mamba2-370m"), "long_500k")
        assert shp.applicable(get_config("zamba2-2.7b"), "long_500k")
        for arch in ("qwen3-0.6b", "internlm2-20b", "whisper-large-v3",
                     "internvl2-76b", "qwen3-moe-30b-a3b"):
            assert not shp.applicable(get_config(arch), "long_500k"), arch

    def test_cell_count_is_32(self):
        """10 archs x 4 shapes - 8 long_500k skips = 32 cells."""
        from repro.configs import ARCHS
        n = sum(1 for a in ARCHS for s in shp.SHAPES
                if shp.applicable(get_config(a), s))
        assert n == 32

    def test_batch_specs_train_microbatched(self):
        cfg = get_config("qwen3-0.6b")
        kind, kw = shp.input_specs(cfg, "train_4k")
        assert kind == "train"
        assert kw["batch"]["tokens"].shape == (8, 32, 4096)
        assert kw["batch"]["labels"].shape == (8, 32, 4096)

    def test_decode_specs_one_token(self):
        cfg = get_config("qwen3-0.6b")
        kind, kw = shp.input_specs(cfg, "decode_32k")
        assert kind == "decode"
        assert kw["batch"]["tokens"].shape == (128, 1)
        assert kw["cache"]["layers"]["k"].shape == (28, 128, 32768, 8, 128)

    def test_vlm_audio_stub_frontends(self):
        cfg = get_config("internvl2-76b")
        _, kw = shp.input_specs(cfg, "prefill_32k")
        assert kw["batch"]["vision_embeds"].shape == (32, 256, 8192)
        cfg = get_config("whisper-large-v3")
        _, kw = shp.input_specs(cfg, "train_4k")
        assert kw["batch"]["audio_embeds"].shape == (8, 32, 1500, 1280)

    def test_production_overrides(self):
        cfg, over = shp.production_config(get_config("internvl2-76b"),
                                          "train_4k")
        assert over["attention_impl"] == "chunked"
        assert over["shard_activations"] is True
        cfg, over = shp.production_config(get_config("mamba2-370m"),
                                          "train_4k")
        assert over == {}   # attention-free: nothing to override


class TestLowerSmallMesh:
    """Lower the real step functions on a tiny host mesh (1 device)."""

    def test_train_step_lowers_and_runs(self):
        from repro.launch.steps import make_train_step
        cfg = get_smoke_config("qwen3-0.6b")
        step = jax.jit(make_train_step(cfg, eta=0.01, beta=0.9,
                                       microbatches=2))
        from repro.models import build_model
        m = build_model(cfg)
        params = m.init(jax.random.PRNGKey(0))
        v = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        B, S = 4, 32
        batch = {"tokens": jnp.zeros((2, B // 2, S), jnp.int32),
                 "labels": jnp.zeros((2, B // 2, S), jnp.int32)}
        p2, v2, metrics = step(params, v, batch, jnp.int32(1))
        assert np.isfinite(float(metrics["loss"]))
        assert float(metrics["gap"]) >= 0
        # params actually moved
        delta = sum(float(jnp.sum(jnp.abs(a - b)))
                    for a, b in zip(jax.tree.leaves(p2),
                                    jax.tree.leaves(params)))
        assert delta > 0

    def test_decode_step_lowers_and_runs(self):
        from repro.launch.steps import make_decode_step
        from repro.models import build_model
        cfg = get_smoke_config("zamba2_2_7b")
        m = build_model(cfg)
        params = m.init(jax.random.PRNGKey(0))
        cache = m.init_cache(2, 16)
        step = jax.jit(make_decode_step(cfg))
        tok, new_cache = step(params, cache,
                              {"tokens": jnp.zeros((2, 1), jnp.int32)})
        assert tok.shape == (2, 1)
        assert int(new_cache["pos"]) == 1


class TestTrainDriver:
    def test_federated_lm_end_to_end(self, tmp_path):
        from repro.launch.train import IslandConfig, run
        cfg = get_smoke_config("qwen3-0.6b")
        icfg = IslandConfig(n_islands=2, slots=120, local_steps=2,
                            batch=4, seq=32, eval_every=60,
                            ckpt_dir=str(tmp_path), ckpt_every=50,
                            app_arrival_p=0.05)
        out = run(cfg, icfg, log=lambda *a: None)
        assert out["updates"] > 0
        assert np.isfinite(out["final_loss"])
        # checkpoints written
        assert any(d.startswith("step_") for d in os.listdir(tmp_path))

    def test_compression_and_gap_aware_path(self, tmp_path):
        from repro.launch.train import IslandConfig, run
        cfg = get_smoke_config("qwen3-0.6b")
        icfg = IslandConfig(n_islands=2, slots=100, local_steps=2,
                            batch=4, seq=32, eval_every=100,
                            compress_ratio=0.1, aggregation="gap_aware",
                            app_arrival_p=0.05)
        out = run(cfg, icfg, log=lambda *a: None)
        assert out["updates"] > 0
        assert np.isfinite(out["final_loss"])


class TestServeDriver:
    @pytest.mark.parametrize("arch", ["qwen3-0.6b", "mamba2-370m",
                                      "whisper-large-v3"])
    def test_generate_shapes_and_determinism(self, arch):
        from repro.launch.serve import BatchedServer
        cfg = get_smoke_config(arch)
        srv = BatchedServer(cfg)
        prompts = np.ones((2, 8), np.int32)
        a = srv.generate(prompts, 6)
        b = srv.generate(prompts, 6)
        assert a.shape == (2, 6)
        np.testing.assert_array_equal(a, b)
        assert (a >= 0).all() and (a < cfg.vocab_size).all()
