"""Sharding rules: divisibility invariants, FSDP post-pass, batch specs."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import ARCHS, get_config
from repro.launch.mesh import make_mesh
from repro.models import build_model, sharding


def _mesh16():
    # 16-way logical mesh on 1 device: shape (1, 1) won't exercise
    # divisibility, so build an ABSTRACT mesh via jax.sharding.Mesh over a
    # reshaped device array is impossible on CPU with 1 device. Instead use
    # AbstractMesh (no devices needed).
    return AbstractMesh((16, 16), ("data", "model"))


@pytest.mark.parametrize("arch", ARCHS)
def test_param_specs_divisible(arch):
    """Every dim sharded over an axis must be divisible by the axis size."""
    cfg = get_config(arch)
    mesh = _mesh16()
    model = build_model(cfg)
    pshape = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    specs = sharding.param_pspecs(cfg, pshape, mesh)

    def check(spec, leaf):
        for dim, axes in zip(leaf.shape, tuple(spec)):
            if axes is None:
                continue
            axes = axes if isinstance(axes, tuple) else (axes,)
            size = 1
            for a in axes:
                size *= mesh.shape[a]
            assert dim % size == 0, (arch, leaf.shape, tuple(spec))

    jax.tree.map(check, specs, pshape,
                 is_leaf=lambda x: isinstance(x, P))


@pytest.mark.parametrize("arch", ["internvl2_76b", "qwen3_moe_30b_a3b",
                                  "internlm2_20b"])
def test_fsdp_adds_data_axis_to_large_leaves(arch):
    cfg = get_config(arch)
    mesh = _mesh16()
    model = build_model(cfg)
    pshape = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    base = sharding.param_pspecs(cfg, pshape, mesh)
    fs = sharding.apply_fsdp(base, pshape, mesh)

    n_upgraded = 0
    for (bs, fss, leaf) in zip(jax.tree.leaves(base, is_leaf=lambda x: isinstance(x, P)),
                               jax.tree.leaves(fs, is_leaf=lambda x: isinstance(x, P)),
                               jax.tree.leaves(pshape)):
        flat_b = [a for a in tuple(bs) if a is not None]
        flat_f = [a for a in tuple(fss) if a is not None]
        if leaf.size >= 1 << 20:
            if "data" in str(flat_f) and "data" not in str(flat_b):
                n_upgraded += 1
            # divisibility still holds
            for dim, axes in zip(leaf.shape, tuple(fss)):
                if axes is None:
                    continue
                axes = axes if isinstance(axes, tuple) else (axes,)
                size = 1
                for a in axes:
                    size *= mesh.shape[a]
                assert dim % size == 0
        else:
            assert tuple(bs) == tuple(fss)   # small leaves untouched
    assert n_upgraded > 0


def test_batch_specs_shard_leading_or_second_dim():
    cfg = get_config("qwen3_0_6b")
    mesh = _mesh16()
    # (B, S): B divisible -> dp on dim 0
    def norm(ax):
        return ax if isinstance(ax, tuple) else (ax,) if ax else None

    b1 = {"tokens": jax.ShapeDtypeStruct((256, 4096), jnp.int32)}
    s1 = sharding.batch_pspecs(cfg, b1, mesh)
    assert norm(tuple(s1["tokens"])[0]) == ("data",)
    # (M, mb, S): M=8 not divisible, mb=32 divisible -> dp on dim 1
    b2 = {"tokens": jax.ShapeDtypeStruct((8, 32, 4096), jnp.int32)}
    s2 = sharding.batch_pspecs(cfg, b2, mesh)
    assert tuple(s2["tokens"])[0] is None
    assert norm(tuple(s2["tokens"])[1]) == ("data",)
    # (1, seq): long-context decode -> seq on dp
    b3 = {"tokens": jax.ShapeDtypeStruct((1, 524288), jnp.int32)}
    s3 = sharding.batch_pspecs(cfg, b3, mesh)
    assert norm(tuple(s3["tokens"])[1]) == ("data",)


def test_cache_specs_prefer_kv_head_sharding_else_seq():
    mesh = _mesh16()
    # internlm2: kv=8 not divisible by 16 -> seq axis takes "model"
    cfg = get_config("internlm2_20b")
    model = build_model(cfg)
    cache = jax.eval_shape(lambda: model.init_cache(128, 4096))
    specs = sharding.cache_pspecs(cfg, cache, mesh)
    k_spec = tuple(specs["layers"]["k"])
    assert "model" in str(k_spec[2])     # seq dim
    # zamba2 kv=32 divisible -> heads take "model"
    cfg2 = get_config("zamba2_2_7b")
    m2 = build_model(cfg2)
    cache2 = jax.eval_shape(lambda: m2.init_cache(128, 4096))
    specs2 = sharding.cache_pspecs(cfg2, cache2, mesh)
    k2 = tuple(specs2["attn"]["k"])
    assert k2[3] == "model"


def test_multi_pod_dp_axes():
    mesh = AbstractMesh((2, 16, 16), ("pod", "data", "model"))
    assert sharding.dp_axes(mesh) == ("pod", "data")
    assert sharding._prod_dp(mesh) == 32
