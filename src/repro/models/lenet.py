"""LeNet-5 — the model the paper trains on CIFAR-10 (Sec. VI: DL4J LeNet-5).

Used by the paper-faithful federated simulation tier (25 clients, batch 20).
Pure JAX; ~2.5 MB of parameters matching the paper's reported model size.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def init_lenet(key, num_classes: int = 10, in_channels: int = 3):
    """float32 parameters, with or without ``jax_enable_x64``: the images
    are float32, and the model computes in their precision."""
    ks = jax.random.split(key, 5)
    f32 = jnp.float32

    def conv_init(k, kh, kw, cin, cout):
        std = (kh * kw * cin) ** -0.5
        return std * jax.random.truncated_normal(k, -3, 3, (kh, kw, cin, cout),
                                                 f32)

    def fc_init(k, din, dout):
        return din ** -0.5 * jax.random.truncated_normal(k, -3, 3, (din, dout),
                                                         f32)

    def zeros(n):
        return jnp.zeros(n, f32)

    return {
        "conv1": {"w": conv_init(ks[0], 5, 5, in_channels, 6), "b": zeros(6)},
        "conv2": {"w": conv_init(ks[1], 5, 5, 6, 16), "b": zeros(16)},
        "fc1": {"w": fc_init(ks[2], 16 * 5 * 5, 120), "b": zeros(120)},
        "fc2": {"w": fc_init(ks[3], 120, 84), "b": zeros(84)},
        "fc3": {"w": fc_init(ks[4], 84, num_classes), "b": zeros(num_classes)},
    }


def _conv(x, p):
    y = jax.lax.conv_general_dilated(
        x, p["w"], window_strides=(1, 1), padding="VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + p["b"]


def _pool(x):
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                 (1, 2, 2, 1), (1, 2, 2, 1), "VALID")


def lenet_logits(params, images):
    """images: (B, 32, 32, C) float32 -> logits (B, num_classes)."""
    x = jax.nn.relu(_conv(images, params["conv1"]))   # (B,28,28,6)
    x = _pool(x)                                      # (B,14,14,6)
    x = jax.nn.relu(_conv(x, params["conv2"]))        # (B,10,10,16)
    x = _pool(x)                                      # (B,5,5,16)
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(x @ params["fc1"]["w"] + params["fc1"]["b"])
    x = jax.nn.relu(x @ params["fc2"]["w"] + params["fc2"]["b"])
    return x @ params["fc3"]["w"] + params["fc3"]["b"]


def lenet_loss(params, batch):
    logits = lenet_logits(params, batch["images"])
    labels = batch["labels"]
    logp = jax.nn.log_softmax(logits)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=1).mean()
    acc = (logits.argmax(-1) == labels).mean()
    return nll, {"loss": nll, "accuracy": acc}


def param_count(params) -> int:
    return sum(p.size for p in jax.tree.leaves(params))
