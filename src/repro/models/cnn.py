"""The paper's §V CNN: the CIFAR-10 architecture from McMahan et al. [7]
(two 5x5 conv + pool stages, two hidden FC layers, ~1-2e6 parameters).

Used by the faithful reproduction of Figure 1 (benchmarks/run.py, examples).
Pure-JAX (lax.conv_general_dilated), fp32 — this is the laptop-scale model.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig


def init_params(cfg: ModelConfig, rng, num_classes: int = 10):
    ks = jax.random.split(rng, 5)
    he = lambda k, sh, fan_in: jax.random.normal(k, sh) * (2.0 / fan_in) ** 0.5
    return {
        "conv1": {"w": he(ks[0], (5, 5, 3, 32), 5 * 5 * 3),
                  "b": jnp.zeros((32,))},
        "conv2": {"w": he(ks[1], (5, 5, 32, 64), 5 * 5 * 32),
                  "b": jnp.zeros((64,))},
        "fc1": {"w": he(ks[2], (8 * 8 * 64, 384), 8 * 8 * 64),
                "b": jnp.zeros((384,))},
        "fc2": {"w": he(ks[3], (384, 192), 384), "b": jnp.zeros((192,))},
        "out": {"w": he(ks[4], (192, num_classes), 192),
                "b": jnp.zeros((num_classes,))},
    }


def _conv(x, p):
    y = jax.lax.conv_general_dilated(
        x, p["w"], window_strides=(1, 1), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return jax.nn.relu(y + p["b"])


def _pool(x):
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                 (1, 2, 2, 1), (1, 2, 2, 1), "VALID")


def forward(cfg: ModelConfig, params, batch, impl: str = "ref"):
    """batch: {images (B,32,32,3) float32} -> (logits (B,10), aux)."""
    x = batch["images"]
    x = _pool(_conv(x, params["conv1"]))
    x = _pool(_conv(x, params["conv2"]))
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(x @ params["fc1"]["w"] + params["fc1"]["b"])
    x = jax.nn.relu(x @ params["fc2"]["w"] + params["fc2"]["b"])
    return x @ params["out"]["w"] + params["out"]["b"], jnp.float32(0)


def loss_fn(cfg: ModelConfig, params, batch, rng=None, impl: str = "ref"):
    logits, _ = forward(cfg, params, batch)
    labels = batch["labels"]
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(logz - gold), {}


def accuracy(params, batch):
    logits, _ = forward(None, params, batch)
    return jnp.mean(jnp.argmax(logits, -1) == batch["labels"])


def num_params(params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))
