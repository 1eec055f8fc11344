"""Plain reference of the decoder-only transformer family (Granite-3.0 and
its kind): pre-norm blocks of grouped-query attention with rotary
positions and a SwiGLU or GELU MLP, then the vocabulary projection.

Departures of the system from the published Granite-3.0, which the
reference shares: no embedding, attention, residual or logit multipliers
(Granite's muP factors); the configuration lists them under ``reduced``.
The output projection is the tied token embedding, as published.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.ref import common as C


def init(cfg: dict, key):
    """Random weights in the layout the system's dense model takes, in the
    configuration's dtype (norm scales in float32)."""
    dt, L = jnp.dtype(cfg["dtype"]), cfg["num_layers"]
    ke, ka, km = jax.random.split(key, 3)
    return {
        "embed": C.embed_params(cfg, ke, dt),
        "layers": {"ln1": C.norm_params(cfg, (L,)),
                   "attn": C.attn_params(cfg, ka, (L,), dt),
                   "ln2": C.norm_params(cfg, (L,)),
                   "mlp": C.mlp_params(cfg, km, (L,), dt)},
        "ln_f": C.norm_params(cfg),
    }


def loss(cfg: dict, params, batch, ein):
    p = C.f32(params)
    tokens = batch["tokens"]
    x = C.embed(cfg, p["embed"], tokens)

    def block(x, lp):
        x = x + C.attention(cfg, lp["attn"], C.norm(cfg, lp["ln1"], x), ein,
                            causal=True)
        return x + C.mlp(cfg, lp["mlp"], C.norm(cfg, lp["ln2"], x), ein)

    x = C.over_layers(block, x, p["layers"])
    return C.xent(cfg, p["embed"], C.norm(cfg, p["ln_f"], x), tokens, ein)
