"""Shared neural-net building blocks (pure-function style, params as pytrees).

Conventions:
* params are plain dicts of jnp arrays; layer-stacked params carry a leading
  ``L`` axis and are consumed via ``jax.lax.scan`` (small HLO, fast SPMD).
* compute dtype = cfg.dtype (bf16 by default); norms/softmax accumulate fp32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig


def dtype_of(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------- norms ----
def rmsnorm(x, scale, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(jnp.float32))
    return out.astype(x.dtype)


def layernorm(x, scale, bias, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32) \
        + bias.astype(jnp.float32)
    return out.astype(x.dtype)


def norm_init(cfg: ModelConfig, shape_prefix=()):
    if cfg.norm_type == "rmsnorm":
        return {"scale": jnp.zeros(shape_prefix + (cfg.d_model,), jnp.float32)}
    return {"scale": jnp.ones(shape_prefix + (cfg.d_model,), jnp.float32),
            "bias": jnp.zeros(shape_prefix + (cfg.d_model,), jnp.float32)}


def apply_norm(cfg: ModelConfig, p, x):
    if cfg.norm_type == "rmsnorm":
        return rmsnorm(x, p["scale"], cfg.norm_eps)
    return layernorm(x, p["scale"], p["bias"], cfg.norm_eps)


# ----------------------------------------------------------------- mlps ----
def mlp_init(cfg: ModelConfig, rng, shape_prefix=(), d_ff=None):
    d, ff = cfg.d_model, d_ff or cfg.d_ff
    dt = dtype_of(cfg)
    k1, k2 = jax.random.split(rng)
    s_in = (2.0 / d) ** 0.5
    s_out = (2.0 / ff) ** 0.5
    if cfg.mlp_type == "swiglu":
        # gate and up fused on the output dim: (d, 2*ff)
        return {
            "wi": (jax.random.normal(k1, shape_prefix + (d, 2 * ff)) * s_in).astype(dt),
            "wo": (jax.random.normal(k2, shape_prefix + (ff, d)) * s_out).astype(dt),
        }
    return {
        "wi": (jax.random.normal(k1, shape_prefix + (d, ff)) * s_in).astype(dt),
        "bi": jnp.zeros(shape_prefix + (ff,), dt),
        "wo": (jax.random.normal(k2, shape_prefix + (ff, d)) * s_out).astype(dt),
        "bo": jnp.zeros(shape_prefix + (d,), dt),
    }


@jax.named_scope("mlp")
def apply_mlp(cfg: ModelConfig, p, x):
    if cfg.mlp_type == "swiglu":
        h = x @ p["wi"]
        gate, up = jnp.split(h, 2, axis=-1)
        return (jax.nn.silu(gate) * up) @ p["wo"]
    h = jax.nn.gelu(x @ p["wi"] + p["bi"])
    return h @ p["wo"] + p["bo"]


# ----------------------------------------------------------- embeddings ----
def padded_vocab(cfg: ModelConfig) -> int:
    """Unembedding is padded to a 128 multiple: keeps the logits' vocab dim
    shardable over the model axis (and MXU-aligned) even for vocabs like
    granite's 49155.  Pad columns are masked to -inf in the loss."""
    return ((cfg.vocab_size + 127) // 128) * 128


def shard_hint(x, spec):
    """with_sharding_constraint that is a no-op outside a mesh context."""
    try:
        return jax.lax.with_sharding_constraint(x, spec)
    except Exception:  # noqa: BLE001 — no ambient mesh (unit tests)
        return x


def embed_init(cfg: ModelConfig, rng):
    dt = dtype_of(cfg)
    p = {"tok": (jax.random.normal(rng, (cfg.vocab_size, cfg.d_model)) * 0.02).astype(dt)}
    if not cfg.tie_embeddings:
        p["unembed"] = (jax.random.normal(jax.random.fold_in(rng, 1),
                                          (cfg.d_model, padded_vocab(cfg))) * 0.02).astype(dt)
    if cfg.pos_type == "learned":
        p["pos"] = (jax.random.normal(jax.random.fold_in(rng, 2),
                                      (cfg.max_position, cfg.d_model)) * 0.02).astype(dt)
    return p


@jax.named_scope("embed")
def embed_tokens(cfg: ModelConfig, p, tokens, pos_offset=0):
    x = jnp.take(p["tok"], tokens, axis=0)
    if cfg.pos_type == "learned":
        s = tokens.shape[-1]
        pos = jax.lax.dynamic_slice_in_dim(p["pos"], pos_offset, s, axis=0)
        x = x + pos
    elif cfg.pos_type == "sinusoidal":
        s = tokens.shape[-1]
        x = x + sinusoidal(pos_offset + jnp.arange(s), cfg.d_model).astype(x.dtype)
    return x


@jax.named_scope("head")
def unembed(cfg: ModelConfig, p, x, *, padded: bool = False):
    """Project to vocab logits (fp32).

    padded=True keeps the padded, model-axis-shardable logits (training path:
    never materialises a replicated full-vocab tensor); padded=False slices to
    the true vocab (serving / small-scale eval paths).
    """
    from jax.sharding import PartitionSpec as P

    w = p["tok"].T if cfg.tie_embeddings else p["unembed"]
    logits = (x @ w).astype(jnp.float32)
    if cfg.logit_softcap > 0:
        c = cfg.logit_softcap
        logits = c * jnp.tanh(logits / c)
    if padded and not cfg.tie_embeddings:
        if cfg.shard_logits_vocab:
            spec = (None,) * (logits.ndim - 1) + ("model",)
            return shard_hint(logits, P(*spec))
        return logits
    if not cfg.tie_embeddings and logits.shape[-1] != cfg.vocab_size:
        logits = logits[..., :cfg.vocab_size]
    return logits


def sinusoidal(positions, dim):
    half = dim // 2
    freqs = jnp.exp(-jnp.log(10000.0) * jnp.arange(half) / max(half - 1, 1))
    ang = positions[..., None].astype(jnp.float32) * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ----------------------------------------------------------------- rope ----
def rope_freqs(cfg: ModelConfig):
    hd = cfg.head_dim
    return 1.0 / (cfg.rope_theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))


def apply_rope(x, positions, theta: float):
    """x: (..., S, H, hd); positions: (..., S) int."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions[..., None].astype(jnp.float32) * inv        # (..., S, hd/2)
    sin, cos = jnp.sin(ang)[..., None, :], jnp.cos(ang)[..., None, :]  # (..., S, 1, hd/2)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------- losses ----
@jax.named_scope("head")
def softmax_xent(logits, labels, mask=None, valid_vocab: int | None = None):
    """Mean token cross-entropy; logits fp32 (B, S, Vp), labels int (B, S).

    valid_vocab: true vocab size when logits carry sharding padding — pad
    columns are suppressed with -inf before the logsumexp.
    """
    if valid_vocab is not None and logits.shape[-1] != valid_vocab:
        col = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
        logits = jnp.where(col < valid_vocab, logits, -1e30)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is None:
        return jnp.mean(nll)
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
