"""Plain reference of the mixture-of-experts decoder family (OLMoE): pre-norm
blocks of multi-head attention with QK-norm (RMSNorm over the full q and k
projections, before rotary positions), then a sparse expert layer, then the
untied vocabulary projection.

The expert layer is the chip's share of an expert-parallel layer: a float32
softmax router over all ``router_experts`` picks each token's top
``experts_per_token`` (their weights renormalised only where
``moe_norm_topk``); of those, the ``num_experts`` held here, from
``expert_offset`` on, each run on every token and are multiplied by the
token's router weight for them, zero where they are not among its top k.
What the absent experts would add is left out, as in the program.  The loss
adds ``moe_aux_coef`` x the load-balancing loss of HF's
``load_balancing_loss_func``, over all layers' tokens together.

Departure of the system from the published OLMoE, which the reference
shares: no router z-loss (the configuration lists it under ``assumed``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.ref import common as C


def init(cfg: dict, key):
    """Random weights in the layout the system's moe model takes, in the
    configuration's dtype (norm scales and the router in float32)."""
    dt, L = jnp.dtype(cfg["dtype"]), cfg["num_layers"]
    d, ff, E = cfg["d_model"], cfg["d_ff"], cfg["num_experts"]
    hd = cfg["head_dim"]
    ke, ka, kr, ki, ko = jax.random.split(key, 5)
    attn = C.attn_params(cfg, ka, (L,), dt)
    attn.update(q_norm=jnp.zeros((L, cfg["num_heads"] * hd), C.F32),
                k_norm=jnp.zeros((L, cfg["num_kv_heads"] * hd), C.F32))
    return {
        "embed": C.embed_params(cfg, ke, dt),
        "layers": {
            "ln1": C.norm_params(cfg, (L,)),
            "attn": attn,
            "ln2": C.norm_params(cfg, (L,)),
            "moe": {"router": C.normal(kr, (L, d, cfg["router_experts"]),
                                       d ** -0.5, C.F32),
                    "wi": C.normal(ki, (L, E, d, 2 * ff), (2 / d) ** 0.5, dt),
                    "wo": C.normal(ko, (L, E, ff, d), (2 / ff) ** 0.5, dt)},
        },
        "ln_f": C.norm_params(cfg),
    }


def _rms(cfg, x, scale):
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + cfg["norm_eps"]) * (1.0 + scale)


def attention(cfg, p, x, ein):
    """Causal multi-head attention with QK-norm and rotary positions."""
    B, S, _ = x.shape
    H, K, hd = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    proj = lambda w: ein("bsd,de->bse", x, p[w])
    q = _rms(cfg, proj("wq"), p["q_norm"]).reshape(B, S, H, hd)
    k = _rms(cfg, proj("wk"), p["k_norm"]).reshape(B, S, K, hd)
    v = proj("wv").reshape(B, S, K, hd)
    q, k = C.rope(q, cfg["rope_theta"]), C.rope(k, cfg["rope_theta"])
    k, v = jnp.repeat(k, H // K, axis=2), jnp.repeat(v, H // K, axis=2)
    s = ein("bqhd,bkhd->bhqk", q, k) / hd ** 0.5
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    o = ein("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    return ein("bse,ed->bsd", o.reshape(B, S, H * hd), p["wo"])


def experts(cfg, p, x, ein):
    """-> (the held experts' part of the layer, the routing shares (k, R)
    and the mean router probabilities (R,) for the load-balancing loss)."""
    R, k = cfg["router_experts"], cfg["experts_per_token"]
    held = cfg["expert_offset"] + jnp.arange(cfg["num_experts"])
    probs = jax.nn.softmax(ein("bsd,de->bse", x, p["router"]), axis=-1)
    w, idx = jax.lax.top_k(probs, k)
    if cfg["moe_norm_topk"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    # (held, B, S): each held expert's router weight, 0 where not picked
    gates = jnp.moveaxis(jnp.sum(
        jnp.where(idx[..., None] == held, w[..., None], 0.0), axis=2), -1, 0)

    @jax.checkpoint             # one expert's activations at a time
    def expert(out, e):
        wi, wo, g = e
        gate, up = jnp.split(ein("bsd,df->bsf", x, wi), 2, axis=-1)
        y = ein("bsf,fd->bsd", jax.nn.silu(gate) * up, wo)
        return out + y * g[..., None], None

    out = jax.lax.scan(expert, jnp.zeros_like(x),
                       (p["wi"], p["wo"], gates))[0]
    frac = jnp.mean(jax.nn.one_hot(idx, R, dtype=C.F32), axis=(0, 1))
    return out, frac, jnp.mean(probs, axis=(0, 1))


def loss(cfg: dict, params, batch, ein):
    p = C.f32(params)
    tokens = batch["tokens"]
    x = C.embed(cfg, p["embed"], tokens)

    @jax.checkpoint                 # one layer's activations at a time
    def block(x, lp):
        x = x + attention(cfg, lp["attn"], C.norm(cfg, lp["ln1"], x), ein)
        y, frac, prob = experts(cfg, lp["moe"], C.norm(cfg, lp["ln2"], x), ein)
        return x + y, (frac, prob)

    x, (frac, prob) = jax.lax.scan(block, x, p["layers"])
    balance = cfg["router_experts"] * jnp.sum(
        jnp.mean(frac, axis=0) * jnp.mean(prob, axis=0)[None])
    return (C.xent(cfg, p["embed"], C.norm(cfg, p["ln_f"], x), tokens, ein)
            + cfg["moe_aux_coef"] * balance)
