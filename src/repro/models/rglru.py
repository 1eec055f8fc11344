"""RecurrentGemma (arXiv:2402.19427): RG-LRU recurrent blocks + local attention,
pattern 1 attention : 2 recurrent (layer l is attention iff l % 3 == 2).

Each layer = temporal-mixing block (RG-LRU or local MQA) + GeGLU MLP, pre-norm.
RG-LRU:  r_t = sigmoid(W_a x_t), i_t = sigmoid(W_i x_t),
         a_t = exp(-c * softplus(Lambda) * r_t)       (c = 8)
         h_t = a_t h_{t-1} + sqrt(1 - a_t^2) * (i_t * x_t)
Train/prefill uses ``jax.lax.associative_scan`` over the linear recurrence;
decode is the O(1) sequential step.  26 layers = 8 x (R,R,A) + 2 tail R.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn_mod
from repro.models import layers as L

_C = 8.0  # RG-LRU decay sharpness constant


# --------------------------------------------------------------- params ----
def _rec_init(cfg: ModelConfig, rng, prefix=()):
    d, w = cfg.d_model, cfg.lru_width or cfg.d_model
    dt = L.dtype_of(cfg)
    ks = jax.random.split(rng, 6)
    g = lambda k, sh, s: (jax.random.normal(k, prefix + sh) * s).astype(dt)
    return {
        "wx": g(ks[0], (d, w), (1 / d) ** 0.5),
        "wy": g(ks[1], (d, w), (1 / d) ** 0.5),
        "conv_w": g(ks[2], (w, cfg.ssm_conv), (1 / cfg.ssm_conv) ** 0.5),
        "conv_b": jnp.zeros(prefix + (w,), dt),
        "wa": g(ks[3], (w, w), (1 / w) ** 0.5),
        "ba": jnp.zeros(prefix + (w,), jnp.float32),
        "wi": g(ks[4], (w, w), (1 / w) ** 0.5),
        "bi": jnp.zeros(prefix + (w,), jnp.float32),
        "lam": jnp.full(prefix + (w,), 0.5, jnp.float32),
        "wo": g(ks[5], (w, d), (1 / w) ** 0.5),
    }


def _layer_init(cfg: ModelConfig, rng, kind: str):
    k1, k2 = jax.random.split(rng)
    p = {"ln1": L.norm_init(cfg), "ln2": L.norm_init(cfg),
         "mlp": L.mlp_init(cfg, k2)}
    if kind == "attn":
        p["attn"] = attn_mod.attn_init(cfg, k1)
    else:
        p["rec"] = _rec_init(cfg, k1)
    return p


def init_params(cfg: ModelConfig, rng):
    """26 = n_blocks x (R,R,A) + n_tail x R; params stacked per role."""
    n_blocks = cfg.num_layers // 3
    n_tail = cfg.num_layers - 3 * n_blocks
    k_embed, kb, kt = jax.random.split(rng, 3)

    def block_init(key):
        k1, k2, k3 = jax.random.split(key, 3)
        return {"r1": _layer_init(cfg, k1, "rec"),
                "r2": _layer_init(cfg, k2, "rec"),
                "attn": _layer_init(cfg, k3, "attn")}

    blocks = jax.vmap(block_init)(jax.random.split(kb, n_blocks))
    p = {"embed": L.embed_init(cfg, k_embed), "blocks": blocks,
         "ln_f": L.norm_init(cfg)}
    if n_tail:
        p["tail"] = jax.vmap(lambda k: _layer_init(cfg, k, "rec"))(
            jax.random.split(kt, n_tail))
    return p


# -------------------------------------------------------------- RG-LRU -----
def _rglru_gates(p, x):
    """x (B,S,w) post-conv -> (log_a (B,S,w) fp32, gated input (B,S,w) fp32)."""
    xf = x.astype(jnp.float32)
    r = jax.nn.sigmoid(xf @ p["wa"].astype(jnp.float32) + p["ba"])
    i = jax.nn.sigmoid(xf @ p["wi"].astype(jnp.float32) + p["bi"])
    log_a = -_C * jax.nn.softplus(p["lam"]) * r
    a2 = jnp.exp(2.0 * log_a)
    b = jnp.sqrt(jnp.maximum(1.0 - a2, 1e-12)) * (i * xf)
    return log_a, b


def _linear_scan(log_a, b, h0=None):
    """h_t = exp(log_a_t) h_{t-1} + b_t via associative scan over axis 1."""
    if h0 is not None:
        b = b.at[:, 0].add(jnp.exp(log_a[:, 0]) * h0)

    def combine(c1, c2):
        (la1, b1), (la2, b2) = c1, c2
        return la1 + la2, b1 * jnp.exp(la2) + b2

    _, h = jax.lax.associative_scan(combine, (log_a, b), axis=1)
    return h


def _rec_apply(cfg, p, x, conv_state=None, h0=None, sequential=False):
    """Recurrent temporal block. x (B,S,d) -> (y (B,S,d), (conv_state, h_last))."""
    xb = x @ p["wx"]
    yb = x @ p["wy"]
    K = p["conv_w"].shape[-1]
    if conv_state is None:
        pad = jnp.zeros(xb.shape[:1] + (K - 1,) + xb.shape[2:], xb.dtype)
    else:
        pad = conv_state.astype(xb.dtype)
    xp = jnp.concatenate([pad, xb], axis=1)
    xc = sum(xp[:, i:i + xb.shape[1]] * p["conv_w"][:, i] for i in range(K)) \
        + p["conv_b"]
    new_conv = xp[:, -(K - 1):]
    log_a, b = _rglru_gates(p, xc)
    if sequential:  # decode: S == 1
        h_prev = jnp.zeros_like(b[:, 0]) if h0 is None else h0
        h = (jnp.exp(log_a[:, 0]) * h_prev + b[:, 0])[:, None]
    else:
        h = _linear_scan(log_a, b, h0)
    out = (h * jax.nn.gelu(yb.astype(jnp.float32))).astype(x.dtype) @ p["wo"]
    return out, (new_conv, h[:, -1])


# --------------------------------------------------------------- layers ----
def _apply_layer(cfg, p, x, kind, positions=None, state=None, pos=None,
                 impl="ref"):
    """Returns (x, new_state).  state: (conv,h) for rec; kv ring cache for attn."""
    z = L.apply_norm(cfg, p["ln1"], x)
    if kind == "rec":
        conv_s, h0 = (None, None) if state is None else state
        y, new_state = _rec_apply(cfg, p["rec"], z, conv_s, h0,
                                  sequential=state is not None and z.shape[1] == 1)
    else:
        if state is None:  # training/prefill full local attention
            y, (k, v) = attn_mod.attention(cfg, p["attn"], z,
                                           positions=positions, causal=True,
                                           window=cfg.local_window, impl=impl)
            new_state = (k, v)
        else:
            y, cache = attn_mod.decode_attention(
                cfg, p["attn"], z, {"k": state[0], "v": state[1]}, pos,
                ring=True, window=cfg.local_window)
            new_state = (cache["k"], cache["v"])
    x = x + y
    x = x + L.apply_mlp(cfg, p["mlp"], L.apply_norm(cfg, p["ln2"], x))
    return x, new_state


def forward(cfg: ModelConfig, params, batch, impl: str = "ref",
            padded_logits: bool = False):
    tokens = batch["tokens"]
    x = L.embed_tokens(cfg, params["embed"], tokens)
    positions = jnp.arange(tokens.shape[1])

    def block(p, h):
        h, _ = _apply_layer(cfg, p["r1"], h, "rec")
        h, _ = _apply_layer(cfg, p["r2"], h, "rec")
        h, _ = _apply_layer(cfg, p["attn"], h, "attn", positions=positions,
                            impl=impl)
        return h

    if cfg.remat:
        block = jax.checkpoint(block)
    x, _ = jax.lax.scan(lambda h, p: (block(p, h), None), x, params["blocks"],
                        unroll=bool(cfg.scan_unroll))
    if "tail" in params:
        def tail(p, h):
            h, _ = _apply_layer(cfg, p, h, "rec")
            return h
        if cfg.remat:
            tail = jax.checkpoint(tail)
        x, _ = jax.lax.scan(lambda h, p: (tail(p, h), None), x, params["tail"],
                            unroll=bool(cfg.scan_unroll))
    x = L.apply_norm(cfg, params["ln_f"], x)
    return L.unembed(cfg, params["embed"], x, padded=padded_logits), jnp.float32(0)


def loss_fn(cfg: ModelConfig, params, batch, rng=None, impl: str = "ref"):
    logits, _ = forward(cfg, params, batch, impl=impl, padded_logits=True)
    return L.softmax_xent(logits[:, :-1], batch["tokens"][:, 1:],
                          valid_vocab=cfg.vocab_size), {}


# ------------------------------------------------------------- serving -----
def init_cache(cfg: ModelConfig, batch: int, cache_len: int = 0):
    """Recurrent state + conv tail per recurrent layer; ring KV per attn layer.
    Cache length for attention = local_window (O(1) in sequence length)."""
    w = cfg.lru_width or cfg.d_model
    n_blocks = cfg.num_layers // 3
    n_tail = cfg.num_layers - 3 * n_blocks
    K = cfg.ssm_conv
    dt = L.dtype_of(cfg)
    W = cfg.local_window
    rec = lambda n: {"conv": jnp.zeros((n, batch, K - 1, w), dt),
                     "h": jnp.zeros((n, batch, w), jnp.float32)}
    cache = {
        "r1": rec(n_blocks), "r2": rec(n_blocks),
        "attn": {"k": jnp.zeros((n_blocks, batch, W, cfg.num_kv_heads,
                                 cfg.head_dim), dt),
                 "v": jnp.zeros((n_blocks, batch, W, cfg.num_kv_heads,
                                 cfg.head_dim), dt)},
    }
    if n_tail:
        cache["tail"] = rec(n_tail)
    return cache


def prefill(cfg: ModelConfig, params, batch, cache_len=None, impl="ref",
            window=None):
    tokens = batch["tokens"]
    B, S = tokens.shape
    W = cfg.local_window
    x = L.embed_tokens(cfg, params["embed"], tokens)
    positions = jnp.arange(S)

    def block(h, p):
        h, (c1, h1) = _apply_layer(cfg, p["r1"], h, "rec")
        h, (c2, h2) = _apply_layer(cfg, p["r2"], h, "rec")
        h, (k, v) = _apply_layer(cfg, p["attn"], h, "attn", positions=positions,
                                 impl=impl)
        return h, ((c1, h1), (c2, h2), (k, v))

    x, (s1, s2, kv) = jax.lax.scan(block, x, params["blocks"],
                                   unroll=bool(cfg.scan_unroll))
    ks, vs = kv
    # ring-ify the last W positions (same layout as attention.cache_write)
    if S >= W:
        ks, vs = ks[:, :, -W:], vs[:, :, -W:]
        shift = S % W
        ks, vs = jnp.roll(ks, shift, axis=2), jnp.roll(vs, shift, axis=2)
    else:
        pad = W - S
        ks = jnp.pad(ks, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        vs = jnp.pad(vs, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))

    cache = {"r1": {"conv": s1[0], "h": s1[1]},
             "r2": {"conv": s2[0], "h": s2[1]},
             "attn": {"k": ks, "v": vs}}
    if "tail" in params:
        tail_p = params["tail"]

        def tailf(h, p):
            h, (c, hs) = _apply_layer(cfg, p, h, "rec")
            return h, (c, hs)

        x, (ct, ht) = jax.lax.scan(tailf, x, tail_p,
                                   unroll=bool(cfg.scan_unroll))
        cache["tail"] = {"conv": ct, "h": ht}
    x = L.apply_norm(cfg, params["ln_f"], x)
    return L.unembed(cfg, params["embed"], x[:, -1:]), cache


def decode_step(cfg: ModelConfig, params, token, cache, pos, *, ring=True,
                window=None, impl="ref"):
    x = L.embed_tokens(cfg, params["embed"], token[:, None])

    def block(h, xs):
        p, c1, h1, c2, h2, ck, cv = xs
        h, (nc1, nh1) = _apply_layer(cfg, p["r1"], h, "rec", state=(c1, h1))
        h, (nc2, nh2) = _apply_layer(cfg, p["r2"], h, "rec", state=(c2, h2))
        h, (nk, nv) = _apply_layer(cfg, p["attn"], h, "attn", state=(ck, cv),
                                   pos=pos)
        return h, (nc1, nh1, nc2, nh2, nk, nv)

    x, outs = jax.lax.scan(block, x, (
        params["blocks"], cache["r1"]["conv"], cache["r1"]["h"],
        cache["r2"]["conv"], cache["r2"]["h"],
        cache["attn"]["k"], cache["attn"]["v"]), unroll=bool(cfg.scan_unroll))
    new_cache = {"r1": {"conv": outs[0], "h": outs[1]},
                 "r2": {"conv": outs[2], "h": outs[3]},
                 "attn": {"k": outs[4], "v": outs[5]}}
    if "tail" in params:
        new_cache["tail"] = cache["tail"]
        tail_p = params["tail"]

        def tailf(h, xs):
            p, c, hs = xs
            h, (nc, nhs) = _apply_layer(cfg, p, h, "rec", state=(c, hs))
            return h, (nc, nhs)

        x, (ct, ht) = jax.lax.scan(
            tailf, x, (tail_p, cache["tail"]["conv"], cache["tail"]["h"]),
            unroll=bool(cfg.scan_unroll))
        new_cache["tail"] = {"conv": ct, "h": ht}
    x = L.apply_norm(cfg, params["ln_f"], x)
    return L.unembed(cfg, params["embed"], x)[:, 0], new_cache
