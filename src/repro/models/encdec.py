"""Whisper-style encoder-decoder backbone (arXiv:2212.04356).

The mel-spectrogram + conv feature extractor is a STUB per the assignment
carve-out: the batch provides precomputed frame embeddings
``frames (B, encoder_seq, d_model)``.  Encoder: bidirectional self-attention +
GELU MLP, sinusoidal positions.  Decoder: causal self-attention + cross
attention to encoder memory + GELU MLP, learned positions, layernorm.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn_mod
from repro.models import layers as L


def init_params(cfg: ModelConfig, rng):
    ke, kenc, kdec = jax.random.split(rng, 3)

    def enc_layer(key):
        k1, k2 = jax.random.split(key)
        return {"ln1": L.norm_init(cfg), "attn": attn_mod.attn_init(cfg, k1),
                "ln2": L.norm_init(cfg), "mlp": L.mlp_init(cfg, k2)}

    def dec_layer(key):
        k1, k2, k3 = jax.random.split(key, 3)
        return {"ln1": L.norm_init(cfg), "attn": attn_mod.attn_init(cfg, k1),
                "lnx": L.norm_init(cfg), "xattn": attn_mod.attn_init(cfg, k2),
                "ln2": L.norm_init(cfg), "mlp": L.mlp_init(cfg, k3)}

    return {
        "embed": L.embed_init(cfg, ke),
        "enc_layers": jax.vmap(enc_layer)(jax.random.split(kenc, cfg.encoder_layers)),
        "enc_ln_f": L.norm_init(cfg),
        "dec_layers": jax.vmap(dec_layer)(jax.random.split(kdec, cfg.num_layers)),
        "ln_f": L.norm_init(cfg),
    }


def encode(cfg: ModelConfig, params, frames, impl="ref"):
    """frames (B, S_enc, d) stub embeddings -> encoder memory (B, S_enc, d)."""
    S = frames.shape[1]
    x = frames.astype(L.dtype_of(cfg)) + \
        L.sinusoidal(jnp.arange(S), cfg.d_model).astype(L.dtype_of(cfg))

    def scan_fn(h, p):
        a, _ = attn_mod.attention(cfg, p["attn"], L.apply_norm(cfg, p["ln1"], h),
                                  causal=False, impl=impl)
        h = h + a
        h = h + L.apply_mlp(cfg, p["mlp"], L.apply_norm(cfg, p["ln2"], h))
        return h, None

    x, _ = jax.lax.scan(scan_fn, x, params["enc_layers"],
                        unroll=bool(cfg.scan_unroll))
    return L.apply_norm(cfg, params["enc_ln_f"], x)


def _dec_layer(cfg, p, x, memory, positions, impl="ref"):
    a, kv = attn_mod.attention(cfg, p["attn"], L.apply_norm(cfg, p["ln1"], x),
                               positions=positions, causal=True, impl=impl)
    x = x + a
    a, xkv = attn_mod.attention(cfg, p["xattn"], L.apply_norm(cfg, p["lnx"], x),
                                memory=memory)
    x = x + a
    x = x + L.apply_mlp(cfg, p["mlp"], L.apply_norm(cfg, p["ln2"], x))
    return x, kv, xkv


def forward(cfg: ModelConfig, params, batch, impl: str = "ref",
            padded_logits: bool = False):
    """batch: {tokens (B,S), frames (B,S_enc,d)} -> (logits, aux)."""
    memory = encode(cfg, params, batch["frames"], impl=impl)
    tokens = batch["tokens"]
    x = L.embed_tokens(cfg, params["embed"], tokens)
    positions = jnp.arange(tokens.shape[1])

    def scan_fn(h, p):
        h, _, _ = _dec_layer(cfg, p, h, memory, positions, impl=impl)
        return h, None

    body = scan_fn
    if cfg.remat:
        body = jax.checkpoint(scan_fn)
    x, _ = jax.lax.scan(body, x, params["dec_layers"],
                        unroll=bool(cfg.scan_unroll))
    x = L.apply_norm(cfg, params["ln_f"], x)
    return L.unembed(cfg, params["embed"], x, padded=padded_logits), jnp.float32(0)


def loss_fn(cfg: ModelConfig, params, batch, rng=None, impl: str = "ref"):
    logits, _ = forward(cfg, params, batch, impl=impl, padded_logits=True)
    return L.softmax_xent(logits[:, :-1], batch["tokens"][:, 1:],
                          valid_vocab=cfg.vocab_size), {}


# ------------------------------------------------------------- serving -----
def init_cache(cfg: ModelConfig, batch: int, cache_len: int):
    dt = L.dtype_of(cfg)
    nl = cfg.num_layers
    z = jnp.zeros((nl, batch, cache_len, cfg.num_kv_heads, cfg.head_dim), dt)
    zx = jnp.zeros((nl, batch, cfg.encoder_seq, cfg.num_kv_heads, cfg.head_dim), dt)
    return {"k": z, "v": z, "xk": zx, "xv": zx}


def prefill(cfg: ModelConfig, params, batch, cache_len=None, impl="ref",
            window=None):
    memory = encode(cfg, params, batch["frames"], impl=impl)
    tokens = batch["tokens"]
    B, S = tokens.shape
    cache_len = cache_len or S
    x = L.embed_tokens(cfg, params["embed"], tokens)
    positions = jnp.arange(S)

    def scan_fn(h, p):
        h, kv, xkv = _dec_layer(cfg, p, h, memory, positions, impl=impl)
        return h, (kv, xkv)

    x, ((ks, vs), (xks, xvs)) = jax.lax.scan(scan_fn, x, params["dec_layers"],
                                             unroll=bool(cfg.scan_unroll))
    x = L.apply_norm(cfg, params["ln_f"], x)
    logits = L.unembed(cfg, params["embed"], x[:, -1:])
    pad = cache_len - S
    if pad > 0:
        ks = jnp.pad(ks, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        vs = jnp.pad(vs, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
    return logits, {"k": ks, "v": vs, "xk": xks, "xv": xvs}


def decode_step(cfg: ModelConfig, params, token, cache, pos, *, ring=False,
                window=None, impl="ref"):
    """Self-attn against the cache + cross-attn against cached encoder K/V."""
    x = jnp.take(params["embed"]["tok"], token[:, None], axis=0)
    if cfg.pos_type == "learned":
        x = x + jax.lax.dynamic_slice_in_dim(params["embed"]["pos"], pos, 1, 0)
    elif cfg.pos_type == "sinusoidal":
        x = x + L.sinusoidal(jnp.asarray(pos)[None], cfg.d_model)[None].astype(x.dtype)
    B = x.shape[0]

    def scan_fn(h, xs):
        p, ck, cv, xk, xv = xs
        z = L.apply_norm(cfg, p["ln1"], h)
        a, new_cache = attn_mod.decode_attention(
            cfg, p["attn"], z, {"k": ck, "v": cv}, pos, ring=ring,
            window=window or 0)
        h = h + a
        # cross attention against fixed encoder memory K/V
        z = L.apply_norm(cfg, p["lnx"], h)
        q = (z @ p["xattn"]["wq"] + p["xattn"].get("bq", 0)).reshape(
            B, 1, cfg.num_heads, cfg.head_dim)
        out = attn_mod.dot_product_attention(
            q, attn_mod.repeat_kv(xk, cfg.num_heads),
            attn_mod.repeat_kv(xv, cfg.num_heads), causal=False)
        h = h + out.reshape(B, 1, cfg.q_dim) @ p["xattn"]["wo"]
        h = h + L.apply_mlp(cfg, p["mlp"], L.apply_norm(cfg, p["ln2"], h))
        return h, (new_cache["k"], new_cache["v"])

    x, (ks, vs) = jax.lax.scan(scan_fn, x, (
        params["dec_layers"], cache["k"], cache["v"], cache["xk"], cache["xv"]),
        unroll=bool(cfg.scan_unroll))
    x = L.apply_norm(cfg, params["ln_f"], x)
    logits = L.unembed(cfg, params["embed"], x)[:, 0]
    return logits, {"k": ks, "v": vs, "xk": cache["xk"], "xv": cache["xv"]}
