"""Decoder-only transformer (dense GQA / MoE / VLM-backbone).

Covers: qwen1.5-4b, granite-3-2b, granite-8b, starcoder2-7b (dense),
mixtral-8x7b, olmoe-1b-7b (moe), internvl2-76b (vlm = dense trunk + stub
vision embeddings spliced into the prefix).  The moe family's expert layer
runs in ``cfg.moe_mode`` (`models/moe.py`): Mixtral in ``dense``, OLMoE in
``dropless``, the chip's share of an expert-parallel layer, whose stats
`loss_fn` returns; ``qk_norm`` gives OLMoE's QK-norm.

Layers are param-stacked (leading L axis) and executed with ``jax.lax.scan``
(+ optional per-layer remat) so the lowered HLO is layer-count independent —
essential for compiling 80-layer/76B configs through SPMD quickly.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn_mod
from repro.models import layers as L
from repro.models import moe as moe_mod


def init_params(cfg: ModelConfig, rng):
    k_embed, k_layers, k_final = jax.random.split(rng, 3)
    n = cfg.num_layers

    def layer_init(key):
        k1, k2 = jax.random.split(key)
        p = {
            "ln1": L.norm_init(cfg),
            "attn": attn_mod.attn_init(cfg, k1),
            "ln2": L.norm_init(cfg),
        }
        if cfg.family == "moe":
            p["moe"] = moe_mod.moe_init(cfg, k2)
        else:
            p["mlp"] = L.mlp_init(cfg, k2)
        return p

    layers = jax.vmap(layer_init)(jax.random.split(k_layers, n))
    return {
        "embed": L.embed_init(cfg, k_embed),
        "layers": layers,
        "ln_f": L.norm_init(cfg),
    }


def _splice_vision(cfg: ModelConfig, x, vision_embeds):
    """VLM stub frontend: overwrite the first ``vision_tokens`` positions with
    the (precomputed) projected patch embeddings."""
    if vision_embeds is None:
        return x
    return jax.lax.dynamic_update_slice(
        x, vision_embeds.astype(x.dtype), (0, 0, 0))


def _layer(cfg: ModelConfig, p, x, positions, impl):
    h, _ = attn_mod.attention(cfg, p["attn"], L.apply_norm(cfg, p["ln1"], x),
                              positions=positions, causal=True, impl=impl)
    x = x + h
    z = L.apply_norm(cfg, p["ln2"], x)
    if cfg.family == "moe":
        h, aux = moe_mod.apply_moe(cfg, p["moe"], z)
    else:
        h, aux = L.apply_mlp(cfg, p["mlp"], z), jnp.float32(0)
    return x + h, aux


def forward(cfg: ModelConfig, params, batch, impl: str = "ref",
            padded_logits: bool = False):
    """batch: {tokens (B,S) int32, [vision_embeds (B,n_vis,d)]} -> (logits,
    aux): aux is (load-balancing loss, stats), `moe.layer_aux` of the
    layers' stacked outputs."""
    tokens = batch["tokens"]
    x = L.embed_tokens(cfg, params["embed"], tokens)
    x = _splice_vision(cfg, x, batch.get("vision_embeds"))
    positions = jnp.arange(tokens.shape[1])

    body = partial(_layer, cfg, positions=positions, impl=impl)
    if cfg.remat:
        body = jax.checkpoint(body)

    if cfg.scan_layers:
        def scan_fn(h, layer_p):
            h, aux = body(layer_p, h)
            return h, aux
        x, auxs = jax.lax.scan(scan_fn, x, params["layers"],
                               unroll=bool(cfg.scan_unroll))
    else:
        per_layer = []
        for i in range(cfg.num_layers):
            layer_p = jax.tree.map(lambda a: a[i], params["layers"])
            x, a = body(layer_p, x)
            per_layer.append(a)
        auxs = jax.tree.map(lambda *a: jnp.stack(a), *per_layer)
    aux = moe_mod.layer_aux(cfg, auxs)
    x = L.apply_norm(cfg, params["ln_f"], x)
    return L.unembed(cfg, params["embed"], x, padded=padded_logits), aux


def loss_fn(cfg: ModelConfig, params, batch, rng=None, impl: str = "ref"):
    """-> (next-token loss + ``cfg.moe_aux_coef`` x load-balancing loss,
    the expert layers' stats: `moe.STATS` summed over layers in the
    dropless mode, else empty)."""
    logits, (aux, stats) = forward(cfg, params, batch, impl=impl,
                                   padded_logits=True)
    loss = L.softmax_xent(logits[:, :-1], batch["tokens"][:, 1:],
                          batch.get("mask"), valid_vocab=cfg.vocab_size)
    return loss + cfg.moe_aux_coef * aux, stats


# ------------------------------------------------------------- serving -----
def init_cache(cfg: ModelConfig, batch: int, cache_len: int):
    dt = L.dtype_of(cfg)
    z = jnp.zeros((cfg.num_layers, batch, cache_len, cfg.num_kv_heads,
                   cfg.head_dim), dt)
    return {"k": z, "v": z}


def prefill(cfg: ModelConfig, params, batch, cache_len: int | None = None,
            impl: str = "ref", window: int | None = None):
    """Run the prompt, return (last-position logits, populated KV cache).

    ``window``: ring-cache width for the sliding-window serving variant
    (cache_len then equals the window, slots hold the last W positions).
    """
    tokens = batch["tokens"]
    B, S = tokens.shape
    cache_len = cache_len or S
    x = L.embed_tokens(cfg, params["embed"], tokens)
    x = _splice_vision(cfg, x, batch.get("vision_embeds"))
    positions = jnp.arange(S)
    eff_window = cfg.sliding_window if window is None else window

    def scan_fn(h, layer_p):
        z = L.apply_norm(cfg, layer_p["ln1"], h)
        a, (k, v) = attn_mod.attention(cfg, layer_p["attn"], z,
                                       positions=positions, causal=True,
                                       window=eff_window, impl=impl)
        h = h + a
        z = L.apply_norm(cfg, layer_p["ln2"], h)
        if cfg.family == "moe":
            m, _ = moe_mod.apply_moe(cfg, layer_p["moe"], z)
        else:
            m = L.apply_mlp(cfg, layer_p["mlp"], z)
        return h + m, (k, v)

    x, (ks, vs) = jax.lax.scan(scan_fn, x, params["layers"],
                                unroll=bool(cfg.scan_unroll))
    x = L.apply_norm(cfg, params["ln_f"], x)
    logits = L.unembed(cfg, params["embed"], x[:, -1:])

    if cache_len >= S:
        pad = cache_len - S
        ks = jnp.pad(ks, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        vs = jnp.pad(vs, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
    else:  # ring: keep the last cache_len positions, rolled into slot order
        ks, vs = ks[:, :, -cache_len:], vs[:, :, -cache_len:]
        shift = S % cache_len
        ks = jnp.roll(ks, shift, axis=2)
        vs = jnp.roll(vs, shift, axis=2)
    return logits, {"k": ks, "v": vs}


def decode_step(cfg: ModelConfig, params, token, cache, pos, *,
                ring: bool = False, window: int | None = None,
                impl: str = "ref"):
    """One decode step.  token (B,) int32; pos: scalar absolute position.

    cache leaves: (L, B, cache_len, K, hd).  Returns (logits (B,V), cache).
    """
    x = jnp.take(params["embed"]["tok"], token[:, None], axis=0)
    if cfg.pos_type == "learned":
        x = x + jax.lax.dynamic_slice_in_dim(params["embed"]["pos"], pos, 1, 0)
    elif cfg.pos_type == "sinusoidal":
        x = x + L.sinusoidal(jnp.asarray(pos)[None], cfg.d_model)[None].astype(x.dtype)
    eff_window = cfg.sliding_window if window is None else window

    def scan_fn(h, xs):
        layer_p, ck, cv = xs
        z = L.apply_norm(cfg, layer_p["ln1"], h)
        a, new_cache = attn_mod.decode_attention(
            cfg, layer_p["attn"], z, {"k": ck, "v": cv}, pos,
            ring=ring, window=eff_window)
        h = h + a
        z = L.apply_norm(cfg, layer_p["ln2"], h)
        if cfg.family == "moe":
            m, _ = moe_mod.apply_moe(cfg, layer_p["moe"], z)
        else:
            m = L.apply_mlp(cfg, layer_p["mlp"], z)
        return h + m, (new_cache["k"], new_cache["v"])

    x, (ks, vs) = jax.lax.scan(scan_fn, x,
                                (params["layers"], cache["k"], cache["v"]),
                                unroll=bool(cfg.scan_unroll))
    x = L.apply_norm(cfg, params["ln_f"], x)
    logits = L.unembed(cfg, params["embed"], x)[:, 0]
    return logits, {"k": ks, "v": vs}
