"""Plain reference of the encoder-decoder family (the Whisper backbone):
an encoder of bidirectional self-attention over the frame embeddings with
sinusoidal positions, and a decoder with learned positions, of causal
self-attention, cross-attention to the encoder's output and a GELU MLP,
layer norms before each block, and the tied token embedding as the output
projection.

Departures of the system from the published Whisper, which the reference
shares: the audio frontend (log-mel and two convolutions) is left out and
the batch carries frame embeddings; attention has biases on q, k and v and
none on its output projection (published: q, v and output; a key bias is
inert under softmax); GELU is in its tanh form.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.ref import common as C


def init(cfg: dict, key):
    """Random weights in the layout the system's encoder-decoder model
    takes, in the configuration's dtype (norm parameters in float32)."""
    dt = jnp.dtype(cfg["dtype"])
    Le, Ld = cfg["encoder_layers"], cfg["num_layers"]
    k = jax.random.split(key, 6)
    return {
        "embed": C.embed_params(cfg, k[0], dt),
        "enc_layers": {"ln1": C.norm_params(cfg, (Le,)),
                       "attn": C.attn_params(cfg, k[1], (Le,), dt),
                       "ln2": C.norm_params(cfg, (Le,)),
                       "mlp": C.mlp_params(cfg, k[2], (Le,), dt)},
        "enc_ln_f": C.norm_params(cfg),
        "dec_layers": {"ln1": C.norm_params(cfg, (Ld,)),
                       "attn": C.attn_params(cfg, k[3], (Ld,), dt),
                       "lnx": C.norm_params(cfg, (Ld,)),
                       "xattn": C.attn_params(cfg, k[4], (Ld,), dt),
                       "ln2": C.norm_params(cfg, (Ld,)),
                       "mlp": C.mlp_params(cfg, k[5], (Ld,), dt)},
        "ln_f": C.norm_params(cfg),
    }


def loss(cfg: dict, params, batch, ein):
    p = C.f32(params)
    frames, tokens = batch["frames"].astype(jnp.float32), batch["tokens"]
    d = cfg["d_model"]
    m = frames + C.sinusoid(frames.shape[1], d)

    def enc_block(m, lp):
        m = m + C.attention(cfg, lp["attn"], C.norm(cfg, lp["ln1"], m), ein,
                            causal=False)
        return m + C.mlp(cfg, lp["mlp"], C.norm(cfg, lp["ln2"], m), ein)

    m = C.norm(cfg, p["enc_ln_f"], C.over_layers(enc_block, m, p["enc_layers"]))

    def dec_block(x, lp):
        x = x + C.attention(cfg, lp["attn"], C.norm(cfg, lp["ln1"], x), ein,
                            causal=True)
        x = x + C.attention(cfg, lp["xattn"], C.norm(cfg, lp["lnx"], x), ein,
                            causal=False, memory=m)
        return x + C.mlp(cfg, lp["mlp"], C.norm(cfg, lp["ln2"], x), ein)

    x = C.over_layers(dec_block, C.embed(cfg, p["embed"], tokens),
                      p["dec_layers"])
    return C.xent(cfg, p["embed"], C.norm(cfg, p["ln_f"], x), tokens, ein)
