"""Building blocks of the plain references, in float32 `jax.numpy`.

Nothing here imports the system under test.  Every matrix product goes
through an ``ein`` function that the caller picks: `ein_f32` is the
reference (float32 at ``highest`` precision), `ein_fp8` the control
(operands rounded to float8 e4m3 with one scale per tensor, cotangents to
e5m2, float32 accumulation: the usual recipe of fp8 training).

Parameters follow the layout the system's models take (`ref/dense.py`,
`ref/encdec.py` build it), so one set of weights, made from the seed,
feeds both.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

F32 = jnp.float32


def ein_f32(spec: str, a, b):
    return jnp.einsum(spec, a.astype(F32), b.astype(F32),
                      precision=jax.lax.Precision.HIGHEST)


def _fp8(x, dtype):
    """Round to ``dtype`` (a float8 type) under one scale for the tensor."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, float(jnp.finfo(dtype).max) / amax, 1.0)
    return (x * scale).astype(dtype).astype(F32) / scale


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def ein_fp8(spec: str, a, b):
    return ein_f32(spec, _fp8(a, jnp.float8_e4m3fn), _fp8(b, jnp.float8_e4m3fn))


def _ein_fp8_fwd(spec, a, b):
    qa, qb = _fp8(a, jnp.float8_e4m3fn), _fp8(b, jnp.float8_e4m3fn)
    return ein_f32(spec, qa, qb), (qa, qb)


def _ein_fp8_bwd(spec, res, g):
    _, vjp = jax.vjp(partial(ein_f32, spec), *res)
    return vjp(_fp8(g, jnp.float8_e5m2))


ein_fp8.defvjp(_ein_fp8_fwd, _ein_fp8_bwd)


def pad128(n: int) -> int:
    """The system pads the output projection's vocabulary to a multiple of
    128; the pad columns take no part in the loss."""
    return (n + 127) // 128 * 128


def normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, F32) * std).astype(dtype)


def norm_params(cfg, lead=()):
    d = cfg["d_model"]
    if cfg["norm_type"] == "rmsnorm":
        return {"scale": jnp.zeros(lead + (d,), F32)}
    return {"scale": jnp.ones(lead + (d,), F32),
            "bias": jnp.zeros(lead + (d,), F32)}


def attn_params(cfg, key, lead, dtype):
    d, hd = cfg["d_model"], cfg["head_dim"]
    qd, kvd = cfg["num_heads"] * hd, cfg["num_kv_heads"] * hd
    k = jax.random.split(key, 4)
    p = {"wq": normal(k[0], lead + (d, qd), d ** -0.5, dtype),
         "wk": normal(k[1], lead + (d, kvd), d ** -0.5, dtype),
         "wv": normal(k[2], lead + (d, kvd), d ** -0.5, dtype),
         "wo": normal(k[3], lead + (qd, d), qd ** -0.5, dtype)}
    if cfg.get("qkv_bias", False):
        p.update(bq=jnp.zeros(lead + (qd,), dtype),
                 bk=jnp.zeros(lead + (kvd,), dtype),
                 bv=jnp.zeros(lead + (kvd,), dtype))
    return p


def mlp_params(cfg, key, lead, dtype):
    d, ff = cfg["d_model"], cfg["d_ff"]
    k1, k2 = jax.random.split(key)
    if cfg["mlp_type"] == "swiglu":
        # gate and up side by side on the output axis
        return {"wi": normal(k1, lead + (d, 2 * ff), (2 / d) ** 0.5, dtype),
                "wo": normal(k2, lead + (ff, d), (2 / ff) ** 0.5, dtype)}
    return {"wi": normal(k1, lead + (d, ff), (2 / d) ** 0.5, dtype),
            "bi": jnp.zeros(lead + (ff,), dtype),
            "wo": normal(k2, lead + (ff, d), (2 / ff) ** 0.5, dtype),
            "bo": jnp.zeros(lead + (d,), dtype)}


def embed_params(cfg, key, dtype):
    V, d = cfg["vocab_size"], cfg["d_model"]
    p = {"tok": normal(key, (V, d), 0.02, dtype)}
    if not cfg.get("tie_embeddings", False):
        p["unembed"] = normal(jax.random.fold_in(key, 1), (d, pad128(V)),
                              0.02, dtype)
    if cfg["pos_type"] == "learned":
        p["pos"] = normal(jax.random.fold_in(key, 2), (cfg["max_position"], d),
                          0.02, dtype)
    return p


def embed(cfg, p, tokens):
    """Token embeddings plus learned or sinusoidal positions (rotary
    positions are applied inside attention)."""
    x = p["tok"][tokens]
    S = tokens.shape[1]
    if cfg["pos_type"] == "learned":
        return x + p["pos"][:S]
    if cfg["pos_type"] == "sinusoidal":
        return x + sinusoid(S, cfg["d_model"])
    return x


# ------------------------------------------------------------ forward ----
def norm(cfg, p, x):
    eps = cfg.get("norm_eps", 1e-5)
    if cfg["norm_type"] == "rmsnorm":
        ms = jnp.mean(x * x, axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(ms + eps) * (1.0 + p["scale"])
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def gelu_tanh(x):
    """GELU in its tanh form, as the system computes it (the published
    Whisper uses the erf form; the two differ by under 1e-3)."""
    return 0.5 * x * (1.0 + jnp.tanh((2 / jnp.pi) ** 0.5
                                     * (x + 0.044715 * x ** 3)))


def mlp(cfg, p, x, ein):
    f = lambda a, w: ein("bsd,df->bsf", a, w)
    if cfg["mlp_type"] == "swiglu":
        gate, up = jnp.split(f(x, p["wi"]), 2, axis=-1)
        return f(jax.nn.silu(gate) * up, p["wo"])
    h = gelu_tanh(f(x, p["wi"]) + p["bi"].astype(F32))
    return f(h, p["wo"]) + p["bo"].astype(F32)


def sinusoid(n: int, dim: int):
    half = dim // 2
    freqs = jnp.exp(-jnp.log(10000.0) * jnp.arange(half) / max(half - 1, 1))
    ang = jnp.arange(n, dtype=F32)[:, None] * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def rope(x, theta: float):
    """Rotate (B, S, H, hd) by position, halves convention (rotate_half)."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv
    sin, cos = jnp.sin(ang)[:, None], jnp.cos(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(cfg, p, x, ein, *, causal: bool, memory=None):
    """Multi-head attention with grouped key/value heads; ``memory`` gives
    cross-attention (keys and values from the encoder, no mask)."""
    B, S, _ = x.shape
    H, K, hd = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    src = x if memory is None else memory
    proj = lambda a, w, b: ein("bsd,de->bse", a, p[w]) + p.get(b, 0.0)
    q = proj(x, "wq", "bq").reshape(B, S, H, hd)
    k = proj(src, "wk", "bk").reshape(B, src.shape[1], K, hd)
    v = proj(src, "wv", "bv").reshape(B, src.shape[1], K, hd)
    if cfg["pos_type"] == "rope" and memory is None:
        q, k = rope(q, cfg["rope_theta"]), rope(k, cfg["rope_theta"])
    k, v = jnp.repeat(k, H // K, axis=2), jnp.repeat(v, H // K, axis=2)
    s = ein("bqhd,bkhd->bhqk", q, k) / hd ** 0.5
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    o = ein("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    return ein("bse,ed->bsd", o.reshape(B, S, H * hd), p["wo"])


def xent(cfg, p, x, tokens, ein):
    """Mean next-token cross-entropy over the true vocabulary."""
    V = cfg["vocab_size"]
    w = p["tok"].T if cfg.get("tie_embeddings", False) else p["unembed"][:, :V]
    logits = ein("bsd,dv->bsv", x[:, :-1], w)
    gold = jnp.take_along_axis(logits, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - gold)


def over_layers(block, x, stacked):
    """``x = block(x, layer)`` for each layer of ``stacked`` (parameters
    with a leading layer axis), in order."""
    return jax.lax.scan(lambda h, lp: (block(h, lp), None), x, stacked)[0]


def f32(tree):
    return jax.tree.map(lambda a: a.astype(F32), tree)
