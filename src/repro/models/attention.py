"""Attention: GQA/MHA with RoPE, causal + sliding-window masks, KV caches.

Shapes: q (B, S, H, hd), k/v (B, S, K, hd) with H % K == 0 (GQA groups).
Caches:
* full cache  — (B, max_len, K, hd) written at absolute positions (decode_32k);
* ring cache  — (B, W, K, hd) written at ``pos mod W`` (sliding-window archs and
  the long-context serving variant; makes 500k-token decode O(W) memory).

``impl="flash"`` routes the training/prefill path through the Pallas kernel
(`repro.kernels.ops.flash_attention`); default "ref" is the pure-jnp path used
on CPU and as the oracle.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import apply_rope, dtype_of, rmsnorm

NEG_INF = -1e30


def attn_init(cfg: ModelConfig, rng, shape_prefix=(), cross: bool = False):
    d, qd, kvd = cfg.d_model, cfg.q_dim, cfg.kv_dim
    dt = dtype_of(cfg)
    ks = jax.random.split(rng, 4)
    s = (1.0 / d) ** 0.5
    p = {
        "wq": (jax.random.normal(ks[0], shape_prefix + (d, qd)) * s).astype(dt),
        "wk": (jax.random.normal(ks[1], shape_prefix + (d, kvd)) * s).astype(dt),
        "wv": (jax.random.normal(ks[2], shape_prefix + (d, kvd)) * s).astype(dt),
        "wo": (jax.random.normal(ks[3], shape_prefix + (qd, d)) * (1.0 / qd) ** 0.5).astype(dt),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros(shape_prefix + (qd,), dt)
        p["bk"] = jnp.zeros(shape_prefix + (kvd,), dt)
        p["bv"] = jnp.zeros(shape_prefix + (kvd,), dt)
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros(shape_prefix + (qd,), jnp.float32)
        p["k_norm"] = jnp.zeros(shape_prefix + (kvd,), jnp.float32)
    return p


def _split_heads(x, n_heads, head_dim):
    return x.reshape(x.shape[:-1] + (n_heads, head_dim))


def _project(cfg: ModelConfig, p, x, src):
    """q from ``x``, k and v from ``src``, split into heads; with
    ``qk_norm`` the full q and k projections are RMS-normalised first
    (OLMoE), before any rotary positions."""
    q = x @ p["wq"] + p.get("bq", 0)
    k = src @ p["wk"] + p.get("bk", 0)
    v = src @ p["wv"] + p.get("bv", 0)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
    return (_split_heads(q, cfg.num_heads, cfg.head_dim),
            _split_heads(k, cfg.num_kv_heads, cfg.head_dim),
            _split_heads(v, cfg.num_kv_heads, cfg.head_dim))


def repeat_kv(k, num_heads):
    """(B, S, K, hd) -> (B, S, H, hd) by repeating each kv head H/K times."""
    K = k.shape[-2]
    if K == num_heads:
        return k
    return jnp.repeat(k, num_heads // K, axis=-2)


def dot_product_attention(q, k, v, *, causal: bool, window: int = 0,
                          q_positions=None, kv_positions=None, bias_mask=None):
    """Reference attention. q (B,Sq,H,hd), k/v (B,Skv,H,hd) (already GQA-repeated).

    ``q_positions``/``kv_positions`` are absolute positions used for the causal
    and sliding-window masks (needed for decode where Sq=1 at position p).
    """
    B, Sq, H, hd = q.shape
    Skv = k.shape[1]
    if q_positions is None:
        q_positions = jnp.arange(Sq)
    if kv_positions is None:
        kv_positions = jnp.arange(Skv)
    scale = 1.0 / (hd ** 0.5)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    mask = jnp.ones((Sq, Skv), bool)
    if causal:
        mask &= q_positions[:, None] >= kv_positions[None, :]
    if window and window > 0:
        mask &= q_positions[:, None] - kv_positions[None, :] < window
    if bias_mask is not None:
        mask &= bias_mask
    logits = jnp.where(mask[None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


def blocked_attention(q, k, v, *, causal: bool, window: int = 0,
                      block_k: int = 2048, unroll: bool = False,
                      q_positions=None, kv_positions=None):
    """Flash-style online-softmax attention in pure jnp: lax.scan over KV
    blocks keeps the working set at (B,H,Sq,block_k) instead of materialising
    the full (B,H,Sq,Skv) score matrix — the XLA-level mirror of
    ``kernels/flash_attention`` (which does the same tiling in VMEM on TPU).

    q (B,Sq,H,D); k/v (B,Skv,H,D) GQA-repeated.  ``unroll`` unrolls the block
    scan (used by the dry-run cost calibration, like every other scan).
    """
    B, Sq, H, D = q.shape
    Skv = k.shape[1]
    block_k = min(block_k, Skv)
    assert Skv % block_k == 0, (Skv, block_k)
    nb = Skv // block_k
    scale = 1.0 / (D ** 0.5)
    if q_positions is None:
        q_positions = jnp.arange(Sq)
    if kv_positions is None:
        kv_positions = jnp.arange(Skv)

    # MXU-style numerics: bf16 operands, fp32 accumulation (halves the
    # dominant score/prob HBM traffic vs fp32 operands — §Perf iteration 2)
    qf = jnp.einsum("bqhd->bhqd", q)
    kb = jnp.moveaxis(k.reshape(B, nb, block_k, H, D), 1, 0)
    vb = jnp.moveaxis(v.reshape(B, nb, block_k, H, D), 1, 0)
    pb = kv_positions.reshape(nb, block_k)

    def body(carry, inp):
        m, l, acc = carry
        k_blk, v_blk, kpos = inp
        s = jnp.einsum("bhqd,bkhd->bhqk", qf, k_blk,
                       preferred_element_type=jnp.float32) * scale
        mask = jnp.ones((Sq, block_k), bool)
        if causal:
            mask &= q_positions[:, None] >= kpos[None, :]
        if window and window > 0:
            mask &= q_positions[:, None] - kpos[None, :] < window
        s = jnp.where(mask[None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p.astype(q.dtype), v_blk,
            preferred_element_type=jnp.float32)
        return (m_new, l, acc), None

    m0 = jnp.full((B, H, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, Sq), jnp.float32)
    a0 = jnp.zeros((B, H, Sq, D), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (kb, vb, pb),
                                  unroll=bool(unroll))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return jnp.einsum("bhqd->bqhd", out).astype(q.dtype)


@jax.named_scope("attention")
def attention(cfg: ModelConfig, p, x, *, positions=None, causal=True,
              window=None, memory=None, impl: str = "ref"):
    """Full attention over a sequence (training / encoder / cross-attention).

    memory: if given, keys/values come from ``memory`` (cross-attention,
    non-causal, no rope on memory side beyond what the encoder applied).
    """
    B, S, _ = x.shape
    win = cfg.sliding_window if window is None else window
    q, k, v = _project(cfg, p, x, x if memory is None else memory)
    if positions is None:
        positions = jnp.arange(S)
    if cfg.pos_type == "rope" and memory is None:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    if impl == "flash" and memory is None:
        from repro.kernels import ops as kops
        out = kops.flash_attention(q, repeat_kv(k, cfg.num_heads),
                                   repeat_kv(v, cfg.num_heads),
                                   causal=causal, window=win or 0)
    elif cfg.attn_blocked and memory is None:
        out = blocked_attention(
            q, repeat_kv(k, cfg.num_heads), repeat_kv(v, cfg.num_heads),
            causal=causal, window=win or 0, block_k=cfg.attn_block_k,
            unroll=cfg.scan_unroll, q_positions=positions)
    else:
        out = dot_product_attention(
            q, repeat_kv(k, cfg.num_heads), repeat_kv(v, cfg.num_heads),
            causal=causal and memory is None, window=win or 0,
            q_positions=positions if memory is None else None)
    out = out.reshape(B, S, cfg.q_dim)
    return out @ p["wo"], (k, v)


# ------------------------------------------------------------- caches ------
def make_kv_cache(batch, length, num_kv_heads, head_dim, dtype):
    z = jnp.zeros((batch, length, num_kv_heads, head_dim), dtype)
    return {"k": z, "v": z}


def cache_write(cache, k_new, v_new, pos, ring: bool):
    """Write (B, 1, K, hd) at absolute position ``pos`` (or pos mod W if ring)."""
    W = cache["k"].shape[1]
    idx = jnp.where(ring, pos % W, jnp.minimum(pos, W - 1)) if isinstance(pos, jax.Array) \
        else (pos % W if ring else min(pos, W - 1))
    k = jax.lax.dynamic_update_slice_in_dim(cache["k"], k_new.astype(cache["k"].dtype), idx, axis=1)
    v = jax.lax.dynamic_update_slice_in_dim(cache["v"], v_new.astype(cache["v"].dtype), idx, axis=1)
    return {"k": k, "v": v}


def decode_attention(cfg: ModelConfig, p, x, cache, pos, *, ring: bool,
                     window: int | None = None):
    """One-token attention against a KV cache.

    x: (B, 1, d); cache k/v: (B, L_cache, K, hd); pos: scalar absolute position.
    Returns (out (B,1,d), new_cache).
    """
    B = x.shape[0]
    win = cfg.sliding_window if window is None else window
    q, k1, v1 = _project(cfg, p, x, x)
    posv = jnp.full((1,), pos)
    if cfg.pos_type == "rope":
        q = apply_rope(q, posv, cfg.rope_theta)
        k1 = apply_rope(k1, posv, cfg.rope_theta)
    cache = cache_write(cache, k1, v1, pos, ring)
    L = cache["k"].shape[1]
    # absolute positions held in each cache slot
    if ring:
        slots = jnp.arange(L)
        wrap = (pos // L) * L
        kv_pos = jnp.where(slots <= pos % L, wrap + slots, wrap - L + slots)
    else:
        kv_pos = jnp.arange(L)
    k = repeat_kv(cache["k"], cfg.num_heads)
    v = repeat_kv(cache["v"], cfg.num_heads)
    valid = (kv_pos <= pos) & (kv_pos >= 0)  # >=0 excludes unwritten ring slots
    if win and win > 0:
        valid &= pos - kv_pos < win
    out = dot_product_attention(
        q, k, v, causal=False, window=0,
        q_positions=posv, kv_positions=kv_pos,
        bias_mask=valid[None, :])
    return out.reshape(B, 1, cfg.q_dim) @ p["wo"], cache
