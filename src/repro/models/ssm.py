"""Mamba2 (state-space duality / SSD, arXiv:2405.21060), attention-free stack.

Block: in_proj -> [z | x | B | C | dt], short causal depthwise conv over
(x,B,C), selective SSM with scalar-per-head decay A, gated RMSNorm, out_proj.

The SSD scan is implemented in the *chunked* form (intra-chunk quadratic dual
+ inter-chunk state recurrence) — the TPU-friendly formulation (MXU matmuls
within a chunk, short scan across chunks).  ``repro.kernels.ssd_scan`` holds
the Pallas version; this module's jnp implementation is also its oracle's
basis.  Decode is the O(1)-state recurrent step.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L


# --------------------------------------------------------------- params ----
def mixer_init(cfg: ModelConfig, rng):
    d = cfg.d_model
    din = cfg.ssm_inner
    H, st, G, K = cfg.ssm_heads, cfg.ssm_state, cfg.ssm_groups, cfg.ssm_conv
    dt = L.dtype_of(cfg)
    conv_ch = din + 2 * G * st
    ks = jax.random.split(rng, 4)
    proj_out = 2 * din + 2 * G * st + H
    return {
        "in_proj": (jax.random.normal(ks[0], (d, proj_out)) * (1 / d) ** 0.5).astype(dt),
        "conv_w": (jax.random.normal(ks[1], (conv_ch, K)) * (1 / K) ** 0.5).astype(dt),
        "conv_b": jnp.zeros((conv_ch,), dt),
        "A_log": jnp.zeros((H,), jnp.float32),             # A = -exp(A_log) = -1
        "D": jnp.ones((H,), jnp.float32),
        "dt_bias": jnp.full((H,), -2.0, jnp.float32),      # softplus(-2) ~ 0.13
        "norm": jnp.zeros((din,), jnp.float32),
        "out_proj": (jax.random.normal(ks[2], (din, d)) * (1 / din) ** 0.5).astype(dt),
    }


def init_params(cfg: ModelConfig, rng):
    k_embed, k_layers = jax.random.split(rng)

    def layer_init(key):
        return {"ln": L.norm_init(cfg), "mixer": mixer_init(cfg, key)}

    layers = jax.vmap(layer_init)(jax.random.split(k_layers, cfg.num_layers))
    return {"embed": L.embed_init(cfg, k_embed), "layers": layers,
            "ln_f": L.norm_init(cfg)}


# ------------------------------------------------------------- SSD core ----
def _causal_conv(x, w, b, state=None):
    """Depthwise causal conv.  x (B,S,C), w (C,K).  If ``state`` (B,K-1,C) is
    given (decode), prepends it; returns (out, new_state)."""
    K = w.shape[1]
    if state is None:
        pad = jnp.zeros(x.shape[:1] + (K - 1,) + x.shape[2:], x.dtype)
    else:
        pad = state.astype(x.dtype)
    xp = jnp.concatenate([pad, x], axis=1)                    # (B, S+K-1, C)
    out = sum(xp[:, i:i + x.shape[1]] * w[:, i] for i in range(K)) + b
    new_state = xp[:, -(K - 1):] if K > 1 else None
    return jax.nn.silu(out), new_state


def ssd_chunked(xh, dt, A, Bm, Cm, chunk: int, unroll: bool = False):
    """Chunked SSD.

    xh: (B,S,H,P) inputs per head;  dt: (B,S,H) softplus'd step sizes;
    A: (H,) negative decay rates;   Bm/Cm: (B,S,G,N) input/output maps.
    Returns y (B,S,H,P) and final state (B,H,P,N).
    """
    Bsz, S, H, P = xh.shape
    G, N = Bm.shape[2], Bm.shape[3]
    Q = chunk
    assert S % Q == 0, f"seq {S} not divisible by chunk {Q}"
    nC = S // Q
    rep = H // G

    # reshape to chunks
    c = lambda t: t.reshape(Bsz, nC, Q, *t.shape[2:])
    xh_, dt_, B_, C_ = c(xh), c(dt), c(Bm), c(Cm)
    Bh = jnp.repeat(B_, rep, axis=3)                          # (B,nC,Q,H,N)
    Ch = jnp.repeat(C_, rep, axis=3)

    dA = dt_ * A[None, None, None, :]                         # (B,nC,Q,H) log-decay
    cum = jnp.cumsum(dA, axis=2)                              # within-chunk cumulative

    # intra-chunk (dual/quadratic) term
    # M[t,s] = exp(cum[t]-cum[s]) for s<=t, causal
    diff = cum[:, :, :, None, :] - cum[:, :, None, :, :]      # (B,nC,Q,Q,H)
    mask = jnp.tril(jnp.ones((Q, Q), bool))
    decay = jnp.where(mask[None, None, :, :, None], jnp.exp(diff), 0.0)
    CB = jnp.einsum("bcqhn,bcshn->bcqsh", Ch.astype(jnp.float32),
                    Bh.astype(jnp.float32))                   # (B,nC,Q,Q,H)
    M = CB * decay * dt_[:, :, None, :, :]                    # weight input by dt_s
    y_intra = jnp.einsum("bcqsh,bcshp->bcqhp", M, xh_.astype(jnp.float32))

    # chunk-final states: sum_s exp(cum_end - cum_s) dt_s B_s x_s^T
    seg = jnp.exp(cum[:, :, -1:, :] - cum)                    # (B,nC,Q,H)
    dBx = jnp.einsum("bcqh,bcqhn,bcqhp->bchpn",
                     (seg * dt_).astype(jnp.float32),
                     Bh.astype(jnp.float32), xh_.astype(jnp.float32))

    # inter-chunk recurrence over chunk states
    chunk_decay = jnp.exp(jnp.sum(dA, axis=2))                # (B,nC,H)

    def scan_fn(h, xs):
        cd, s = xs                                            # cd (B,H), s (B,H,P,N)
        h_new = h * cd[:, :, None, None] + s
        return h_new, h                                       # emit state BEFORE chunk

    h0 = jnp.zeros((Bsz, H, P, N), jnp.float32)
    hT, h_prevs = jax.lax.scan(
        scan_fn, h0,
        (jnp.moveaxis(chunk_decay, 1, 0), jnp.moveaxis(dBx, 1, 0)),
        unroll=bool(unroll))
    h_prevs = jnp.moveaxis(h_prevs, 0, 1)                     # (B,nC,H,P,N)

    # inter-chunk contribution: C_t . (exp(cum_t) * h_prev)
    y_inter = jnp.einsum("bcqhn,bchpn,bcqh->bcqhp",
                         Ch.astype(jnp.float32), h_prevs, jnp.exp(cum))
    y = (y_intra + y_inter).reshape(Bsz, S, H, P)
    return y, hT


def ssd_sequential(xh, dt, A, Bm, Cm, h0=None):
    """Naive per-step recurrence (oracle + decode).  Same shapes as above."""
    Bsz, S, H, P = xh.shape
    G, N = Bm.shape[2], Bm.shape[3]
    rep = H // G
    Bh = jnp.repeat(Bm, rep, axis=2).astype(jnp.float32)
    Ch = jnp.repeat(Cm, rep, axis=2).astype(jnp.float32)
    h = jnp.zeros((Bsz, H, P, N), jnp.float32) if h0 is None else h0

    def step(h, xs):
        x_t, dt_t, B_t, C_t = xs                              # (B,H,P),(B,H),(B,H,N),(B,H,N)
        a = jnp.exp(dt_t * A[None])                           # (B,H)
        h = h * a[:, :, None, None] \
            + jnp.einsum("bh,bhn,bhp->bhpn", dt_t, B_t, x_t.astype(jnp.float32))
        y = jnp.einsum("bhn,bhpn->bhp", C_t, h)
        return h, y

    xs = (jnp.moveaxis(xh, 1, 0), jnp.moveaxis(dt, 1, 0),
          jnp.moveaxis(Bh, 1, 0), jnp.moveaxis(Ch, 1, 0))
    h, ys = jax.lax.scan(step, h, xs)
    return jnp.moveaxis(ys, 0, 1), h


# ---------------------------------------------------------------- block ----
def _mixer_apply(cfg: ModelConfig, p, x, conv_state=None, ssm_state=None,
                 mode: str = "chunked"):
    """x (B,S,d) -> (y (B,S,d), (conv_state, ssm_state))."""
    Bsz, S, _ = x.shape
    din, H, st, G = cfg.ssm_inner, cfg.ssm_heads, cfg.ssm_state, cfg.ssm_groups
    P = cfg.ssm_head_dim

    proj = x @ p["in_proj"]
    # layout: [z (din) | xBC (din + 2G*st) | dt (H)]
    z = proj[..., :din]
    xbc = proj[..., din:din + din + 2 * G * st]
    dt_raw = proj[..., din + din + 2 * G * st:]

    xbc, new_conv = _causal_conv(xbc, p["conv_w"], p["conv_b"], conv_state)
    xh = xbc[..., :din].reshape(Bsz, S, H, P)
    Bm = xbc[..., din:din + G * st].reshape(Bsz, S, G, st)
    Cm = xbc[..., din + G * st:].reshape(Bsz, S, G, st)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])

    if mode == "chunked" and S % cfg.ssm_chunk == 0 and S > 1:
        y, h = ssd_chunked(xh, dt, A, Bm, Cm, cfg.ssm_chunk,
                           unroll=cfg.scan_unroll)
    else:
        y, h = ssd_sequential(xh, dt, A, Bm, Cm, ssm_state)
    y = y + p["D"][None, None, :, None] * xh.astype(jnp.float32)
    y = y.reshape(Bsz, S, din)
    # gated RMSNorm (mamba2): norm(y * silu(z))
    y = L.rmsnorm(y * jax.nn.silu(z.astype(jnp.float32)), p["norm"], cfg.norm_eps)
    return y.astype(x.dtype) @ p["out_proj"], (new_conv, h)


def forward(cfg: ModelConfig, params, batch, impl: str = "ref",
            padded_logits: bool = False):
    tokens = batch["tokens"]
    x = L.embed_tokens(cfg, params["embed"], tokens)

    def body(p, h):
        y, _ = _mixer_apply(cfg, p["mixer"], L.apply_norm(cfg, p["ln"], h))
        return h + y

    if cfg.remat:
        body = jax.checkpoint(body)

    def scan_fn(h, layer_p):
        return body(layer_p, h), None

    x, _ = jax.lax.scan(scan_fn, x, params["layers"],
                        unroll=bool(cfg.scan_unroll))
    x = L.apply_norm(cfg, params["ln_f"], x)
    return L.unembed(cfg, params["embed"], x, padded=padded_logits), jnp.float32(0)


def loss_fn(cfg: ModelConfig, params, batch, rng=None, impl: str = "ref"):
    logits, _ = forward(cfg, params, batch, impl=impl, padded_logits=True)
    return L.softmax_xent(logits[:, :-1], batch["tokens"][:, 1:],
                          valid_vocab=cfg.vocab_size), {}


# ------------------------------------------------------------- serving -----
def init_cache(cfg: ModelConfig, batch: int, cache_len: int = 0):
    """SSM cache is O(1) in sequence length: conv tail + state per layer."""
    din, H, st, G = cfg.ssm_inner, cfg.ssm_heads, cfg.ssm_state, cfg.ssm_groups
    conv_ch = din + 2 * G * st
    K = cfg.ssm_conv
    nl = cfg.num_layers
    return {
        "conv": jnp.zeros((nl, batch, K - 1, conv_ch), L.dtype_of(cfg)),
        "ssm": jnp.zeros((nl, batch, H, cfg.ssm_head_dim, st), jnp.float32),
    }


def prefill(cfg: ModelConfig, params, batch, cache_len=None, impl="ref",
            window=None):
    tokens = batch["tokens"]
    x = L.embed_tokens(cfg, params["embed"], tokens)

    def scan_fn(h, layer_p):
        y, (conv_s, ssm_s) = _mixer_apply(
            cfg, layer_p["mixer"], L.apply_norm(cfg, layer_p["ln"], h))
        return h + y, (conv_s, ssm_s)

    x, (convs, ssms) = jax.lax.scan(scan_fn, x, params["layers"],
                                    unroll=bool(cfg.scan_unroll))
    x = L.apply_norm(cfg, params["ln_f"], x)
    logits = L.unembed(cfg, params["embed"], x[:, -1:])
    return logits, {"conv": convs, "ssm": ssms}


def decode_step(cfg: ModelConfig, params, token, cache, pos, *, ring=False,
                window=None, impl="ref"):
    x = L.embed_tokens(cfg, params["embed"], token[:, None])

    def scan_fn(h, xs):
        layer_p, conv_s, ssm_s = xs
        y, (new_conv, new_ssm) = _mixer_apply(
            cfg, layer_p["mixer"], L.apply_norm(cfg, layer_p["ln"], h),
            conv_state=conv_s, ssm_state=ssm_s, mode="sequential")
        return h + y, (new_conv, new_ssm)

    x, (convs, ssms) = jax.lax.scan(
        scan_fn, x, (params["layers"], cache["conv"], cache["ssm"]),
        unroll=bool(cfg.scan_unroll))
    x = L.apply_norm(cfg, params["ln_f"], x)
    logits = L.unembed(cfg, params["embed"], x)[:, 0]
    return logits, {"conv": convs, "ssm": ssms}
