"""Mixture-of-Experts FFN (Mixtral / OLMoE style): softmax top-k router,
SwiGLU experts, load-balancing auxiliary loss.

Compute modes (DESIGN.md §4):

* ``dense``   — every token runs EVERY expert, gated by the (renormalised)
  top-k weights.  Simple, dropless, collective-free — but wastes
  (E/k)x FLOPs.  Baseline mode.
* ``dispatch`` — GShard/Switch-style capacity-based dispatch: tokens are
  scatter/gathered to per-expert buffers of capacity
  ``ceil(k * S / E * capacity_factor)`` via one-hot einsums; overflow tokens
  drop to the residual path.  Active-FLOPs-proportional compute; lowers to
  all-to-all under expert sharding.
* ``sorted`` / ``sorted_local`` — the same capacity and drops, with the
  assignments sorted by expert into an (E, capacity, d) buffer.
* ``dropless`` — the chip's share of an expert-parallel layer (OLMoE): the
  router scores all ``router_experts``, this chip holds ``num_experts`` of
  them from ``expert_offset`` on, and every assignment to a held expert is
  computed, whatever the skew, by two grouped matmuls
  (`jax.lax.ragged_dot`).  What absent experts would add is left out.  The
  load-balancing loss is formed over all layers at once (`layer_aux`), and
  the layer counts its work (`STATS`).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import dtype_of


# what the dropless layer counts, summed over layers: the assignments that
# fall on the held experts, the busiest held expert's rows, the held
# assignments over the experts held, and the forward FLOPs the grouped
# matmuls owe (6 d ff a held assignment)
STATS = ("moe_assignments_held", "moe_load_max", "moe_load_mean",
         "moe_expert_flops")


def counts_stats(cfg: ModelConfig) -> bool:
    return cfg.family == "moe" and cfg.moe_mode == "dropless"


def moe_init(cfg: ModelConfig, rng, shape_prefix=()):
    d, ff, E = cfg.d_model, cfg.d_ff, cfg.num_experts
    dt = dtype_of(cfg)
    k1, k2, k3 = jax.random.split(rng, 3)
    return {
        "router": (jax.random.normal(k1, shape_prefix + (d, cfg.router_width))
                   * (1 / d) ** 0.5).astype(jnp.float32),
        "wi": (jax.random.normal(k2, shape_prefix + (E, d, 2 * ff)) * (2 / d) ** 0.5
               ).astype(dt),
        "wo": (jax.random.normal(k3, shape_prefix + (E, ff, d)) * (2 / ff) ** 0.5
               ).astype(dt),
    }


def _router(cfg: ModelConfig, p, x):
    """float32 router over all ``router_experts`` -> (top-k weights
    (B,S,k), top-k expert ids (B,S,k), probabilities (B,S,R)); the weights
    are renormalised over the top k only where ``moe_norm_topk``."""
    logits = x.astype(jnp.float32) @ p["router"]            # (B,S,R)
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, cfg.experts_per_token)
    if cfg.moe_norm_topk:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return w, idx, probs


def _route(cfg: ModelConfig, p, x):
    """Router logits -> (topk weights (B,S,k), topk idx (B,S,k), aux loss)."""
    E = cfg.num_experts
    w, idx, probs = _router(cfg, p, x)
    # Switch-style load-balance loss: E * sum_e f_e * P_e
    onehot = jax.nn.one_hot(idx, E, dtype=jnp.float32)      # (B,S,k,E)
    f = jnp.mean(jnp.sum(onehot, axis=-2), axis=(0, 1))     # fraction routed per e
    P = jnp.mean(probs, axis=(0, 1))
    aux = E * jnp.sum(f * P)
    return w, idx, aux


def apply_moe(cfg: ModelConfig, p, x):
    """x: (B, S, d) -> (out, aux): the layer's load-balancing loss, or in
    the dropless mode what `layer_aux` forms it from."""
    if cfg.moe_mode == "dropless":
        return _apply_dropless(cfg, p, x)
    if cfg.router_width != cfg.num_experts:
        raise ValueError(f"moe_mode {cfg.moe_mode!r} holds every expert the "
                         f"router scores; a share of them needs 'dropless'")
    if cfg.moe_mode == "dispatch":
        return _apply_dispatch(cfg, p, x)
    if cfg.moe_mode == "sorted":
        return _apply_sorted(cfg, p, x)
    if cfg.moe_mode == "sorted_local":
        # locality-aware: dispatch within each batch row (rows are sharded
        # over the data axes, so sort/gather never crosses devices)
        y, aux = jax.vmap(lambda xr: _apply_sorted(cfg, p, xr[None]))(x)
        return y[:, 0], jnp.mean(aux)
    return _apply_dense(cfg, p, x)


def _grouped(a, w, sizes, live):
    """`jax.lax.ragged_dot` of the rows of ``a`` in groups ``sizes``
    against ``w`` (groups, K, N).  The rows past the last group are not
    defined by it (on the TPU they hold whatever the buffer held, in the
    product and in its gradient), so the ``live`` rows are selected on
    the way in and on the way out."""
    a = jnp.where(live[:, None], a, 0)
    return jnp.where(live[:, None], jax.lax.ragged_dot(a, w, sizes), 0)


@jax.named_scope("mlp")
def _apply_dropless(cfg: ModelConfig, p, x):
    """The held experts' part of the layer for every token, none dropped.

    The N*k assignments are sorted by local expert id, those of absent
    experts past the last group; each held expert's rows are one group of a
    static (N*k, d) buffer, and two grouped matmuls (`_grouped`) run the
    groups against the held experts' weights.  The rows come back to
    (N, k, d), each weighted by its router probability (zero for an absent
    expert), and are summed over k in float32.  Returns the output and, for
    `layer_aux`, the layer's routing shares (k, R) and mean probabilities
    (R,) with its `STATS`."""
    B, S, d = x.shape
    E, k, R = cfg.num_experts, cfg.experts_per_token, cfg.router_width
    N = B * S
    with jax.named_scope("router"):
        w, idx, probs = _router(cfg, p, x)
    with jax.named_scope("dispatch"):
        local = idx.reshape(N * k) - cfg.expert_offset
        held = (local >= 0) & (local < E)
        group = jnp.where(held, local, E)             # absent experts last
        order = jnp.argsort(group, stable=True)
        sizes = jnp.bincount(group, length=E + 1)[:E].astype(jnp.int32)
        live = jnp.arange(N * k) < jnp.sum(sizes)
        rows = x.reshape(N, d)[order // k]
    with jax.named_scope("experts"):
        gate, up = jnp.split(_grouped(rows, p["wi"], sizes, live), 2, axis=-1)
        y = _grouped(jax.nn.silu(gate) * up, p["wo"], sizes, live)
    with jax.named_scope("combine"):
        back = jnp.zeros_like(order).at[order].set(
            jnp.arange(N * k, dtype=order.dtype))
        gates = jnp.where(held, w.reshape(N * k), 0.0)
        out = jnp.sum((y[back].astype(jnp.float32) * gates[:, None])
                      .reshape(N, k, d), axis=1)
    n_held = jnp.sum(sizes).astype(jnp.float32)
    aux = {"route_frac": jnp.mean(jax.nn.one_hot(idx, R, dtype=jnp.float32),
                                  axis=(0, 1)),
           "route_prob": jnp.mean(probs, axis=(0, 1)),
           "moe_assignments_held": n_held,
           "moe_load_max": jnp.max(sizes).astype(jnp.float32),
           "moe_load_mean": n_held / E,
           "moe_expert_flops": n_held * (6 * d * cfg.d_ff)}
    return out.reshape(B, S, d).astype(x.dtype), aux


def layer_aux(cfg: ModelConfig, auxs):
    """The layers' stacked ``aux`` outputs -> (load-balancing loss, stats).

    Dropless: OLMoE's loss as HF ``load_balancing_loss_func`` computes it,
    over all layers' tokens together from the full router: R * sum over
    (k, R) of the routing shares times the mean probabilities; the stats
    are `STATS` summed over layers.  Other modes: the layers' losses
    summed, no stats."""
    if not counts_stats(cfg):
        return jnp.sum(auxs), {}
    frac = jnp.mean(auxs["route_frac"], axis=0)               # (k, R)
    prob = jnp.mean(auxs["route_prob"], axis=0)               # (R,)
    loss = cfg.router_width * jnp.sum(frac * prob[None])
    return loss, {s: jnp.sum(auxs[s]) for s in STATS}


def _apply_dense(cfg: ModelConfig, p, x):
    w, idx, aux = _route(cfg, p, x)
    E = cfg.num_experts
    onehot = jax.nn.one_hot(idx, E, dtype=jnp.float32)       # (B,S,k,E)
    gates = jnp.einsum("bske,bsk->bse", onehot, w)           # (B,S,E)
    h = jnp.einsum("bsd,edf->bsef", x, p["wi"])              # every expert
    gate, up = jnp.split(h, 2, axis=-1)
    h = jax.nn.silu(gate) * up
    out = jnp.einsum("bsef,efd->bsed", h, p["wo"])
    out = jnp.einsum("bsed,bse->bsd", out.astype(jnp.float32), gates)
    return out.astype(x.dtype), aux


def _apply_sorted(cfg: ModelConfig, p, x):
    """Sort-based capacity dispatch (the hillclimbed mode, §Perf).

    Unlike the GShard one-hot einsum (which materialises a (B,S,E,cap)
    dispatch tensor — quadratic-ish in sequence at 4k+), this flattens tokens,
    argsorts (token, expert) assignments by expert, gathers the first ``cap``
    per expert into an (E, cap, d) buffer, runs E batched expert matmuls
    (MXU-friendly), and scatter-adds back with the gate weights.  Memory is
    O(N*k*d); FLOPs are proportional to ACTIVE params (top-k), not total.
    Overflow tokens beyond capacity fall through on the residual path
    (standard token dropping).
    """
    B, S, d = x.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    N = B * S
    cap = int(max(1, round(k * N / E * cfg.capacity_factor)))
    w, idx, aux = _route(cfg, p, x)

    xf = x.reshape(N, d)
    ef = idx.reshape(N * k)                       # expert of each assignment
    wf = w.reshape(N * k).astype(jnp.float32)
    tok = jnp.repeat(jnp.arange(N, dtype=jnp.int32), k)

    order = jnp.argsort(ef)                       # group assignments by expert
    sorted_e = ef[order]
    starts = jnp.searchsorted(sorted_e, jnp.arange(E, dtype=sorted_e.dtype))
    pos = jnp.arange(N * k, dtype=jnp.int32) - starts[sorted_e]
    keep = pos < cap
    dest = jnp.where(keep, sorted_e * cap + pos, E * cap)  # E*cap = drop slot

    buf = jnp.zeros((E * cap + 1, d), x.dtype)
    buf = buf.at[dest].set(xf[tok[order]])
    h = jnp.einsum("ecd,edf->ecf", buf[:-1].reshape(E, cap, d), p["wi"])
    gate, up = jnp.split(h, 2, axis=-1)
    h = jax.nn.silu(gate) * up
    y = jnp.einsum("ecf,efd->ecd", h, p["wo"])    # (E, cap, d)
    y = jnp.concatenate([y.reshape(E * cap, d),
                         jnp.zeros((1, d), y.dtype)])
    out = jnp.zeros((N, d), jnp.float32)
    out = out.at[tok[order]].add(
        y[jnp.where(keep, dest, E * cap)].astype(jnp.float32)
        * (wf[order] * keep)[:, None])
    return out.reshape(B, S, d).astype(x.dtype), aux


def _apply_dispatch(cfg: ModelConfig, p, x):
    """Capacity-based dispatch (GShard).  Per batch row to bound buffer size."""
    B, S, d = x.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    C = int(max(1, round(k * S / E * cfg.capacity_factor)))
    w, idx, aux = _route(cfg, p, x)

    onehot = jax.nn.one_hot(idx, E, dtype=jnp.float32)       # (B,S,k,E)
    # position of each (token, slot) within its expert's buffer
    pos_in_e = jnp.cumsum(onehot.reshape(B, S * k, E), axis=1).reshape(B, S, k, E) - 1.0
    keep = (pos_in_e < C) * onehot                           # drop overflow
    combine = keep * w[..., None]                            # (B,S,k,E)
    pos_oh = jax.nn.one_hot(pos_in_e.astype(jnp.int32), C, dtype=jnp.float32)
    dispatch = (keep[..., None] * pos_oh).sum(axis=2)        # (B,S,E,C)
    combine_w = (combine[..., None] * pos_oh).sum(axis=2)    # (B,S,E,C)

    xb = jnp.einsum("bsec,bsd->becd", dispatch.astype(x.dtype), x)  # (B,E,C,d)
    h = jnp.einsum("becd,edf->becf", xb, p["wi"])
    gate, up = jnp.split(h, 2, axis=-1)
    h = jax.nn.silu(gate) * up
    yb = jnp.einsum("becf,efd->becd", h, p["wo"])            # (B,E,C,d)
    out = jnp.einsum("bsec,becd->bsd", combine_w.astype(jnp.float32),
                     yb.astype(jnp.float32))
    return out.astype(x.dtype), aux
