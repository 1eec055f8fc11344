"""MoE compute modes: dense (baseline), GShard dispatch, sorted dispatch
(the hillclimbed mode) must agree when capacity admits every token; they
hold every expert the router scores."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import get_model
from repro.models import moe as moe_mod


@pytest.fixture(scope="module")
def setup():
    cfg = dataclasses.replace(get_smoke_config("olmoe-1b-7b"),
                              router_experts=0, moe_mode="dense")
    key = jax.random.PRNGKey(0)
    p = moe_mod.moe_init(cfg, key)
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, 16, cfg.d_model)) * 0.5
    return cfg, p, x


@pytest.mark.parametrize("mode", ["dispatch", "sorted"])
def test_modes_match_dense_at_full_capacity(setup, mode):
    cfg, p, x = setup
    big = dataclasses.replace(cfg, capacity_factor=float(cfg.num_experts),
                              moe_mode=mode)
    y_dense, aux_d = moe_mod._apply_dense(cfg, p, x)
    y_mode, aux_m = moe_mod.apply_moe(big, p, x)
    np.testing.assert_allclose(np.asarray(y_mode), np.asarray(y_dense),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(float(aux_m), float(aux_d), rtol=1e-5)


@pytest.mark.parametrize("mode", ["dispatch", "sorted"])
def test_capacity_drops_are_bounded(setup, mode):
    """At capacity_factor=1.0 some tokens drop; output stays finite and close
    to dense in aggregate (drops fall back to the residual path)."""
    cfg, p, x = setup
    tight = dataclasses.replace(cfg, capacity_factor=1.0, moe_mode=mode)
    y, _ = moe_mod.apply_moe(tight, p, x)
    assert np.isfinite(np.asarray(y)).all()
    y_dense, _ = moe_mod._apply_dense(cfg, p, x)
    # most tokens unaffected: median abs deviation small
    dev = np.abs(np.asarray(y, np.float32) - np.asarray(y_dense, np.float32))
    assert np.median(dev) < 0.15


def test_sorted_mode_trains(setup):
    cfg, p, x = setup
    scfg = dataclasses.replace(cfg, moe_mode="sorted")
    model = get_model(scfg)
    params = model.init_params(jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                          scfg.vocab_size)}
    (loss, _), g = jax.value_and_grad(lambda q: model.loss_fn(q, batch),
                                      has_aux=True)(params)
    assert np.isfinite(float(loss))
    gn = sum(float(jnp.sum(jnp.square(t))) for t in jax.tree.leaves(g))
    assert np.isfinite(gn) and gn > 0


# ---------------------------------------------------------- dropless ----
def _share(router=8, held=4, offset=0, norm=False, k=2):
    """OLMoE's smoke layer as a chip's share: ``held`` of ``router``."""
    return dataclasses.replace(
        get_smoke_config("olmoe-1b-7b"), router_experts=router,
        num_experts=held, expert_offset=offset, experts_per_token=k,
        moe_norm_topk=norm, moe_mode="dropless")


def _per_expert_loop(cfg, p, x):
    """Each held expert on every token, times its router weight (0 where
    it is not among the token's top k)."""
    probs = jax.nn.softmax(x.astype(jnp.float32) @ p["router"], axis=-1)
    w, idx = jax.lax.top_k(probs, cfg.experts_per_token)
    if cfg.moe_norm_topk:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    out = jnp.zeros(x.shape, jnp.float32)
    for e in range(cfg.num_experts):
        g = jnp.sum(jnp.where(idx == cfg.expert_offset + e, w, 0.0), axis=-1)
        gate, up = jnp.split(x @ p["wi"][e], 2, axis=-1)
        out = out + ((jax.nn.silu(gate) * up) @ p["wo"][e]) * g[..., None]
    return out


def _layer_inputs(cfg, skew: bool):
    key = jax.random.PRNGKey(3)
    p = moe_mod.moe_init(cfg, key)
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, 16, cfg.d_model))
    if skew:   # expert 0 in every token's top k
        p["router"] = p["router"].at[:, 0].set(0.0).at[0, 0].set(50.0)
        x = x.at[..., 0].set(jnp.abs(x[..., 0]) + 1.0)
    return p, x


def _nan_past_groups(monkeypatch):
    """`jax.lax.ragged_dot` whose rows past the last group are NaN, in the
    product and in the gradient of its rows, as the TPU leaves them
    undefined (the CPU's decomposition zeroes them)."""
    real = jax.lax.ragged_dot

    def poison(y, sizes):
        live = jnp.arange(y.shape[0]) < jnp.sum(sizes)
        return jnp.where(live[:, None], y, jnp.nan)

    @jax.custom_vjp
    def ragged_dot(a, w, sizes):
        return poison(real(a, w, sizes), sizes)

    def fwd(a, w, sizes):
        return ragged_dot(a, w, sizes), (a, w, sizes)

    def bwd(res, ct):
        a, w, sizes = res
        da, dw = jax.vjp(lambda a, w: real(a, w, sizes), a, w)[1](ct)
        return poison(da, sizes), dw, None

    ragged_dot.defvjp(fwd, bwd)
    monkeypatch.setattr(jax.lax, "ragged_dot", ragged_dot)


@pytest.mark.parametrize("tail", ["zero", "nan"])
@pytest.mark.parametrize("skew", [False, True], ids=["uniform", "skewed"])
def test_dropless_equals_per_expert_loop(skew, tail, monkeypatch):
    """Forward and gradient, with routing as drawn and with one expert
    taking over half of the held assignments (where a capacity of 1.0 in
    the ``sorted`` mode would drop tokens); and with the grouped matmul's
    rows past the last group zero (the CPU) or NaN (undefined, as on the
    TPU), which reach neither the output nor a gradient."""
    if tail == "nan":
        _nan_past_groups(monkeypatch)
    cfg = _share()
    p, x = _layer_inputs(cfg, skew)
    y, aux = moe_mod.apply_moe(cfg, p, x)
    if skew:
        assert aux["moe_load_max"] > 0.5 * aux["moe_assignments_held"]
    np.testing.assert_allclose(np.asarray(y), np.asarray(_per_expert_loop(
        cfg, p, x)), rtol=1e-5, atol=1e-5)
    ct = jax.random.normal(jax.random.PRNGKey(9), x.shape)
    grads = [jax.grad(lambda p, x: jnp.sum(f(p, x) * ct), (0, 1))(p, x)
             for f in (lambda p, x: moe_mod.apply_moe(cfg, p, x)[0],
                       lambda p, x: _per_expert_loop(cfg, p, x))]
    for a, b in zip(jax.tree.leaves(grads[0]), jax.tree.leaves(grads[1])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("norm", [False, True])
def test_shares_add_up_to_the_uncut_layer(norm):
    """Offsets 0 and 4 of a router of 8: the two chips' parts sum to the
    layer that holds all 8 experts."""
    whole = _share(held=8, norm=norm)
    p, x = _layer_inputs(whole, skew=False)
    y_whole, _ = moe_mod.apply_moe(whole, p, x)
    parts = []
    for off in (0, 4):
        cfg = _share(held=4, offset=off, norm=norm)
        q = dict(p, wi=p["wi"][off:off + 4], wo=p["wo"][off:off + 4])
        parts.append(moe_mod.apply_moe(cfg, q, x)[0])
    np.testing.assert_allclose(np.asarray(parts[0] + parts[1]),
                               np.asarray(y_whole), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("norm", [False, True])
def test_topk_weights_by_hand(norm):
    """Router probabilities 0.4, 0.3, 0.2, 0.1 (logits their logs), top 2:
    the weights are 0.4 and 0.3 as they are, or 4/7 and 3/7
    renormalised."""
    cfg = dataclasses.replace(_share(router=4, held=4, norm=norm), d_model=4)
    p = {"router": jnp.diag(jnp.log(jnp.array([0.4, 0.3, 0.2, 0.1])))}
    x = jnp.ones((1, 1, 4))
    w, idx, probs = moe_mod._router(cfg, p, x)
    want = [4 / 7, 3 / 7] if norm else [0.4, 0.3]
    np.testing.assert_allclose(np.asarray(w[0, 0]), want, rtol=1e-6)
    assert idx[0, 0].tolist() == [0, 1]
    np.testing.assert_allclose(np.asarray(probs[0, 0]),
                               [0.4, 0.3, 0.2, 0.1], rtol=1e-6)


def test_balance_loss_is_hf_over_all_layers():
    """`layer_aux` from per-layer routing shares and mean probabilities
    equals HF's ``load_balancing_loss_func`` over the layers' router
    logits concatenated: R * sum_(k, e) (share of tokens whose slot k is
    e) x (mean probability of e)."""
    cfg = _share()
    logits = jax.random.normal(jax.random.PRNGKey(5), (3, 32, 8))  # (L, N, R)
    probs = jax.nn.softmax(logits, axis=-1)
    _, idx = jax.lax.top_k(probs, cfg.experts_per_token)
    onehot = jax.nn.one_hot(idx, 8)                                  # (L,N,k,R)
    auxs = {"route_frac": jnp.mean(onehot, axis=1),
            "route_prob": jnp.mean(probs, axis=1),
            **{s: jnp.ones(3) for s in moe_mod.STATS}}
    loss, stats = moe_mod.layer_aux(cfg, auxs)
    flat_p, flat_oh = probs.reshape(-1, 8), onehot.reshape(-1, 2, 8)
    hf = 8 * jnp.sum(jnp.mean(flat_oh, axis=0)
                     * jnp.mean(flat_p, axis=0)[None])
    np.testing.assert_allclose(float(loss), float(hf), rtol=1e-6)
    assert stats == {s: 3.0 for s in moe_mod.STATS}
