"""Round engine: parallel == sequential == participants only == by-hand
local SGD + aggregation."""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (FedConfig, Policy, aggregate, parallel_round,
                        participant_round, participation_mask, local_update,
                        accumulate_client_delta, apply_accumulated,
                        zeros_like_fp32, aggregation_scale)
from repro.optim import adam, sgd


def _quad_loss(p, batch, rng):
    x, y = batch
    return 0.5 * jnp.mean((x @ p["w"] + p["b"] - y) ** 2), {}


def _setup(C=6, T=3, B=4, d=3, seed=0):
    key = jax.random.PRNGKey(seed)
    w0 = {"w": jnp.zeros((d,)), "b": jnp.zeros(())}
    xs = jax.random.normal(key, (C, T, B, d))
    ys = jax.random.normal(jax.random.fold_in(key, 1), (C, T, B))
    p = jnp.ones((C,)) / C
    E = jnp.asarray(([1, 2, 3] * C)[:C], jnp.int32)
    return w0, (xs, ys), p, E, key


def test_parallel_round_equals_manual():
    """parallel_round == (per-client T-step local_update, then eq. 13)."""
    for opt in (sgd(0.1), sgd(0.05, momentum=0.9), adam(1e-2)):
        C, T = 6, 3
        w0, batches, p, E, key = _setup(C, T)
        cfg = FedConfig(num_clients=C, local_steps=T,
                        policy=Policy.SUSTAINABLE, seed=3)
        w_par, metrics = parallel_round(_quad_loss, opt, cfg, w0, batches,
                                        p, E, jnp.int32(0), key)
        # manual: replicate the exact per-client rng derivation of the engine
        mask = participation_mask(cfg.policy, cfg.seed, jnp.int32(0), E)
        w_stack = []
        for i in range(C):
            cb = jax.tree.map(lambda b: b[i], batches)
            # engine folds (rng, i) then (key_i, t) inside the scan step
            ki = jax.random.fold_in(key, i)
            # reproduce via local_update with the same keys: run manually
            params = w0
            s = opt.init(params)
            for t in range(T):
                bt = jax.tree.map(lambda b: b[t], cb)
                g = jax.grad(lambda q: _quad_loss(q, bt, None)[0])(params)
                params, s = opt.update(g, s, params, jnp.int32(t))
            w_stack.append(params)
        w_stack = jax.tree.map(lambda *xs: jnp.stack(xs), *w_stack)
        w_manual = aggregate(w0, w_stack, mask, p,
                             aggregation_scale(cfg.policy, E))
        for k in w_par:
            np.testing.assert_allclose(np.asarray(w_par[k]),
                                       np.asarray(w_manual[k]),
                                       rtol=1e-5, atol=1e-5)


def test_sequential_equals_parallel():
    """Linearity of eq. 13: one-at-a-time accumulation == stacked round."""
    from repro.core.round import sequential_client_step, finish_sequential_round
    opt = sgd(0.1)
    C, T = 4, 2
    w0, batches, p, E, key = _setup(C, T)
    E = E[:C]
    cfg = FedConfig(num_clients=C, local_steps=T, policy=Policy.SUSTAINABLE,
                    seed=1)
    mask = participation_mask(cfg.policy, cfg.seed, jnp.int32(0), E[:C])

    acc = zeros_like_fp32(w0)
    for i in range(C):
        cb = jax.tree.map(lambda b: b[i], batches)
        acc, _, _ = sequential_client_step(
            _quad_loss, opt, cfg, w0, acc, cb, p[i], E[i], mask[i],
            jax.random.fold_in(key, i))
    w_seq = finish_sequential_round(cfg, w0, acc)

    # parallel result with rng-independent loss must match exactly
    w_par, _ = parallel_round(_quad_loss, opt, cfg, w0, batches, p, E,
                              jnp.int32(0), key)
    for k in w_par:
        np.testing.assert_allclose(np.asarray(w_par[k]), np.asarray(w_seq[k]),
                                   rtol=1e-5, atol=1e-5)


def test_wait_all_noop_rounds_keep_model():
    opt = sgd(0.1)
    C, T = 4, 2
    w0, batches, p, E, key = _setup(C, T)
    E = jnp.asarray([2, 2, 4, 4], jnp.int32)
    cfg = FedConfig(num_clients=C, local_steps=T, policy=Policy.WAIT_ALL)
    # round 1 is not a multiple of E_max=4: nobody participates
    w1, m = parallel_round(_quad_loss, opt, cfg, w0, batches, p, E,
                           jnp.int32(1), key)
    assert float(m["participants"]) == 0
    for k in w0:
        np.testing.assert_allclose(np.asarray(w1[k]), np.asarray(w0[k]))


def test_adam_local_state_reset_each_round():
    """local optimizer state must NOT leak across rounds (fresh init)."""
    opt = adam(1e-2)
    C, T = 2, 2
    w0, batches, p, E, key = _setup(C, T)
    p, E = p[:C] * 3, E[:C]
    cfg = FedConfig(num_clients=C, local_steps=T, policy=Policy.ALWAYS)
    w1, _ = parallel_round(_quad_loss, opt, cfg, w0, batches, p, E,
                           jnp.int32(0), key)
    w1b, _ = parallel_round(_quad_loss, opt, cfg, w0, batches, p, E,
                            jnp.int32(5), key)
    # same inputs, different round index: identical result (no hidden state)
    for k in w1:
        np.testing.assert_allclose(np.asarray(w1[k]), np.asarray(w1b[k]))


def _noisy_loss(p, batch, rng):
    """_quad_loss plus a term drawn from the step's key, so that a round
    matches another only if each client step gets the same key."""
    x, y = batch
    noise = jax.random.normal(rng, y.shape)
    return (_quad_loss(p, batch, rng)[0]
            + 0.1 * jnp.mean(noise * (x @ p["w"])), {})


OPTIMIZERS = {"sgd": lambda: sgd(0.1),
              "sgd_momentum": lambda: sgd(0.05, momentum=0.9),
              "adam": lambda: adam(1e-2)}


@pytest.mark.parametrize("opt_name", sorted(OPTIMIZERS))
@pytest.mark.parametrize("policy, rnd", [
    ("sustainable", 2), ("greedy", 1), ("always", 3),
    ("wait_all", 3), ("wait_all", 4)])
def test_participant_round_equals_parallel(policy, rnd, opt_name):
    """Training the participants only gives parallel_round's model and
    metrics; it computes participants x T client steps, and a round
    nobody takes part in (wait_all between sync points) returns the
    global model bit for bit."""
    C, T = 6, 3
    w0, batches, p, E, key = _setup(C, T)
    w0 = {"w": jnp.asarray([0.5, -0.25, 1.0]), "b": jnp.asarray(-0.0)}
    cfg = FedConfig(num_clients=C, local_steps=T, policy=Policy(policy),
                    seed=3)
    opt = OPTIMIZERS[opt_name]()
    args = (w0, batches, p, E, jnp.int32(rnd), key)
    w_par, m_par = jax.jit(partial(parallel_round, _noisy_loss, opt, cfg))(
        *args)
    w_part, m_part = jax.jit(partial(participant_round, _noisy_loss, opt,
                                     cfg))(*args)
    k = float(m_part["participants"])
    assert k == float(m_par["participants"])
    assert int(m_part["client_steps"]) == k * T
    for name in w0:
        np.testing.assert_allclose(np.asarray(w_part[name]),
                                   np.asarray(w_par[name]),
                                   rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(float(m_part["loss"]), float(m_par["loss"]),
                               rtol=1e-5, atol=1e-5)
    if policy == "wait_all" and rnd % 3:
        assert k == 0 and float(m_part["loss"]) == 0.0
        for name in w0:
            assert np.asarray(w_part[name]).tobytes() == \
                np.asarray(w0[name]).tobytes()
    else:
        assert k > 0
