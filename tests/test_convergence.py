"""Theorem 1 validation on problems where its assumptions hold EXACTLY:
strongly-convex quadratic client losses, eta_t = 2/(mu(gamma+t)).

Checks: (a) Algorithm 1 converges to the global optimum w* (unbiased);
(b) the O(1/K) rate: error at 2K is ~half the error at K (up to slack);
(c) the greedy benchmark converges to a *different* (biased) fixed point when
clients are heterogeneous; (d) the bound evaluator is sane and dominates the
observed error.
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import FedConfig, Policy, simulate, Theorem1Constants
from repro.core.convergence import quadratic_problem_constants
from repro.optim import sgd
from repro.optim.schedules import paper_theorem1


def _make_problem(C=4, d=3, seed=0):
    """Client losses F_i(w) = 0.5||A_i w - b_i||^2 with distinct optima."""
    rs = np.random.RandomState(seed)
    A = [rs.randn(6, d) + np.eye(6, d) * 2.0 for _ in range(C)]
    b = [rs.randn(6) * (i + 1) for i in range(C)]
    p = np.ones(C) / C
    H = sum(pi * a.T @ a for pi, a in zip(p, A))
    g = sum(pi * a.T @ bb for pi, a, bb in zip(p, A, b))
    w_star = np.linalg.solve(H, g)
    return A, b, p, w_star


def _loss_fn_for(A, b):
    A = jnp.asarray(np.stack(A))
    b = jnp.asarray(np.stack(b))

    def loss(params, batch, rng):
        i = batch["client"]
        r = A[i] @ params["w"] - b[i]
        return 0.5 * jnp.sum(r * r), {}

    return loss


def _run(policy, E, K, T=2, seed=0, lr_scale=1.0):
    A, b, p, w_star = _make_problem()
    C, d = len(A), A[0].shape[1]
    loss = _loss_fn_for(A, b)
    consts = quadratic_problem_constants(A, b, p, E, np.zeros(d), w_star)
    sched = paper_theorem1(consts.mu, consts.L, T)
    opt = sgd(lambda t: lr_scale * sched(t))
    cfg = FedConfig(num_clients=C, local_steps=T, policy=policy, seed=seed)

    def batch_fn(rnd, i):  # full-gradient "minibatch" (sigma^2 = 0)
        return {"client": jnp.full((T,), i, jnp.int32)}

    w0 = {"w": jnp.zeros((d,))}
    res = simulate(loss, opt, cfg, w0, batch_fn, p, np.asarray(E), K,
                   jax.random.PRNGKey(seed))
    return np.asarray(res.params["w"]), w_star, consts


def test_algorithm1_converges_to_global_optimum():
    E = np.array([1, 2, 4, 4], np.int32)
    w_K, w_star, _ = _run(Policy.SUSTAINABLE, E, K=600)
    assert np.linalg.norm(w_K - w_star) < 0.15 * (1 + np.linalg.norm(w_star))


def test_rate_is_o_one_over_k():
    E = np.array([1, 2, 2, 4], np.int32)
    errs = []
    for K in (100, 200, 400):
        w_K, w_star, _ = _run(Policy.SUSTAINABLE, E, K=K, seed=1)
        errs.append(np.linalg.norm(w_K - w_star) ** 2)
    # O(1/K): doubling K should at least noticeably shrink the error
    assert errs[2] < 0.7 * errs[0], errs


def test_greedy_is_biased_under_heterogeneity():
    """Benchmark 1 over-weights frequent-energy clients: its fixed point
    differs from w* (the paper's bias claim) — Algorithm 1 gets closer."""
    E = np.array([1, 8, 8, 8], np.int32)  # client 0 participates 8x as often
    w_alg1, w_star, _ = _run(Policy.SUSTAINABLE, E, K=600, seed=2)
    w_greedy, _, _ = _run(Policy.GREEDY, E, K=600, seed=2)
    d_alg1 = np.linalg.norm(w_alg1 - w_star)
    d_greedy = np.linalg.norm(w_greedy - w_star)
    assert d_alg1 < d_greedy, (d_alg1, d_greedy)


def test_bound_evaluator_sane():
    c = Theorem1Constants(mu=1.0, L=4.0, T=5, G2=10.0, sigma2=1.0,
                          gamma_het=0.5, E_max=20, w0_dist2=2.0)
    assert c.kappa == 4.0
    assert c.gamma == 32.0
    b1, b2 = c.bound(100), c.bound(1000)
    assert b1 > b2 > 0
    # C term grows with E_max^2 (Lemma 2)
    c2 = Theorem1Constants(mu=1.0, L=4.0, T=5, G2=10.0, sigma2=1.0,
                           gamma_het=0.5, E_max=40, w0_dist2=2.0)
    assert c2.C() == 4 * c.C()
    # eta_t satisfies the Lemma-2 condition eta_t <= 2 eta_{t+T}
    for t in range(0, 100, 7):
        assert c.eta(t) <= 2 * c.eta(t + c.T) + 1e-12
