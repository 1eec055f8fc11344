"""End-to-end behaviour of the paper's system (fast CPU-scale versions of the
§V experiment): Algorithm 1 trains, stays unbiased, and beats the greedy
benchmark under heterogeneous energy arrivals."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import FedConfig, Policy, simulate
from repro.data import FederatedLoader, SyntheticImages, iid_partition, \
    client_weights
from repro.models import get_model
from repro.configs import get_config
from repro.optim import adam, sgd


def _mlp_loss(params, batch, rng):
    x = batch["images"].reshape(batch["images"].shape[0], -1)
    h = jax.nn.relu(x @ params["w1"] + params["b1"])
    logits = h @ params["w2"] + params["b2"]
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, batch["labels"][:, None], axis=-1)[:, 0]
    return jnp.mean(logz - gold), {}


def _mlp_init(key, d_in=32 * 32 * 3, hidden=32, classes=10):
    k1, k2 = jax.random.split(key)
    return {
        "w1": jax.random.normal(k1, (d_in, hidden)) * (2 / d_in) ** 0.5,
        "b1": jnp.zeros(hidden),
        "w2": jax.random.normal(k2, (hidden, classes)) * (2 / hidden) ** 0.5,
        "b2": jnp.zeros(classes),
    }


def _accuracy(params, images, labels):
    x = images.reshape(images.shape[0], -1)
    h = jax.nn.relu(x @ params["w1"] + params["b1"])
    logits = h @ params["w2"] + params["b2"]
    return float(jnp.mean(jnp.argmax(logits, -1) == labels))


def _run_policy(policy, rounds=30, C=8, T=5, batch=16, seed=0, noise=0.35):
    data = SyntheticImages(num_train=1200, num_test=400, seed=seed,
                           noise=noise)
    xtr, ytr = data.train_set()
    xte, yte = data.test_set()
    shards = iid_partition(ytr, C, seed)
    loader = FederatedLoader({"images": xtr, "labels": ytr}, shards, batch, T,
                             seed)
    p = client_weights(shards)
    E = np.asarray([(1, 2, 4, 8)[i % 4] for i in range(C)], np.int32)
    cfg = FedConfig(num_clients=C, local_steps=T, policy=policy, seed=seed)
    w0 = _mlp_init(jax.random.PRNGKey(seed))

    def batch_fn(r, i):
        b = loader.round_batch(r)
        return {"images": jnp.asarray(b["images"][i]),
                "labels": jnp.asarray(b["labels"][i])}

    res = simulate(_mlp_loss, adam(1e-3), cfg, w0, batch_fn, p, E, rounds,
                   jax.random.PRNGKey(seed))
    acc = _accuracy(res.params, jnp.asarray(xte), jnp.asarray(yte))
    test_loss = float(_mlp_loss(res.params, {"images": jnp.asarray(xte),
                                             "labels": jnp.asarray(yte)},
                                 None)[0])
    return acc, res, test_loss


def test_algorithm1_learns():
    acc, res, _ = _run_policy(Policy.SUSTAINABLE, rounds=30)
    assert acc > 0.55, acc  # well above 10% chance
    losses = [h["loss"] for h in res.history if "loss" in h]
    assert losses[-1] < losses[0]


def test_algorithm1_beats_wait_all_at_equal_rounds():
    """Benchmark 2 syncs only every E_max rounds -> much slower per round
    budget (the paper's second comparison)."""
    _, _, loss1 = _run_policy(Policy.SUSTAINABLE, rounds=7, seed=1, noise=2.5)
    _, _, loss2 = _run_policy(Policy.WAIT_ALL, rounds=7, seed=1, noise=2.5)
    # E_max=8: wait-all has synced once (round 0) vs Alg.1's 7 active rounds;
    # held-out xent is the sensitive metric (accuracy saturates on this task)
    assert loss1 < loss2, (loss1, loss2)


def test_fedavg_upper_bound_is_competitive():
    """Unconstrained FedAvg is the paper's upper bound: Algorithm 1 should be
    within striking distance but not above by a large margin in expectation."""
    acc1, _, _ = _run_policy(Policy.SUSTAINABLE, rounds=20, seed=2)
    accU, _, _ = _run_policy(Policy.ALWAYS, rounds=20, seed=2)
    assert accU >= acc1 - 0.08, (acc1, accU)


def test_cnn_federated_round_runs():
    """The paper's own CNN goes through one full simulated round."""
    cfg = get_config("cifar-cnn")
    model = get_model(cfg)
    data = SyntheticImages(num_train=160, num_test=40)
    xtr, ytr = data.train_set()
    shards = iid_partition(ytr, 4, 0)
    loader = FederatedLoader({"images": xtr, "labels": ytr}, shards, 8, 2)
    p = client_weights(shards)
    E = np.asarray([1, 2, 1, 2], np.int32)
    fed = FedConfig(num_clients=4, local_steps=2, policy=Policy.SUSTAINABLE)

    def loss(params, batch, rng):
        return model.loss_fn(params, batch)

    def batch_fn(r, i):
        b = loader.round_batch(r)
        return {"images": jnp.asarray(b["images"][i]),
                "labels": jnp.asarray(b["labels"][i])}

    w0 = model.init_params(jax.random.PRNGKey(0))
    res = simulate(loss, sgd(0.01), fed, w0, batch_fn, p, E, 2,
                   jax.random.PRNGKey(0))
    assert all(np.isfinite(h.get("loss", 0.0)) for h in res.history)
