"""Beyond-paper ablation: non-iid label skew (Dirichlet partitions).

The paper's §V uses an iid partition; under non-iid data the bias of the
greedy benchmark should WORSEN (frequent-energy clients drag the model toward
their label mixture), widening Algorithm 1's margin.  This script measures
the gap as a function of Dirichlet alpha.

  PYTHONPATH=src python examples/noniid_ablation.py --rounds 40
"""
import argparse
import json

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import FedConfig, simulate
from repro.data import (FederatedLoader, SyntheticImages, client_weights,
                        dirichlet_partition, iid_partition)
from repro.optim import adam


def mlp_init(key, d_in=32 * 32 * 3, hidden=64, classes=10):
    k1, k2 = jax.random.split(key)
    return {"w1": jax.random.normal(k1, (d_in, hidden)) * (2 / d_in) ** 0.5,
            "b1": jnp.zeros(hidden),
            "w2": jax.random.normal(k2, (hidden, classes)) * (2 / hidden) ** 0.5,
            "b2": jnp.zeros(classes)}


def mlp_apply(params, x):
    h = jax.nn.relu(x.reshape(x.shape[0], -1) @ params["w1"] + params["b1"])
    return h @ params["w2"] + params["b2"]


def loss_fn(params, batch, rng):
    logits = mlp_apply(params, batch["images"])
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, batch["labels"][:, None], -1)[:, 0]
    return jnp.mean(logz - gold), {}


def run(alpha, policy, rounds, C=16, T=5, batch=8, seed=0, noise=4.0):
    data = SyntheticImages(num_train=1500, num_test=1000, seed=seed,
                           noise=noise)
    xtr, ytr = data.train_set()
    xte, yte = data.test_set()
    if alpha is None:
        shards = iid_partition(ytr, C, seed)
    else:
        shards = dirichlet_partition(ytr, C, alpha, seed, min_per_client=batch)
    loader = FederatedLoader({"images": xtr, "labels": ytr}, shards, batch, T,
                             seed)
    p = client_weights(shards)
    E = np.asarray([(1, 4, 8, 16)[i % 4] for i in range(C)], np.int32)
    fed = FedConfig(num_clients=C, local_steps=T, policy=policy, seed=seed)

    def batch_fn(r, i):
        b = loader.round_batch(r)
        return {"images": jnp.asarray(b["images"][i]),
                "labels": jnp.asarray(b["labels"][i])}

    res = simulate(loss_fn, adam(1e-3), fed, mlp_init(jax.random.PRNGKey(seed)),
                   batch_fn, p, E, rounds, jax.random.PRNGKey(seed))
    acc = float(jnp.mean(jnp.argmax(mlp_apply(res.params, jnp.asarray(xte)), -1)
                         == jnp.asarray(yte)))
    tl = float(loss_fn(res.params, {"images": jnp.asarray(xte),
                                    "labels": jnp.asarray(yte)}, None)[0])
    return acc, tl


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=40)
    ap.add_argument("--out", default="benchmarks/results/noniid_ablation.json")
    a = ap.parse_args()
    table = {}
    for alpha in (None, 1.0, 0.2):
        name = "iid" if alpha is None else f"dir({alpha})"
        res = {pol: run(alpha, pol, a.rounds)
               for pol in ("sustainable", "greedy")}
        gap = res["greedy"][1] - res["sustainable"][1]  # loss gap (greedy worse > 0)
        table[name] = {"alg1_acc": res["sustainable"][0],
                       "greedy_acc": res["greedy"][0],
                       "alg1_loss": res["sustainable"][1],
                       "greedy_loss": res["greedy"][1],
                       "loss_gap": gap}
        print(f"{name:10s} alg1 acc={res['sustainable'][0]:.3f} "
              f"loss={res['sustainable'][1]:.3f} | greedy acc={res['greedy'][0]:.3f} "
              f"loss={res['greedy'][1]:.3f} | loss_gap={gap:+.3f}", flush=True)
    with open(a.out, "w") as f:
        json.dump(table, f, indent=1)
