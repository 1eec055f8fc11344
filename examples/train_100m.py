"""End-to-end driver: federated training of a ~100M-parameter dense LM
(granite-family reduced: 8L x d512, vocab 49155) for a few hundred global
rounds under Algorithm 1, with checkpointing and a held-out eval.

Default scale targets a real run (~hours on 1 CPU core; minutes on real
hardware).  --steps/--batch/--seq let you scale down for a quick pass:

  PYTHONPATH=src python examples/train_100m.py --rounds 20 --batch 2 --seq 128
"""
import argparse
import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import save_checkpoint
from repro.configs import get_config
from repro.core import EnergyProfile, FedConfig, Policy, simulate
from repro.data import SyntheticTokens
from repro.models import get_model
from repro.optim import adam


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=300)
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--local-steps", type=int, default=5)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--policy", default="sustainable")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt", default="benchmarks/results/train_100m.msgpack")
    ap.add_argument("--log", default="benchmarks/results/train_100m.json")
    a = ap.parse_args()

    # ~100M params: granite-3-2b family, reduced depth/width, full vocab
    cfg = dataclasses.replace(
        get_config("granite-3-2b"), num_layers=8, d_model=512, num_heads=8,
        num_kv_heads=4, head_dim=64, d_ff=2048, dtype="float32", remat=False)
    model = get_model(cfg)
    w = model.init_params(jax.random.PRNGKey(a.seed))
    n = model.num_params(w)
    print(f"model: {cfg.name}-100m {n:,} params "
          f"({cfg.num_layers}L d{cfg.d_model} vocab {cfg.vocab_size})")

    C, T = a.clients, a.local_steps
    E = np.asarray(EnergyProfile(C, (1, 2, 4, 8)).cycles())
    p = np.ones(C) / C
    fed = FedConfig(num_clients=C, local_steps=T, policy=a.policy, seed=a.seed)
    source = SyntheticTokens(cfg.vocab_size, a.seq, C, client_skew=0.5,
                             seed=a.seed)
    held_out = {"tokens": jnp.asarray(source.batch(0, 8, 999_999))}

    def loss_fn(params, batch, rng):
        return model.loss_fn(params, batch)

    eval_loss = jax.jit(lambda w: model.loss_fn(w, held_out)[0])

    def batch_fn(rnd, i):
        toks = np.stack([source.batch(i, a.batch, rnd * 131 + t)
                         for t in range(T)])
        return {"tokens": jnp.asarray(toks)}

    t0 = time.time()
    res = simulate(loss_fn, adam(a.lr), fed, w, batch_fn, p, E, a.rounds,
                   jax.random.PRNGKey(a.seed),
                   eval_fn=lambda w: {"eval_loss": float(eval_loss(w))},
                   eval_every=max(1, a.rounds // 10), verbose=True)
    wall = time.time() - t0
    evals = [(h["round"], h["eval_loss"]) for h in res.history
             if "eval_loss" in h]
    print(f"eval loss {evals[0][1]:.3f} -> {evals[-1][1]:.3f} "
          f"in {a.rounds} rounds ({wall/60:.1f} min)")
    save_checkpoint(a.ckpt, res.params, step=a.rounds,
                    metadata={"arch": "granite-100m", "policy": a.policy})
    with open(a.log, "w") as f:
        json.dump({"params": n, "rounds": a.rounds, "wall_s": wall,
                   "history": res.history}, f, indent=1)
    print(f"checkpoint -> {a.ckpt}\nlog -> {a.log}")


if __name__ == "__main__":
    main()
