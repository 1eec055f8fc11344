"""Federated training launcher.

Runs real (small-scale, CPU-capable) federated training with any scheduling
policy over any registered architecture's smoke config, or — on real
hardware — the full config over the production mesh.  The same round step
that the dry-run lowers is executed here.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch granite-3-2b --smoke \\
      --rounds 20 --policy sustainable
  PYTHONPATH=src python -m repro.launch.train --arch cifar-cnn --smoke \\
      --rounds 100 --policy greedy
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import save_checkpoint
from repro.configs import get_config, get_smoke_config
from repro.core import EnergyProfile, FedConfig, participant_round
from repro.data import SyntheticImages, SyntheticTokens, iid_partition, \
    FederatedLoader, client_weights
from repro.launch.cache import enable_compile_cache
from repro.launch.steps import make_optimizer_for
from repro.models import get_model
from repro.models import moe as moe_mod
from repro.obs.metrics import Counter
from repro.obs.profile import gc_spans, span


def token_batch_fn(cfg, source, C, T, bc):
    def fn(rnd):
        toks = np.stack([
            np.stack([source.batch(c, bc, rnd * 131 + t) for t in range(T)])
            for c in range(C)])
        batch = {"tokens": jnp.asarray(toks)}
        if cfg.family == "vlm":
            batch["vision_embeds"] = jnp.zeros(
                (C, T, bc, cfg.vision_tokens, cfg.d_model), cfg.dtype)
        if cfg.family == "encdec":
            batch["frames"] = jnp.asarray(
                np.random.RandomState(rnd).randn(
                    C, T, bc, cfg.encoder_seq, cfg.d_model), cfg.dtype)
        return batch
    return fn


# what `train_rounds` counts; ``train.client_steps_computed`` are the local
# steps the round ran (its ``client_steps``), ``train.client_steps_useful``
# the participants' (participants x T): equal under `participant_round`
TRAIN_COUNTERS = ("train.rounds", "train.client_steps_computed",
                  "train.client_steps_useful", "train.gc_collections",
                  "train.gc_ms")


def _counters(stats=()) -> dict[str, Counter]:
    """`TRAIN_COUNTERS`, and ``train.<stat>`` for each stat the round
    returns (the dropless expert layer's `moe.STATS`)."""
    return {n: Counter(n)
            for n in TRAIN_COUNTERS + tuple(f"train.{s}" for s in stats)}


@dataclasses.dataclass
class TrainRun:
    """Everything one federated training run needs, built by
    `setup_training` and driven by `train_rounds`."""

    cfg: Any
    model: Any
    fed: FedConfig
    p: jax.Array                    # (C,) data weights
    E: jax.Array                    # (C,) energy renewal cycles
    rng: jax.Array
    batch_fn: Callable[[int], dict]  # round -> (C, T, ...) batches
    round_fn: Callable               # jitted `participant_round`
    stats: tuple[str, ...] = ()      # the model's stats among its metrics
    counters: dict[str, Counter] = dataclasses.field(default_factory=_counters)

    def init_params(self):
        return self.model.init_params(self.rng)


def setup_training(cfg, *, clients: int, local_steps: int, batch: int,
                   seq: int, taus=(1, 2, 4, 8), policy: str = "sustainable",
                   optimizer: str = "adam", lr: float = 1e-3,
                   seed: int = 0) -> TrainRun:
    """Model, schedule, data and the jitted round for one launcher run.

    The round is `core.round.participant_round`: one program per process
    that trains only the round's participants, a loop whose trip count it
    reads off its own mask (DESIGN.md §3.2).  The loss carries the model's
    stats out of the round, summed over the participants' steps."""
    model = get_model(cfg)
    C, T = clients, local_steps
    fed = FedConfig(num_clients=C, local_steps=T, policy=policy, seed=seed)
    opt = make_optimizer_for(cfg, optimizer, lr)
    stats = moe_mod.STATS if moe_mod.counts_stats(cfg) else ()

    def loss_fn(params, batch, key):
        return model.loss_fn(params, batch)

    if cfg.family == "cnn":
        data = SyntheticImages(num_train=2000, num_test=512, seed=seed)
        imgs, labels = data.train_set()
        shards = iid_partition(labels, C, seed)
        loader = FederatedLoader({"images": imgs, "labels": labels}, shards,
                                 batch, T, seed)
        batch_fn = lambda r: jax.tree.map(jnp.asarray, loader.round_batch(r))
    else:
        source = SyntheticTokens(cfg.vocab_size, seq, C, seed=seed)
        batch_fn = token_batch_fn(cfg, source, C, T, batch)
    return TrainRun(cfg=cfg, model=model, fed=fed, p=jnp.ones((C,)) / C,
                    E=EnergyProfile(C, tuple(taus)).cycles(),
                    rng=jax.random.PRNGKey(seed), batch_fn=batch_fn,
                    round_fn=jax.jit(partial(participant_round, loss_fn, opt,
                                             fed)),
                    stats=stats, counters=_counters(stats))


def train_rounds(run: TrainRun, w, rounds: int, *, start: int = 0,
                 history: list | None = None, obs=None, after_round=None):
    """The launcher's round loop: rounds ``start .. rounds-1`` from global
    model ``w``.  Each round's batches and key derive from its absolute
    index, so a resumed run replays bit-exactly.  ``after_round(r, w,
    history)`` runs after each round (checkpointing).  Returns ``(w,
    history)``; history records hold host floats, so every round is
    materialized before the next is dispatched.

    Each round is a ``train.round`` span (`repro.obs.profile.span`, with
    its index) tiled by ``train.batch``, ``train.args``, ``train.dispatch``,
    ``train.fetch`` and ``train.after_round``; each garbage collection is
    a ``train.gc`` span; ``run.counters`` count the rounds, the client
    steps computed and those of participants, the collections, and each
    of ``run.stats`` as ``train.<stat>``.  With
    ``obs`` the spans are events too and the counters ride on its closing
    ``metrics`` event."""
    history = [] if history is None else history
    count, T = run.counters, run.fed.local_steps
    if obs is not None:
        for c in count.values():
            obs.metrics.attach(c)

    def collected(ms):
        count["train.gc_collections"].inc()
        count["train.gc_ms"].inc(ms)

    t0 = time.time()
    with gc_spans("train.gc", collected):
        for r in range(start, rounds):
            with span("train.round", obs, round=r):
                with span("train.batch", obs):
                    batches = run.batch_fn(r)
                with span("train.args", obs):
                    rnd, key = jnp.int32(r), jax.random.fold_in(run.rng, r)
                with span("train.dispatch", obs):
                    w, m = run.round_fn(w, batches, run.p, run.E, rnd, key)
                with span("train.fetch", obs):
                    m = jax.device_get(m)
                    rec = {"round": r, "loss": float(m["loss"]),
                           "participants": float(m["participants"])}
                    history.append(rec)
                    count["train.rounds"].inc()
                    count["train.client_steps_computed"].inc(
                        int(m["client_steps"]))
                    count["train.client_steps_useful"].inc(
                        int(rec["participants"]) * T)
                    for s in run.stats:
                        if s in m:   # `parallel_round` drops the stats
                            count[f"train.{s}"].inc(float(m[s]))
                    if obs is not None:
                        obs.event("round", scan="train", **rec)
                with span("train.after_round", obs):
                    if after_round is not None:
                        after_round(r, w, history)
                    if r % max(1, rounds // 10) == 0 or r == rounds - 1:
                        print(f"round {r:4d} loss={rec['loss']:.4f} "
                              f"participants={rec['participants']:.0f} "
                              f"({time.time()-t0:.1f}s)", flush=True)
    return w, history


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config (CPU-runnable)")
    ap.add_argument("--policy", default="sustainable",
                    choices=["sustainable", "greedy", "wait_all", "always"])
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--local-steps", type=int, default=5)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--taus", default="1,2,4,8",
                    help="energy renewal cycles, assigned round-robin")
    ap.add_argument("--optimizer", default="adam")
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--log", default="")
    ap.add_argument("--checkpoint-dir", default="",
                    help="save a resumable run checkpoint (params + round + "
                         "history, retained-last-k rotation) into this "
                         "directory every --checkpoint-every rounds")
    ap.add_argument("--resume", action="store_true",
                    help="resume from the newest intact checkpoint in "
                         "--checkpoint-dir (bit-exact: per-round RNG and "
                         "batches are derived from the absolute round index)")
    ap.add_argument("--checkpoint-every", type=int, default=5)
    ap.add_argument("--obs-dir", default="",
                    help="stream a repro.obs run (manifest + per-round "
                         "events + span timings) to this directory")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    C, T = args.clients, args.local_steps
    taus = tuple(int(x) for x in args.taus.split(","))
    run = setup_training(cfg, clients=C, local_steps=T, batch=args.batch,
                         seq=args.seq, taus=taus, policy=args.policy,
                         optimizer=args.optimizer, lr=args.lr, seed=args.seed)
    fed = run.fed
    w = run.init_params()
    n_params = run.model.num_params(w)
    print(f"arch={cfg.name} family={cfg.family} params={n_params:,} "
          f"clients={C} T={T} policy={args.policy} "
          f"E={list(np.asarray(run.E))}")

    ckptr, cfg_hash, start, history = None, None, 0, []
    if args.resume and not args.checkpoint_dir:
        raise SystemExit("--resume requires --checkpoint-dir")
    if args.checkpoint_dir:
        from repro.checkpoint import resume as resume_lib
        from repro.obs.events import pytree_hash
        ckptr = resume_lib.as_checkpointer(args.checkpoint_dir)
        cfg_hash = pytree_hash(("train", cfg.name, fed, args.optimizer,
                                args.lr, T, args.batch, args.seq, taus))
        if args.resume:
            rc = resume_lib.restore_run(ckptr, kind="train", state_like=w,
                                        config_hash=cfg_hash, seed=args.seed)
            if rc is not None:
                w, start = rc.state, rc.round_offset
                history = [{"round": i, "loss": float(l),
                            "participants": float(p)}
                           for i, (l, p) in enumerate(
                               zip(rc.stats["loss"],
                                   rc.stats["participants"]))]
                print(f"resumed from round {start} "
                      f"({ckptr.path(start)})")

    obs = None
    if args.obs_dir:
        from repro.obs import Obs
        obs = Obs(args.obs_dir)
        if start:
            # re-attach to the existing event stream: a resumed run emits a
            # `resume` event, never a second manifest (DESIGN.md §13.4)
            obs.event("resume", run_kind="train", round=start,
                      horizon=args.rounds, config_hash=cfg_hash,
                      checkpoint_dir=args.checkpoint_dir)
        else:
            obs.write_manifest("train", config=fed, seed=args.seed,
                               num_clients=C, horizon=args.rounds,
                               arch=cfg.name, family=cfg.family,
                               params=int(n_params), policy=args.policy,
                               local_steps=T, optimizer=args.optimizer,
                               lr=args.lr)

    def save_run(r, w, history):
        if ckptr is None or not ((r + 1) % max(1, args.checkpoint_every) == 0
                                 or r == args.rounds - 1):
            return
        from repro.checkpoint import resume as resume_lib
        resume_lib.save_run(
            ckptr, kind="train", round_offset=r + 1, state=w,
            stats={"loss": np.asarray([h["loss"] for h in history]),
                   "participants": np.asarray(
                       [h["participants"] for h in history])},
            config_hash=cfg_hash, seed=args.seed)

    w, history = train_rounds(run, w, args.rounds, start=start,
                              history=history, obs=obs, after_round=save_run)
    if args.ckpt:
        save_checkpoint(args.ckpt, w, step=args.rounds,
                        metadata={"arch": cfg.name, "policy": args.policy})
        print("checkpoint ->", args.ckpt)
    if args.log:
        with open(args.log, "w") as f:
            json.dump(history, f, indent=1)
    if obs is not None:
        obs.close()
        print("obs events ->", obs.log.path)
    print(f"final loss {history[-1]['loss']:.4f}")


if __name__ == "__main__":
    main()
