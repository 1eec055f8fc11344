"""The federated round engine (Algorithm 1 of the paper).

A *global round* is: (1) clients decide participation via the scheduling policy
(`core.scheduling`), (2) scheduled clients run ``T`` local optimizer steps from
the current global model (eq. 7), (3) the server aggregates scaled deltas
(eqs. 12-13) into the new global model.

Three schedules of the same round (see DESIGN.md §3.2); linearity of
eq. (13) makes them equivalent:

* **parallel** (`parallel_round`) — all client groups run simultaneously:
  local models are stacked on a leading client axis ``C`` that is sharded
  over the mesh's data axis.  The whole round is one jitted function; no
  communication during the local phase, one fused weighted reduction at the
  end.  Every client computes; the mask zeroes non-participants.  Used by
  the mesh step (`launch.steps.build_train_step`) and `run_rounds`.
* **participants** (`participant_round`) — one jitted program that runs the
  local update once per *participating* client, a loop whose trip count the
  program reads off its own mask, folding each delta into an fp32
  accumulator.  Used by the training launcher (`launch.train`).
* **sequential** (`sequential_client_step`) — one client per call over the
  full mesh (for architectures whose parameters cannot be replicated per
  client group); the mesh step's sequential mode.

The engine is model-agnostic: it takes a ``loss_fn(params, batch, rng) ->
(loss, stats)``, ``stats`` a dict of the step's counts (empty for a model
that counts nothing; `models.api`), and an ``Optimizer``; everything else is
pytrees.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.core import aggregation, scheduling
from repro.optim import Optimizer

PyTree = Any
LossFn = Callable[[PyTree, PyTree, jax.Array], tuple[jax.Array, dict]]


def micro_value_and_grad(loss_fn: LossFn, num_micro: int,
                         unroll: bool = False):
    """``jax.value_and_grad(loss_fn, has_aux=True)``, ``(params, batch, key)
    -> ((loss, stats), grads)``, with gradient accumulation over
    ``num_micro`` splits of the batch's leading dim (peak-activation memory
    / num_micro; fp32 accum) and the stats summed over the splits.
    """
    vg = jax.value_and_grad(loss_fn, has_aux=True)
    if num_micro <= 1:
        return vg

    def f(params, batch, key):
        for leaf in jax.tree.leaves(batch):
            if leaf.ndim == 0 or leaf.shape[0] % num_micro:
                raise ValueError(
                    f"micro_value_and_grad: batch leading dim "
                    f"{leaf.shape[0] if leaf.ndim else '<scalar>'} is not "
                    f"divisible by micro_batches={num_micro}; pick a "
                    f"micro_batches that divides the per-client batch size")
        mb = jax.tree.map(
            lambda b: b.reshape((num_micro, b.shape[0] // num_micro)
                                + b.shape[1:]), batch)

        def step(carry, xs):
            acc_l, acc_g = carry
            (l, stats), g = vg(params, xs, key)
            acc_g = jax.tree.map(
                lambda a, x: a + x.astype(jnp.float32) / num_micro, acc_g, g)
            return (acc_l + l / num_micro, acc_g), stats

        zeros = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), params)
        (loss, grads), stats = jax.lax.scan(step, (jnp.float32(0), zeros), mb,
                                            unroll=bool(unroll))
        grads = jax.tree.map(lambda g, p: g.astype(p.dtype), grads, params)
        return (loss, _summed(stats)), grads

    return f


def _summed(stats):
    """Stats stacked on a leading axis (steps, splits) -> their sums."""
    return jax.tree.map(lambda x: jnp.sum(x, axis=0), stats)


@dataclasses.dataclass(frozen=True)
class FedConfig:
    """Federated-learning hyperparameters (paper §II/§V notation)."""

    num_clients: int = 40               # N
    local_steps: int = 5                # T
    policy: scheduling.Policy = scheduling.Policy.SUSTAINABLE
    server_lr: float = 1.0
    mode: str = "parallel"              # parallel | sequential
    seed: int = 0
    unroll: bool = False                # unroll the local-step scan (cost calibration)
    micro_batches: int = 1              # grad accumulation within a local step
    phase: tuple[int, ...] | None = None  # per-client start offsets (footnote 1)

    def phase_array(self) -> jnp.ndarray | None:
        return None if self.phase is None else jnp.asarray(self.phase, jnp.int32)


def local_update(
    loss_fn: LossFn,
    optimizer: Optimizer,
    params: PyTree,
    batches: PyTree,          # leaves have leading axis T (one minibatch per local step)
    rng: jax.Array,
    num_steps: int,
    unroll: bool = False,
    micro_batches: int = 1,
    step_offset: jax.Array | int = 0,
) -> tuple[PyTree, jax.Array, dict]:
    """Eq. (7): ``T`` local optimizer steps via lax.scan.

    The local optimizer state is freshly initialised each round (FedAvg
    convention for stateful client optimizers such as Adam).

    ``step_offset`` is the global schedule index of this round's first local
    step (round * T): Theorem 1's eta_t = 2/(mu(gamma+t)) must keep decaying
    across rounds, not restart at eta_0 every round.  Step ``t``'s key is
    ``fold_in(rng, t)``, as in `parallel_round`.

    Returns (local params after T steps, mean local loss, the loss's stats
    summed over the steps).
    """
    with jax.named_scope("broadcast"):
        opt_state = optimizer.init(params)
    vg = micro_value_and_grad(loss_fn, micro_batches, unroll=unroll)

    def step(carry, xs):
        p, s = carry
        batch, t = xs
        with jax.named_scope("local_step"):
            (loss, stats), grads = vg(p, batch, jax.random.fold_in(rng, t))
        with jax.named_scope("optimizer"):
            p, s = optimizer.update(grads, s, p, t)
        return (p, s), (loss, stats)

    ts = jnp.asarray(step_offset, jnp.int32) \
        + jnp.arange(num_steps, dtype=jnp.int32)
    (params, _), (losses, stats) = jax.lax.scan(
        step, (params, opt_state), (batches, ts), unroll=bool(unroll))
    return params, jnp.mean(losses), _summed(stats)


def parallel_round(
    loss_fn: LossFn,
    optimizer: Optimizer,
    cfg: FedConfig,
    w_global: PyTree,
    client_batches: PyTree,   # leaves: (C, T, ...) per-client per-local-step minibatches
    p: jax.Array,             # (C,) data weights p_i
    E: jax.Array,             # (C,) energy renewal cycles
    rnd: jax.Array,           # scalar int32 global round index
    rng: jax.Array,
    constrain=None,           # optional per-leaf sharding constraint for stacked state
    constrain_opt=None,       # separate constraint for optimizer state (ZeRO-1)
) -> tuple[PyTree, dict[str, jax.Array]]:
    """One full global round with all client groups in parallel.

    Faithfulness note: *all* clients compute the local update and the mask
    zeroes out non-participants at aggregation.  This matches the equivalent
    form the paper itself uses for analysis (eqs. 18-19: "assume that all
    clients perform local training ... but the global model is updated using
    only the local updates from the clients that were originally scheduled").
    On hardware the masked clients' work is the price of a static schedule; the
    sequential mode avoids it.

    Distribution: client-stacked state (params, optimizer) carries an explicit
    leading C axis; ``constrain`` (dist.sharding.stacked_constrainer) pins it
    to the mesh's data axes so the local phase is communication-free and the
    final aggregation lowers to one reduction over the client axis.

    Named scopes ``schedule``, ``broadcast``, ``local_step``, ``optimizer``
    and ``aggregate`` tag the round's ops for a device trace (DESIGN.md
    §12); they change op metadata only.  The loss's stats are not among the
    metrics: they would count the masked clients' steps too.
    """
    n = cfg.num_clients
    cst = constrain if constrain is not None else (lambda t: t)
    cst_opt = constrain_opt if constrain_opt is not None else cst
    with jax.named_scope("schedule"):
        mask = scheduling.participation_mask(cfg.policy, cfg.seed, rnd, E,
                                             phase=cfg.phase_array())
        scale = scheduling.aggregation_scale(cfg.policy, E)

    # stacked local models, fresh per-round local optimizer state (eq. 6)
    with jax.named_scope("broadcast"):
        w_stack = cst(jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), w_global))
        opt_state = cst_opt(optimizer.init(w_stack))
    keys = jax.vmap(lambda i: jax.random.fold_in(rng, i))(jnp.arange(n))

    # (C, T, ...) -> (T, C, ...) for the local-step scan (eq. 7)
    xs = jax.tree.map(lambda b: jnp.moveaxis(b, 1, 0), client_batches)

    vg = micro_value_and_grad(loss_fn, cfg.micro_batches, unroll=cfg.unroll)

    def step(carry, inp):
        w, s = carry
        batch, t = inp
        kt = jax.vmap(lambda k: jax.random.fold_in(k, t))(keys)
        with jax.named_scope("local_step"):
            (losses, _), grads = jax.vmap(vg)(w, batch, kt)
        with jax.named_scope("optimizer"):
            w, s = optimizer.update(grads, s, w, t)
        return (cst(w), cst_opt(s)), losses

    # global schedule index: Theorem 1's eta_t keeps decaying across rounds
    ts = jnp.asarray(rnd, jnp.int32) * cfg.local_steps \
        + jnp.arange(cfg.local_steps, dtype=jnp.int32)
    (w_stack, _), losses = jax.lax.scan(step, (w_stack, opt_state), (xs, ts),
                                        unroll=bool(cfg.unroll))
    losses = jnp.mean(losses, axis=0)  # (C,) mean local loss per client

    with jax.named_scope("aggregate"):
        w_new = aggregation.aggregate(w_global, w_stack, mask, p, scale,
                                      cfg.server_lr)
    metrics = {
        "loss": jnp.sum(losses * mask) / jnp.maximum(jnp.sum(mask), 1.0),
        "participants": jnp.sum(mask),
        # client-local steps computed: every client runs all T today
        "client_steps": jnp.int32(n * cfg.local_steps),
    }
    return w_new, metrics


def sequential_client_step(
    loss_fn: LossFn,
    optimizer: Optimizer,
    cfg: FedConfig,
    w_global: PyTree,
    acc: PyTree,              # fp32 delta accumulator (zeros at round start)
    batches: PyTree,          # (T, ...) this client's minibatches
    p_i: jax.Array,
    E_i: jax.Array,
    alpha_i: jax.Array,       # this client's participation bit for this round
    rng: jax.Array,
    step_offset: jax.Array | int = 0,   # round * T, global schedule index
) -> tuple[PyTree, jax.Array, dict]:
    """Sequential mode: process ONE client's local round and fold its scaled
    delta into the accumulator.  ``apply_accumulated`` finishes the round.
    Returns (accumulator, mean local loss, the loss's stats summed over the
    client's steps)."""
    w_local, loss, stats = local_update(
        loss_fn, optimizer, w_global, batches, rng, cfg.local_steps,
        unroll=cfg.unroll, micro_batches=cfg.micro_batches,
        step_offset=step_offset)
    if scheduling.Policy(cfg.policy) == scheduling.Policy.SUSTAINABLE:
        scale_i = jnp.asarray(E_i, jnp.float32)  # eq. (12)
    else:
        scale_i = jnp.asarray(1.0, jnp.float32)  # eq. (9)
    coeff = jnp.asarray(alpha_i, jnp.float32) * jnp.asarray(p_i, jnp.float32) * scale_i
    with jax.named_scope("aggregate"):
        acc = aggregation.accumulate_client_delta(acc, w_local, w_global, coeff)
    return acc, loss, stats


def finish_sequential_round(cfg: FedConfig, w_global: PyTree, acc: PyTree) -> PyTree:
    return aggregation.apply_accumulated(w_global, acc, cfg.server_lr)


def participant_round(
    loss_fn: LossFn,
    optimizer: Optimizer,
    cfg: FedConfig,
    w_global: PyTree,
    client_batches: PyTree,   # leaves: (C, T, ...) per-client per-local-step minibatches
    p: jax.Array,             # (C,) data weights p_i
    E: jax.Array,             # (C,) energy renewal cycles
    rnd: jax.Array,           # scalar int32 global round index
    rng: jax.Array,
) -> tuple[PyTree, dict[str, jax.Array]]:
    """One global round that trains the round's participants only.

    The same round as `parallel_round`, in one program: the participants'
    indices come off the round's mask in ascending order, and a loop whose
    trip count is their number ``k`` runs `sequential_client_step` for
    each (client ``i``'s batches, key ``fold_in(rng, i)``, schedule index
    ``rnd * T``), then `finish_sequential_round` applies the fp32
    accumulator.  Non-participants cost nothing, so the round's work
    follows the schedule with no capacity to choose and one compile.  A
    round with no participants runs no trip and returns ``w_global`` as it
    is.  Only the order of the fp32 sum over clients differs from
    `parallel_round`.

    Metrics: ``loss`` (mean of the participants' mean local losses, 0 with
    none), ``participants`` (the mask's sum), ``client_steps`` (``k * T``,
    the client steps computed) and the loss's stats, each summed over the
    participants' steps; empty stats add none.  Named
    scopes as in `parallel_round`; ``broadcast`` holds a participant's
    batch gather and optimizer init (DESIGN.md §12.3).
    """
    n, T = cfg.num_clients, cfg.local_steps
    with jax.named_scope("schedule"):
        mask = scheduling.participation_mask(cfg.policy, cfg.seed, rnd, E,
                                             phase=cfg.phase_array())
        (idx,) = jnp.nonzero(mask, size=n, fill_value=0)
        k = jnp.sum(mask > 0, dtype=jnp.int32)
    step_offset = jnp.asarray(rnd, jnp.int32) * T
    # the stats' shapes, from one step's loss traced abstractly: no op
    stats0 = jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        jax.eval_shape(lambda: loss_fn(
            w_global, jax.tree.map(lambda b: b[0, 0], client_batches),
            rng)[1]))

    def client(j, carry):
        acc, loss_sum, stats_sum = carry
        i = idx[j]
        with jax.named_scope("broadcast"):
            batches = jax.tree.map(
                lambda b: jax.lax.dynamic_index_in_dim(b, i, keepdims=False),
                client_batches)
        acc, loss, stats = sequential_client_step(
            loss_fn, optimizer, cfg, w_global, acc, batches, p[i], E[i],
            mask[i], jax.random.fold_in(rng, i), step_offset)
        return (acc, loss_sum + loss.astype(jnp.float32),
                jax.tree.map(jnp.add, stats_sum, stats))

    acc, loss_sum, stats = jax.lax.fori_loop(
        0, k, client,
        (aggregation.zeros_like_fp32(w_global), jnp.float32(0), stats0))
    with jax.named_scope("aggregate"):
        # w + 0 would turn a -0.0 weight into +0.0: keep w as it is
        w_new = jax.tree.map(lambda new, old: jnp.where(k > 0, new, old),
                             finish_sequential_round(cfg, w_global, acc),
                             w_global)
    metrics = {
        "loss": loss_sum / jnp.maximum(k, 1),
        "participants": jnp.sum(mask),
        "client_steps": k * T,
        **stats,
    }
    return w_new, metrics


def run_rounds(
    loss_fn: LossFn,
    optimizer: Optimizer,
    cfg: FedConfig,
    w0: PyTree,
    batch_fn: Callable[[int], PyTree],   # round -> (C, T, ...) batches
    p: jax.Array,
    E: jax.Array,
    num_rounds: int,
    rng: jax.Array,
    eval_fn: Callable[[PyTree], dict] | None = None,
    eval_every: int = 0,
    round_fn=None,
) -> tuple[PyTree, list[dict]]:
    """Host-side driver: iterate ``parallel_round`` for ``num_rounds`` rounds.

    ``batch_fn`` is called on the host each round (data pipeline); the round
    itself is jitted once.  Returns final global model + per-round metrics.
    """
    if round_fn is None:
        round_fn = jax.jit(partial(parallel_round, loss_fn, optimizer, cfg))
    history: list[dict] = []
    w = w0
    for r in range(num_rounds):
        batches = batch_fn(r)
        w, metrics = round_fn(w, batches, p, E,
                              jnp.asarray(r, jnp.int32), jax.random.fold_in(rng, r))
        rec = {"round": r, **{k: float(v) for k, v in metrics.items()}}
        if eval_fn is not None and eval_every and (r + 1) % eval_every == 0:
            rec.update({k: float(v) for k, v in eval_fn(w).items()})
        history.append(rec)
    return w, history
