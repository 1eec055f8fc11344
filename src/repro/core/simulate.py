"""Host-side faithful simulation of Algorithm 1 and the paper's benchmarks.

Unlike the mesh-parallel round engine (round.py), this driver computes local
updates ONLY for scheduled participants — exactly the paper's Algorithm 1
control flow — which is also what makes CPU reproduction of Figure 1
tractable (participants are ~1/3 of clients under the paper's energy profile).

Per round r:
  alpha   = participation_mask(policy, seed, r, E, phase)
  for i with alpha_i = 1:   w_i <- T local optimizer steps from w   (eq. 7)
  w <- w + sum_i alpha_i p_i scale_i (w_i - w)                      (eqs. 9/12/13)

Two scheduling sources:

* **paper-faithful** (default) — stateless `scheduling.participation_mask`
  from assumed renewal cycles ``E`` (and optional ``cfg.phase`` offsets).
* **energy-closed-loop** — pass ``energy=repro.energy.fleet.EnergyLoop(...)``:
  masks come from realized stochastic harvests gated by battery state, and
  per-round energy telemetry (``energy_*`` keys) lands in the history.

With a battery-aware server controller attached
(``EnergyLoop(..., controller=repro.energy.control.ServerController(...))``)
the loop closes on the *server* side too: each round the driver reads the
controller's adapted local-step count ``T`` and per-group cycles ``E``
(``ctrl_T``/``ctrl_E_mean`` land in the history), then feeds the round's
realized telemetry back.  Each distinct ``T`` jits its own local-update
program once (bounded by ``ControlBounds.t_max - t_min``); the Theorem-1 LR
schedule offset advances by the *realized* cumulative local steps.
"""
from __future__ import annotations

import dataclasses
import inspect
import time
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import aggregation, scheduling
from repro.core.round import FedConfig, local_update
from repro.optim import Optimizer

PyTree = Any


def _accepts_num_steps(batch_fn: Callable) -> bool:
    """True if ``batch_fn`` can take a third (num_steps) positional arg —
    decided once from its signature, never from whether a controller happens
    to be attached, so a provider's contract is stable either way."""
    try:
        params = list(inspect.signature(batch_fn).parameters.values())
    except (TypeError, ValueError):   # builtins / C callables: assume legacy
        return False
    if any(p.kind is inspect.Parameter.VAR_POSITIONAL for p in params):
        return True
    positional = [p for p in params if p.kind in
                  (inspect.Parameter.POSITIONAL_ONLY,
                   inspect.Parameter.POSITIONAL_OR_KEYWORD)]
    return len(positional) >= 3


@dataclasses.dataclass
class SimResult:
    params: PyTree
    history: list[dict]

    def curve(self, key: str) -> tuple[np.ndarray, np.ndarray]:
        xs = [h["round"] for h in self.history if key in h]
        ys = [h[key] for h in self.history if key in h]
        return np.asarray(xs), np.asarray(ys)


def simulate(
    loss_fn: Callable,
    optimizer: Optimizer,
    cfg: FedConfig,
    w0: PyTree,
    batch_fn: Callable[[int, int], PyTree],  # (round, client) -> (T, B, ...) batches
    #   a provider accepting a third positional arg is called as
    #   (round, client, num_steps) — required when an adaptive controller
    #   varies T, since the batch leading dim must track it
    p: np.ndarray,
    E: np.ndarray,
    num_rounds: int,
    rng: jax.Array,
    eval_fn: Callable[[PyTree], dict] | None = None,
    eval_every: int = 0,
    verbose: bool = False,
    energy=None,   # repro.energy.fleet.EnergyLoop -> closed-loop scheduling
) -> SimResult:
    """Run ``num_rounds`` global rounds of Algorithm 1 / a benchmark policy."""
    locals_by_T: dict[int, Callable] = {}

    def local_for(T: int) -> Callable:
        # one jitted program per distinct local-step count: the static
        # schedule uses exactly one; an adaptive controller a bounded handful
        if T not in locals_by_T:
            locals_by_T[T] = jax.jit(partial(
                local_update, loss_fn, optimizer, num_steps=T,
                unroll=cfg.unroll, micro_batches=cfg.micro_batches))
        return locals_by_T[T]

    E = np.asarray(E)
    p = np.asarray(p)
    phase = cfg.phase_array()
    ctrl = getattr(energy, "controller", None) if energy is not None else None
    if energy is not None:
        energy.reset()
    # batch_fn contract: (round, client) normally; providers that accept a
    # third parameter are handed the round's (possibly adapted) step count
    batch_takes_steps = _accepts_num_steps(batch_fn)
    static_scale = np.asarray(scheduling.aggregation_scale(cfg.policy, E))

    w = w0
    history: list[dict] = []
    t0 = time.time()
    local_steps_done = 0  # realized cumulative local steps (LR-schedule offset)
    for r in range(num_rounds):
        T_r = ctrl.T if ctrl is not None else cfg.local_steps
        E_r = np.asarray(ctrl.client_E(cfg.num_clients)) if ctrl is not None \
            else E
        scale = (np.asarray(scheduling.aggregation_scale(cfg.policy, E_r))
                 if ctrl is not None else static_scale)
        if energy is not None:
            mask, estats = energy.step(cfg.policy, cfg.seed, r, E_r,
                                       T_r, phase=phase)
        else:
            mask, estats = np.asarray(scheduling.participation_mask(
                cfg.policy, cfg.seed, jnp.int32(r), jnp.asarray(E_r),
                phase=phase)), None
        parts = np.nonzero(mask)[0]
        rec = {"round": r, "participants": int(len(parts))}
        if estats is not None:
            rec.update({f"energy_{k}": v for k, v in estats.items()})
        if ctrl is not None:
            rec["ctrl_T"] = T_r
            rec["ctrl_E_mean"] = float(E_r.mean())
        if len(parts):
            acc = aggregation.zeros_like_fp32(w)
            losses = []
            local = local_for(T_r)
            for i in parts:
                key = jax.random.fold_in(jax.random.fold_in(rng, r), int(i))
                batch = (batch_fn(r, int(i), T_r) if batch_takes_steps
                         else batch_fn(r, int(i)))
                w_i, loss, _ = local(w, batch, key,
                                  step_offset=jnp.int32(local_steps_done))
                coeff = float(p[i] * scale[i])
                acc = aggregation.accumulate_client_delta(acc, w_i, w, coeff)
                losses.append(float(loss))
            w = aggregation.apply_accumulated(w, acc, cfg.server_lr)
            rec["loss"] = float(np.mean(losses))
        local_steps_done += T_r
        if ctrl is not None and estats is not None:
            ctrl.update(estats, cfg.num_clients)
        if eval_fn is not None and eval_every and \
                ((r + 1) % eval_every == 0 or r == num_rounds - 1):
            rec.update({k: float(v) for k, v in eval_fn(w).items()})
        history.append(rec)
        if verbose and (r % max(1, num_rounds // 20) == 0 or r == num_rounds - 1):
            msg = " ".join(f"{k}={v:.4f}" for k, v in rec.items()
                           if isinstance(v, float))
            print(f"[{cfg.policy}] round {r:4d} |S|={rec['participants']:2d} "
                  f"{msg} ({time.time()-t0:.0f}s)", flush=True)
    return SimResult(w, history)
