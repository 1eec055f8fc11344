"""The plain float32 federated round, and the numbers that compare the
system's first rounds with it.

One round of Algorithm 1 (Güler & Yener): every client draws its
participation from the shared seed (lines 5-7), each participant runs
``local_steps`` steps of plain SGD from the global model on its own
batches (eq. 7), and the server adds the participants' deltas, each
weighted by its data share p_i = 1/C and, under the sustainable schedule,
scaled by its renewal cycle E_i (eqs. 12-13).  Non-participants compute
nothing here.

The reference imports nothing of the system under test.  It takes the
configuration, the traffic, the seed's weights (`ref/<family>.init`, in
the configuration's dtype) and batches, and an ``ein`` from
`ref/common.py`: float32 for the reference, fp8 for the control.
"""
from __future__ import annotations

import dataclasses
import importlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.ref import common

# leaves whose first-round change in the reference is under this share of
# the median leaf's are nought to rounding, and are not compared
NOUGHT = 1e-3


def family(cfg: dict):
    return importlib.import_module(f"bench.ref.{cfg['family']}")


def schedule(policy: str, seed: int, rounds: int, E) -> np.ndarray:
    """(rounds, C) participation masks.  Sustainable: within each window of
    E_i rounds, client i takes part once, in the round J ~ U{0..E_i-1}
    drawn from the key (PRNGKey(0) + seed) folded with i, then with the
    window index.  Always: every client every round."""
    E = jnp.asarray(E, jnp.int32)
    if policy == "always":
        return np.ones((rounds, E.shape[0]), np.float32)
    if policy != "sustainable":
        raise ValueError(f"no reference schedule for policy {policy!r}")

    @jax.jit
    def masks(r):
        def one(i, e):
            key = jax.random.fold_in(jax.random.fold_in(
                jax.random.PRNGKey(0) + jnp.asarray(seed), i), r // e)
            return r % e == jax.random.randint(key, (), 0, e)
        return jax.vmap(one)(jnp.arange(E.shape[0], dtype=jnp.int32), E)

    return np.asarray(jax.vmap(masks)(jnp.arange(rounds, dtype=jnp.int32)),
                      np.float32)


def scales(policy: str, E) -> np.ndarray:
    """eq. (12): sustainable deltas are scaled by E_i; FedAvg's by 1."""
    E = np.asarray(E, np.float32)
    return E if policy == "sustainable" else np.ones_like(E)


@dataclasses.dataclass
class Round:
    """One reference round, jitted per client.

    The arithmetic is float32 throughout (``ein`` aside).  The model's
    state keeps the dtype the configuration gives each leaf (bfloat16
    matrices, float32 norms): after each SGD step and after aggregation
    the new weights are rounded to it, as Algorithm 1 run on weights of
    that type does.
    """

    cfg: dict
    traffic: dict
    ein: object = common.ein_f32
    half_batch: bool = False     # fault: half of each batch left out

    def __post_init__(self):
        loss = partial(family(self.cfg).loss, self.cfg, ein=self.ein)
        lr = float(self.traffic["lr"])
        f32 = lambda t: jax.tree.map(lambda x: x.astype(jnp.float32), t)

        def local(w, batches):       # leaves (T, b, ...)
            def step(wc, batch):
                l, g = jax.value_and_grad(loss)(f32(wc), batch)
                return jax.tree.map(lambda p, d: (p.astype(jnp.float32)
                                                  - lr * d).astype(p.dtype),
                                    wc, g), l
            wc, losses = jax.lax.scan(step, w, batches)
            return wc, jnp.mean(losses)

        self._local = jax.jit(local)
        self._acc = jax.jit(lambda acc, wc, w, c: jax.tree.map(
            lambda a, x, y: a + c * (x.astype(jnp.float32)
                                     - y.astype(jnp.float32)), acc, wc, w))
        self._apply = jax.jit(lambda w, acc: jax.tree.map(
            lambda x, a: (x.astype(jnp.float32) + a).astype(x.dtype), w, acc))

    def __call__(self, w, batches, mask, scale):
        """w: global model; batches: leaves (C, T, b, ...).  Returns (new
        global model, mean local loss over participants)."""
        C = len(mask)
        acc = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), w)
        losses = []
        for c in np.flatnonzero(mask):
            bc = jax.tree.map(lambda x: x[c], batches)
            if self.half_batch:
                bc = jax.tree.map(lambda x: x[:, :x.shape[1] // 2], bc)
            wc, l = self._local(w, bc)
            acc = self._acc(acc, wc, w, jnp.float32(scale[c] / C))
            losses.append(float(l))
        return self._apply(w, acc), (float(np.mean(losses)) if losses else 0.0)


@jax.jit
def leaf_norms(a, b):
    """L2 norm of a - b, leaf by leaf, in float32."""
    return jnp.stack([jnp.linalg.norm((x.astype(jnp.float32)
                                       - y.astype(jnp.float32)).ravel())
                      for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))])


def leaf_names(tree) -> list[str]:
    return [jax.tree_util.keystr(p)
            for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


@dataclasses.dataclass
class Readings:
    """What three rounds from the seed's weights give: the loss of each
    round, and per leaf the norms of the first round's change (the
    server's pseudo-gradient, eq. 13 with server step 1) and of the
    change after three rounds."""

    losses: list
    grad1: np.ndarray
    change3: np.ndarray


def run_reference(rnd: Round, w0, batches_of, masks, scale) -> Readings:
    w, losses = w0, []
    for r in range(3):
        w, loss = rnd(w, batches_of(r), masks[r], scale)
        losses.append(loss)
        if r == 0:
            g1 = np.asarray(leaf_norms(w, w0))
    return Readings(losses, g1, np.asarray(leaf_norms(w, w0)))


def compare(prog: Readings, ref: Readings, names: list[str]) -> dict:
    """The numbers that decide `correct`, each a worst case:

    * loss0_gap, loss1_gap, loss2_gap: |loss - reference loss| / reference
      loss in each of the three rounds (round 0 starts from the same
      weights on both sides, so its gap is the arithmetic's alone);
    * grad1_gap, change3_gap: per leaf, the gap between the two norms,
      over the larger of the reference's norm of that leaf and of the
      median leaf; the worst leaf's.  Leaves whose reference first-round
      change is under NOUGHT of the median leaf's are left out.
    """
    out = {f"loss{r}_gap": abs(a - b) / abs(b)
           for r, (a, b) in enumerate(zip(prog.losses, ref.losses))}
    keep = ref.grad1 >= NOUGHT * np.median(ref.grad1)
    for key in ("grad1", "change3"):
        p, r = getattr(prog, key), getattr(ref, key)
        gap = np.abs(p - r) / np.maximum(r, np.median(r))
        gap = np.where(keep, gap, 0.0)
        i = int(np.argmax(gap))
        out[f"{key}_gap"] = float(gap[i])
        out[f"{key}_worst_leaf"] = names[i]
    return out
