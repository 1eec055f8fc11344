"""Sharding rules: name-based axis placement with divisibility-safe fallbacks.

Every tensor layout decision in the system goes through this module
(DESIGN.md §3.3 has the full rule table).  The core contract:

* **Pure spec construction.** ``_param_spec``/``param_specs``/``batch_spec``/
  ``cache_specs`` only read ``mesh.shape`` (axis name -> size) and
  ``mesh.axis_names``, so they work against any mesh-shaped object — including
  fakes with no devices — and never touch jax device state.  Only the
  ``NamedSharding`` wrappers (``param_shardings``, ``shardings_of``,
  ``stacked_constrainer``) need a real ``jax.sharding.Mesh``.
* **Divisibility safety.** A mesh axis is placed on a tensor dim only if the
  axis size divides that dim; otherwise the rule falls through to the next
  candidate dim and ultimately to replication.  No spec produced here can make
  GSPMD pad or fail — e.g. qwen's 20 heads don't divide a 16-way model axis,
  but the flat 20*128 = 2560 head x head_dim projection dim does; granite's
  49155-entry vocab doesn't, so its token embedding shards on d_model instead.
* **FSDP composes by prepending data axes** onto the first free (divisible)
  non-stacked dim, so weight-sharded (model) and weight-gathered (data) axes
  coexist on different dims of the same tensor.

Mesh convention: the ``model`` axis is tensor parallelism; every other axis
(``data``, and ``pod`` ahead of it on multi-pod meshes) is data/client
parallelism, reported by ``data_axes`` in mesh order.
"""
from __future__ import annotations

from typing import Any, Sequence

import jax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

PyTree = Any

MODEL_AXIS = "model"

# Leaf names that are always replicated: norm scales/biases (QK-norm's
# too), projection biases, per-head scalar vectors (A_log, D, dt_bias,
# lambda).  They are tiny, and replicating them keeps elementwise ops
# collective-free.
_REPLICATED = {
    "scale", "bias", "norm", "lam", "q_norm", "k_norm",
    "b", "bq", "bk", "bv", "bi", "bo", "ba", "conv_b",
    "a_log", "d", "dt_bias",
}

# name -> (core rank, candidate core dims for the model axis, by preference).
# Dims left of the core rank are leading stack axes (layers/blocks) and are
# never sharded over the model axis.  Projections that *produce* the hidden
# features are column-parallel (shard the output dim); projections that
# *consume* them (wo / out_proj) are row-parallel (shard the input dim), so a
# column-parallel -> row-parallel pair needs a single all-reduce.
_MATRIX_RULES = {
    "wq": (2, (1, 0)),
    "wk": (2, (1, 0)),
    "wv": (2, (1, 0)),
    "wi": (2, (1, 0)),
    "wx": (2, (1, 0)),
    "wy": (2, (1, 0)),
    "wa": (2, (1, 0)),
    "w": (2, (1, 0)),
    "in_proj": (2, (1, 0)),
    "router": (2, (1, 0)),
    "wo": (2, (0, 1)),
    "out_proj": (2, (0, 1)),
    "conv_w": (2, (0,)),          # depthwise conv: channels only, never taps
    # embeddings: vocab-parallel when the vocab divides, d_model otherwise
    "tok": (2, (0, 1)),
    "pos": (2, (0, 1)),
    "unembed": (2, (1, 0)),       # output side: padded vocab dim first
}

# MoE experts under a "moe" parent: expert-parallel when E divides the model
# axis, otherwise fall back to the ff dim (classic megablocks-style TP).
_MOE_RULES = {
    "wi": (3, (0, 2, 1)),         # (E, d_model, ff*)
    "wo": (3, (0, 1, 2)),         # (E, ff, d_model)
}


# ------------------------------------------------------------- mesh intro --
def mesh_axis_size(mesh, axes) -> int:
    """Product of the named mesh axes' sizes (str, None, or sequence)."""
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    sizes = dict(mesh.shape)
    n = 1
    for a in axes:
        n *= int(sizes[a])
    return n


def data_axes(mesh) -> tuple[str, ...]:
    """All non-model mesh axes, in mesh order (client/data parallel axes)."""
    return tuple(a for a in mesh.axis_names if a != MODEL_AXIS)


def _divides(dim: int, mesh, axes) -> bool:
    n = mesh_axis_size(mesh, axes)
    return n > 0 and dim % n == 0


def _progressive_data(dim: int, mesh, daxes: Sequence[str]):
    """Largest suffix of the data axes whose product divides ``dim``.

    Dropping *leading* axes first means a batch that fits a single pod's data
    axis still shards there on a multi-pod mesh (pod-replicated) instead of
    falling all the way back to full replication.
    """
    for k in range(len(daxes)):
        cand = tuple(daxes[k:])
        if dim and _divides(dim, mesh, cand):
            return cand if len(cand) > 1 else cand[0]
    return None


def _key_names(path) -> tuple[str, ...]:
    """jax tree-path entries (DictKey/GetAttrKey/SequenceKey) -> name strings."""
    names = []
    for k in path:
        if hasattr(k, "key"):
            names.append(str(k.key))
        elif hasattr(k, "name"):
            names.append(str(k.name))
        elif hasattr(k, "idx"):
            names.append(str(k.idx))
        else:
            names.append(str(k))
    return tuple(names)


# ------------------------------------------------------------ param rules --
def _param_spec(path, shape, mesh, model_axis=MODEL_AXIS,
                fsdp_axes: Sequence[str] = ()) -> P:
    """PartitionSpec for one parameter leaf.

    ``path``: tuple of tree key names (e.g. ``("layers", "attn", "wq")``);
    ``shape``: the leaf's shape; ``model_axis``: mesh axis for tensor
    parallelism (None = dp mode, weights replicated over the model axis);
    ``fsdp_axes``: data axes to additionally shard every weight over (ZeRO-3
    style), placed as a prepended tuple on the first free divisible dim.
    """
    names = tuple(str(n).lower() for n in path)
    name = names[-1] if names else ""
    ndim = len(shape)
    entries: list = [None] * ndim

    replicated = name in _REPLICATED
    if not replicated:
        if "moe" in names and name in _MOE_RULES:
            core_rank, candidates = _MOE_RULES[name]
        elif name in _MATRIX_RULES:
            core_rank, candidates = _MATRIX_RULES[name]
        else:
            # unknown leaf: try dims from the last (feature) dim backwards
            core_rank, candidates = ndim, tuple(range(ndim - 1, -1, -1))
        lead = max(ndim - core_rank, 0)

        if model_axis is not None:
            for c in candidates:
                dim = lead + c
                if dim < ndim and shape[dim] > 1 \
                        and _divides(shape[dim], mesh, model_axis):
                    entries[dim] = model_axis
                    break

        if fsdp_axes:
            fsdp = tuple(fsdp_axes)
            placed = False
            for dim in range(lead, ndim):
                if entries[dim] is None and shape[dim] > 1 \
                        and _divides(shape[dim], mesh, fsdp):
                    entries[dim] = fsdp
                    placed = True
                    break
            if not placed:
                # compose: prepend the data axes onto the model-sharded dim
                for dim in range(lead, ndim):
                    if entries[dim] == model_axis and _divides(
                            shape[dim], mesh, fsdp + (model_axis,)):
                        entries[dim] = fsdp + (model_axis,)
                        break

    return P(*entries)


def param_specs(params: PyTree, mesh, model_axis=MODEL_AXIS,
                fsdp: bool = False) -> PyTree:
    """PartitionSpec tree for a parameter (or optimizer-state) pytree.

    Works on concrete arrays and ``ShapeDtypeStruct`` trees alike; with
    ``fsdp=True`` every weight is additionally sharded over the mesh's data
    axes (sequential federated mode: one client owns the whole mesh).
    """
    fsdp_axes = data_axes(mesh) if fsdp else ()

    def leaf(path, x):
        return _param_spec(_key_names(path), tuple(x.shape), mesh,
                           model_axis=model_axis, fsdp_axes=fsdp_axes)

    return jax.tree_util.tree_map_with_path(leaf, params)


def param_shardings(params: PyTree, mesh, model_axis=MODEL_AXIS,
                    fsdp: bool = False) -> PyTree:
    """``param_specs`` wrapped into ``NamedSharding``s (needs a real Mesh)."""
    return shardings_of(
        param_specs(params, mesh, model_axis=model_axis, fsdp=fsdp), mesh)


def shardings_of(specs: PyTree, mesh) -> PyTree:
    """Wrap a tree of PartitionSpecs into NamedShardings on ``mesh``."""
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


# ------------------------------------------------------- batches & caches --
def batch_spec(mesh, ndim: int, batch_dim: int, batch_size: int) -> P:
    """Spec for a model input: batch dim over the data axes when divisible.

    Falls back through suffixes of the data axes (multi-pod: ``(pod, data)``
    -> ``(data,)``) and finally to replication (e.g. the batch-1 long-context
    decode shape).
    """
    entries: list = [None] * ndim
    if 0 <= batch_dim < ndim:
        entries[batch_dim] = _progressive_data(batch_size, mesh,
                                               data_axes(mesh))
    return P(*entries)


def cache_specs(cache: PyTree, mesh) -> PyTree:
    """Specs for serving caches: leaves shaped (L, B, S, heads, head_dim) or
    similar (L, B, *state) SSM/conv states.

    Axis 0 is the layer stack and axis 1 the batch (data axes); the sequence
    axis is never sharded (ring writes are position-local); the model axis
    goes on the kv-head dim when it divides, else the trailing feature dim
    (e.g. recurrentgemma's single kv head with head_dim 256).
    """
    daxes = data_axes(mesh)

    def spec(x):
        shape = tuple(x.shape)
        nd = len(shape)
        entries: list = [None] * nd
        if nd >= 2:
            entries[1] = _progressive_data(shape[1], mesh, daxes)
        for dim in (nd - 2, nd - 1):
            if dim >= 2 and entries[dim] is None and shape[dim] > 1 \
                    and _divides(shape[dim], mesh, MODEL_AXIS):
                entries[dim] = MODEL_AXIS
                break
        return P(*entries)

    return jax.tree.map(spec, cache)


# ------------------------------------------------------------ fleet state --
def fleet_spec(mesh, ndim: int = 1) -> P:
    """Spec for one fleet-state leaf: client dim 0 over ALL data axes.

    The fleet simulator's state is flat ``(N, ...)`` pytrees (battery charge,
    arrival-process state, per-client parameters).  There is exactly one rule:
    dim 0 — the client axis — is sharded over the mesh's full data-axis tuple
    (`data_axes`, so ``(pod, data)`` composes on multi-pod meshes) and every
    trailing dim is replicated.  Divisibility is guaranteed by the caller
    padding N up to a multiple of the data-axis product
    (`energy.fleet.simulate_fleet`'s padding rule, DESIGN.md §7), never by
    falling back to replication — a fleet that silently replicated 1e8
    clients per host would defeat the point.
    """
    daxes = data_axes(mesh)
    lead = daxes if len(daxes) > 1 else daxes[0]
    return P(lead, *([None] * (ndim - 1)))


def fleet_specs(tree: PyTree, num_clients: int, mesh) -> PyTree:
    """Spec tree for a fleet pytree: leaves with a leading client dim of size
    ``num_clients`` get `fleet_spec`; everything else (scalar battery fields,
    shared constants) is replicated."""
    def leaf(x):
        shape = tuple(getattr(x, "shape", ()))
        if shape and shape[0] == num_clients:
            return fleet_spec(mesh, len(shape))
        return P()

    return jax.tree.map(leaf, tree)


# ------------------------------------------------- stacked (parallel) mode --
def stacked_constrainer(mesh, model_axis=MODEL_AXIS, zero_axis=None):
    """Constraint fn for client-stacked state in the parallel federated round.

    The returned callable maps a pytree whose leaves carry a leading client
    axis ``C`` (stacked local params / optimizer moments, see
    ``core.round.parallel_round``) to the same tree with every leaf pinned to
    ``P((data axes), *param rule spec)``: the client axis lives on the mesh's
    data axes, so the local phase is communication-free and the final
    aggregation lowers to one reduction over the client axis.

    ``zero_axis``: ZeRO-1 — additionally shard each (otherwise free) trailing
    dim of the optimizer state over this axis when divisible (dp-mode, where
    the model axis is idle for weights).  Scalar leaves (step counters) pass
    through untouched.
    """
    daxes = data_axes(mesh)
    lead = daxes if len(daxes) > 1 else daxes[0]

    def constrain(tree: PyTree) -> PyTree:
        def leaf(path, x):
            if x.ndim == 0:
                return x
            spec = _param_spec(_key_names(path), tuple(x.shape)[1:], mesh,
                               model_axis=model_axis)
            entries = [lead] + list(spec)
            if zero_axis is not None:
                for dim in range(x.ndim - 1, 0, -1):
                    if entries[dim] is None and x.shape[dim] > 1 \
                            and _divides(x.shape[dim], mesh, zero_axis):
                        entries[dim] = zero_axis
                        break
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, P(*entries)))

        return jax.tree_util.tree_map_with_path(leaf, tree)

    return constrain
