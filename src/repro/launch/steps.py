"""Build the jitted distributed step functions + ShapeDtypeStruct input specs
for every (architecture x input-shape x mesh) combination.

Step kinds (DESIGN.md decode-shape policy):
* ``train``   -> one federated global round (the paper's Algorithm 1), in the
                 arch's fed mode: parallel (client groups = data axis) or
                 sequential (one client over the full mesh, delta accumulator).
* ``prefill`` -> serve_step prompt pass: logits + populated KV/state cache.
* ``decode``  -> serve_step for ONE token against a seq_len cache; archs
                 without native sub-quadratic serving use the sliding-window
                 serving variant for ``long_500k``.

All functions here return (fn, example_args, in_shardings, out_shardings) —
``dryrun.py`` lowers them; ``train.py``/``serve.py`` execute them.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import InputShape, ModelConfig
from repro.core import FedConfig, parallel_round, sequential_client_step
from repro.dist import sharding as shard
from repro.models import get_model
from repro.optim import adam, sgd

F32 = jnp.float32
I32 = jnp.int32


@dataclasses.dataclass(frozen=True)
class StepBundle:
    kind: str
    fn: Any
    args: tuple
    in_shardings: tuple
    out_shardings: Any
    meta: dict


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _eval_params(model):
    return jax.eval_shape(lambda: model.init_params(jax.random.PRNGKey(0)))


def _batch_struct(cfg: ModelConfig, lead: tuple[int, ...], seq: int):
    """Model-input ShapeDtypeStructs with leading dims ``lead`` (e.g. (C,T,B))."""
    b = {"tokens": _sds(lead + (seq,), I32)}
    if cfg.family == "vlm":
        b["vision_embeds"] = _sds(lead + (cfg.vision_tokens, cfg.d_model),
                                  jnp.dtype(cfg.dtype))
    if cfg.family == "encdec":
        b["frames"] = _sds(lead + (cfg.encoder_seq, cfg.d_model),
                           jnp.dtype(cfg.dtype))
    return b


def _batch_shardings(batch, mesh, batch_dim: int, batch_size: int):
    spec = {k: shard.batch_spec(mesh, v.ndim, batch_dim, batch_size)
            for k, v in batch.items()}
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec,
                        is_leaf=lambda x: isinstance(x, P))


def _repl(mesh):
    return NamedSharding(mesh, P())


def make_optimizer_for(cfg: ModelConfig, name: str | None = None,
                       lr: float = 1e-4):
    name = name or cfg.optimizer
    if name == "adam":
        return adam(lr)
    if name == "sgd_momentum":
        return sgd(lr, momentum=0.9)
    return sgd(lr)


# ------------------------------------------------------------- training ----
def build_train_step(cfg: ModelConfig, shape: InputShape, mesh,
                     local_steps: int = 5, optimizer: str | None = None,
                     unroll: bool = False) -> StepBundle:
    dp_mode = cfg.model_axis_role == "dp"
    if dp_mode and cfg.shard_logits_vocab:
        # vocab-over-model logits hint conflicts with batch-over-model
        cfg = dataclasses.replace(cfg, shard_logits_vocab=False)
    model = get_model(cfg)
    params = _eval_params(model)
    opt = make_optimizer_for(cfg, optimizer)
    daxes = shard.data_axes(mesh)
    C = shard.mesh_axis_size(mesh, daxes)        # client groups (parallel mode)
    model_axis = None if dp_mode else "model"

    def loss_fn(p, batch, rng):
        return model.loss_fn(p, batch)

    if cfg.fed_mode == "parallel":
        assert shape.global_batch % C == 0
        bc = shape.global_batch // C
        fed = FedConfig(num_clients=C, local_steps=local_steps,
                        policy="sustainable", unroll=unroll,
                        micro_batches=cfg.micro_batches)
        batches = _batch_struct(cfg, (C, local_steps, bc), shape.seq_len)
        args = (
            params,
            batches,
            _sds((C,), F32),                     # p_i
            _sds((C,), I32),                     # E_i
            _sds((), I32),                       # round index
            _sds((2,), jnp.uint32),              # rng key
        )
        p_sh = shard.param_shardings(params, mesh, model_axis=model_axis)
        if dp_mode:
            # per-client batch dim additionally split over the model axis
            # (weights replicated there: small-model regime, see DESIGN.md);
            # falls back to replicating that dim when bc is not divisible
            # (e.g. multi-pod: 256/32 groups = 8 < model=16)
            msplit = "model" if bc % shard.mesh_axis_size(mesh, "model") == 0 \
                else None
            bspec = {k: P(daxes if len(daxes) > 1 else daxes[0], None,
                          msplit, *((None,) * (v.ndim - 3)))
                     for k, v in batches.items()}
            b_sh = jax.tree.map(lambda sp: NamedSharding(mesh, sp), bspec,
                                is_leaf=lambda x: isinstance(x, P))
        else:
            b_sh = _batch_shardings(batches, mesh, 0, C)
        in_sh = (
            p_sh,
            b_sh,
            _repl(mesh), _repl(mesh), _repl(mesh), _repl(mesh),
        )
        out_sh = (p_sh, {k: _repl(mesh)
                         for k in ("loss", "participants", "client_steps")})
        zero = "model" if (dp_mode and cfg.zero_opt_over_model) else None
        fn = partial(parallel_round, loss_fn, opt, fed,
                     constrain=shard.stacked_constrainer(
                         mesh, model_axis=model_axis),
                     constrain_opt=shard.stacked_constrainer(
                         mesh, model_axis=model_axis, zero_axis=zero))
        meta = dict(mode="parallel", client_groups=C, batch_per_client=bc,
                    local_steps=local_steps, model_axis_role=cfg.model_axis_role,
                    micro_batches=cfg.micro_batches,
                    zero_opt=cfg.zero_opt_over_model)
    else:
        fed = FedConfig(num_clients=C, local_steps=local_steps,
                        policy="sustainable", mode="sequential", unroll=unroll,
                        micro_batches=cfg.micro_batches)
        batches = _batch_struct(cfg, (local_steps, shape.global_batch),
                                shape.seq_len)
        acc = jax.tree.map(lambda x: _sds(x.shape, F32), params)
        args = (
            params, acc, batches,
            _sds((), F32), _sds((), F32), _sds((), F32),  # p_i, E_i, alpha_i
            _sds((2,), jnp.uint32),
            _sds((), I32),                                # step_offset (rnd*T)
        )
        p_sh = shard.param_shardings(params, mesh, fsdp=True)
        in_sh = (
            p_sh, p_sh,
            _batch_shardings(batches, mesh, 1, shape.global_batch),
            _repl(mesh), _repl(mesh), _repl(mesh), _repl(mesh), _repl(mesh),
        )
        out_sh = (p_sh, _repl(mesh), _repl(mesh))   # acc, loss, stats
        fn = partial(sequential_client_step, loss_fn, opt, fed)
        meta = dict(mode="sequential", local_steps=local_steps,
                    micro_batches=cfg.micro_batches)

    return StepBundle("train", fn, args, in_sh, out_sh, meta)


# -------------------------------------------------------------- serving ----
def _serve_variant(cfg: ModelConfig, shape: InputShape) -> dict:
    """Decide cache length / ring / window for this (arch, shape)."""
    if cfg.family in ("ssm",):
        return dict(cache_len=0, ring=False, window=None)
    if cfg.family == "hybrid":
        return dict(cache_len=cfg.local_window, ring=True, window=None)
    native_w = cfg.sliding_window
    if native_w:
        W = min(native_w, shape.seq_len)
        return dict(cache_len=W, ring=True, window=native_w)
    if shape.seq_len > 100_000:
        # long-context serving variant for full-attention archs (DESIGN.md)
        W = cfg.serve_swa_window
        return dict(cache_len=W, ring=True, window=W, swa_variant=True)
    return dict(cache_len=shape.seq_len, ring=False, window=None)


def build_prefill_step(cfg: ModelConfig, shape: InputShape, mesh) -> StepBundle:
    model = get_model(cfg)
    params = _eval_params(model)
    var = _serve_variant(cfg, shape)
    B = shape.global_batch

    def fn(p, batch):
        return model.prefill(p, batch, cache_len=var["cache_len"] or None,
                             window=var["window"])

    batch = _batch_struct(cfg, (B,), shape.seq_len)
    args = (params, batch)
    p_sh = shard.param_shardings(params, mesh)
    logits_s, cache_s = jax.eval_shape(fn, params, batch)
    cache_sh = shard.shardings_of(shard.cache_specs(cache_s, mesh), mesh)
    in_sh = (p_sh, _batch_shardings(batch, mesh, 0, B))
    out_sh = (NamedSharding(mesh, shard.batch_spec(mesh, len(logits_s.shape), 0, B)),
              cache_sh)
    return StepBundle("prefill", fn, args, in_sh, out_sh,
                      dict(**{k: v for k, v in var.items()}))


def build_decode_step(cfg: ModelConfig, shape: InputShape, mesh) -> StepBundle:
    model = get_model(cfg)
    params = _eval_params(model)
    var = _serve_variant(cfg, shape)
    B = shape.global_batch
    cache_len = var["cache_len"] or shape.seq_len

    def fn(p, token, cache, pos):
        return model.decode_step(p, token, cache, pos, ring=var["ring"],
                                 window=var["window"])

    cache = jax.eval_shape(lambda: model.init_cache(B, cache_len))
    args = (params, _sds((B,), I32), cache, _sds((), I32))
    p_sh = shard.param_shardings(params, mesh)
    cache_sh = shard.shardings_of(shard.cache_specs(cache, mesh), mesh)
    tok_sh = NamedSharding(mesh, shard.batch_spec(mesh, 1, 0, B))
    logits_s, _ = jax.eval_shape(fn, params, _sds((B,), I32), cache,
                                 _sds((), I32))
    in_sh = (p_sh, tok_sh, cache_sh, _repl(mesh))
    out_sh = (NamedSharding(mesh, shard.batch_spec(mesh, logits_s.ndim, 0, B)),
              cache_sh)
    return StepBundle("decode", fn, args, in_sh, out_sh,
                      dict(cache_len=cache_len, **{k: v for k, v in var.items()
                                                   if k != "cache_len"}))


def build_step(cfg: ModelConfig, shape: InputShape, mesh, **kw) -> StepBundle:
    if shape.kind == "train":
        return build_train_step(cfg, shape, mesh, **kw)
    if shape.kind == "prefill":
        return build_prefill_step(cfg, shape, mesh)
    if shape.kind == "decode":
        return build_decode_step(cfg, shape, mesh)
    raise ValueError(shape.kind)
