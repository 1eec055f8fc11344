"""Unified model API: ``get_model(cfg)`` dispatches on ``cfg.family``.

Every family exposes:
  init_params(cfg, rng) -> params
  forward(cfg, params, batch, impl) -> (logits, aux)
  loss_fn(cfg, params, batch, rng, impl) -> (scalar, stats)
      stats: a dict of the step's counts (the dropless expert layer's,
      `moe.STATS`); empty for families that count nothing
  init_cache(cfg, batch, cache_len) -> cache            (decoder families)
  prefill(cfg, params, batch, cache_len, impl, window) -> (logits, cache)
  decode_step(cfg, params, token, cache, pos, ring, window, impl) -> (logits, cache)
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable

import jax

from repro.configs.base import ModelConfig
from repro.models import cnn, encdec, rglru, ssm, transformer


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig
    init_params: Callable
    forward: Callable
    loss_fn: Callable
    init_cache: Callable | None = None
    prefill: Callable | None = None
    decode_step: Callable | None = None

    def num_params(self, params) -> int:
        return sum(x.size for x in jax.tree.leaves(params))


_FAMILY_MODULES = {
    "dense": transformer,
    "moe": transformer,
    "vlm": transformer,
    "ssm": ssm,
    "hybrid": rglru,
    "encdec": encdec,
    "cnn": cnn,
}


def get_model(cfg: ModelConfig) -> Model:
    mod = _FAMILY_MODULES[cfg.family]
    kw = dict(
        cfg=cfg,
        init_params=partial(mod.init_params, cfg),
        forward=partial(mod.forward, cfg),
        loss_fn=partial(mod.loss_fn, cfg),
    )
    if hasattr(mod, "init_cache"):
        kw.update(
            init_cache=partial(mod.init_cache, cfg),
            prefill=partial(mod.prefill, cfg),
            decode_step=partial(mod.decode_step, cfg),
        )
    return Model(**kw)
