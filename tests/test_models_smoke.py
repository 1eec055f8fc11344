"""Per-architecture smoke tests (assignment requirement): a REDUCED variant of
each family runs one forward + one train step on CPU with correct shapes and
no NaNs."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ASSIGNED_ARCHS, get_smoke_config, get_config
from repro.models import get_model
from repro.optim import adam

B, S = 2, 32


def _batch(cfg, key, seq=S):
    if cfg.family == "cnn":
        return {"images": jax.random.normal(key, (B, 32, 32, 3)),
                "labels": jnp.zeros((B,), jnp.int32)}
    b = {"tokens": jax.random.randint(key, (B, seq), 0, cfg.vocab_size)}
    if cfg.family == "vlm":
        b["vision_embeds"] = jax.random.normal(
            key, (B, cfg.vision_tokens, cfg.d_model))
    if cfg.family == "encdec":
        b["frames"] = jax.random.normal(key, (B, cfg.encoder_seq, cfg.d_model))
    return b


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS + ["cifar-cnn"])
def test_smoke_forward_and_train_step(arch, rng):
    cfg = get_smoke_config(arch)
    assert cfg.d_model <= 512 and cfg.num_layers <= 5
    if cfg.num_experts:
        assert cfg.num_experts <= 4
    model = get_model(cfg)
    params = model.init_params(rng)
    batch = _batch(cfg, rng)

    logits, aux = model.forward(params, batch)
    if cfg.family == "cnn":
        assert logits.shape == (B, 10)
    else:
        assert logits.shape == (B, S, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits)).all()

    opt = adam(1e-3)
    state = opt.init(params)
    (loss0, _), grads = jax.value_and_grad(
        lambda p: model.loss_fn(p, batch), has_aux=True)(params)
    params2, _ = opt.update(grads, state, params, 0)
    loss1, _ = model.loss_fn(params2, batch)
    assert np.isfinite(float(loss0)) and np.isfinite(float(loss1))
    assert float(loss1) < float(loss0)  # one step on the same batch improves


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_full_config_matches_assignment(arch):
    """The FULL configs carry the exact assigned hyperparameters."""
    spec = {
        "internvl2-76b": dict(num_layers=80, d_model=8192, num_heads=64,
                              num_kv_heads=8, d_ff=28672, vocab_size=128256),
        "qwen1.5-4b": dict(num_layers=40, d_model=2560, num_heads=20,
                           num_kv_heads=20, d_ff=6912, vocab_size=151936,
                           qkv_bias=True),
        "granite-3-2b": dict(num_layers=40, d_model=2048, num_heads=32,
                             num_kv_heads=8, d_ff=8192, vocab_size=49155),
        "whisper-tiny": dict(num_layers=4, d_model=384, num_heads=6,
                             num_kv_heads=6, d_ff=1536, vocab_size=51865),
        "mixtral-8x7b": dict(num_layers=32, d_model=4096, num_heads=32,
                             num_kv_heads=8, d_ff=14336, vocab_size=32000,
                             num_experts=8, experts_per_token=2),
        "mamba2-1.3b": dict(num_layers=48, d_model=2048, d_ff=0,
                            vocab_size=50280, ssm_state=128),
        "granite-8b": dict(num_layers=36, d_model=4096, num_heads=32,
                           num_kv_heads=8, d_ff=14336, vocab_size=49152),
        "starcoder2-7b": dict(num_layers=32, d_model=4608, num_heads=36,
                              num_kv_heads=4, d_ff=18432, vocab_size=49152),
        "recurrentgemma-2b": dict(num_layers=26, d_model=2560, num_heads=10,
                                  num_kv_heads=1, d_ff=7680, vocab_size=256000),
        "olmoe-1b-7b": dict(num_layers=16, d_model=2048, num_heads=16,
                            num_kv_heads=16, d_ff=1024, vocab_size=50304,
                            num_experts=64, experts_per_token=8),
    }[arch]
    cfg = get_config(arch)
    for k, v in spec.items():
        assert getattr(cfg, k) == v, (arch, k, getattr(cfg, k), v)
    assert cfg.source  # every config cites its source


def test_analytic_param_counts_match_constructed():
    """cfg.num_params() (used for MODEL_FLOPS) vs actual leaf counts on the
    smoke variants — must agree within the unembed-padding slack."""
    for arch in ASSIGNED_ARCHS:
        cfg = get_smoke_config(arch)
        model = get_model(cfg)
        params = model.init_params(jax.random.PRNGKey(0))
        actual = model.num_params(params)
        analytic = cfg.num_params()
        pad_slack = cfg.d_model * 256  # unembed padding upper bound
        assert abs(actual - analytic) <= 0.12 * analytic + pad_slack, \
            (arch, actual, analytic)
