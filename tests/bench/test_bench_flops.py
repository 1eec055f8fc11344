"""The benchmark's FLOP counts against XLA's own count of an un-rematted,
unrolled forward of each family's smoke configuration.  XLA also counts
the elementwise work (norms, activations, softmax) that the model-FLOP
convention leaves out, so the count from shapes sits a little below."""
from __future__ import annotations

import dataclasses
import importlib

import jax
import jax.numpy as jnp
import pytest

import _bench_cells  # puts the repo root on the path


@pytest.mark.parametrize("arch", ["granite-3-2b", "whisper-tiny"])
@pytest.mark.parametrize("seq", [32, 64])
def test_forward_flops_match_xla(arch, seq):
    from repro.configs import get_smoke_config
    from repro.models import get_model

    mc = dataclasses.replace(get_smoke_config(arch), remat=False,
                             scan_unroll=True)
    cfg = dataclasses.asdict(mc)
    flops = importlib.import_module(f"bench.flops.{mc.family}")
    model = get_model(mc)
    batch = {"tokens": jax.ShapeDtypeStruct((1, seq), jnp.int32)}
    if mc.family == "encdec":
        batch["frames"] = jax.ShapeDtypeStruct(
            (1, mc.encoder_seq, mc.d_model), jnp.float32)
    params = jax.eval_shape(lambda: model.init_params(jax.random.PRNGKey(0)))
    cost = jax.jit(lambda p, b: model.forward(p, b)[0]).lower(
        params, batch).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    ratio = flops.forward(cfg, seq) / cost["flops"]
    assert 0.9 <= ratio <= 1.0, ratio
    assert flops.train(cfg, seq) == 3 * flops.forward(cfg, seq)


def test_published_widths_per_token():
    """The cells' configurations as run: Granite-3.0-2B at 4 layers and 1024
    tokens, ~2.164 GFLOP per token for forward and backward (6 x 344M
    matmul parameters with the tied head + attention); Whisper-tiny, 207.6
    GFLOP per sample of 1500 frames and 448 tokens."""
    import json

    from bench.flops import dense, encdec

    conf = lambda n: json.loads(
        (_bench_cells.ROOT / "bench" / "configs" / f"{n}.json").read_text())
    assert dense.train(conf("granite-3-2b"), 1024) / 1024 == pytest.approx(
        2.164e9, rel=1e-3)
    assert encdec.train(conf("whisper-tiny"), 448) == pytest.approx(
        207.6e9, rel=1e-3)
