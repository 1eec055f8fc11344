"""The launcher's round loop under its own instrumentation: one
``train.round`` span a round tiled by its five children, the counters of
computed and useful client steps, garbage collections as spans, the
counters on the closing ``metrics`` event, and the named scopes the round
engine and the models put on the round program's ops."""
from __future__ import annotations

import gc
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_smoke_config
from repro.launch.train import setup_training, train_rounds
from repro.obs import Obs, load_events, recent_spans, reset_spans

C, T = 3, 2
CHILDREN = ["train.batch", "train.args", "train.dispatch", "train.fetch",
            "train.after_round"]
SCOPES = ["schedule", "broadcast", "local_step", "optimizer", "aggregate",
          "embed", "attention", "mlp", "head"]
MOE_SCOPES = ["router", "dispatch", "experts", "combine"]


def _run(arch="granite-3-2b"):
    return setup_training(get_smoke_config(arch), clients=C, local_steps=T,
                          batch=2, seq=16, taus=(1, 2, 4),
                          policy="sustainable", optimizer="sgd", lr=0.01)


@pytest.fixture(scope="module")
def traced():
    """Three rounds of the smoke granite run; gc.collect() in round 1's
    hook."""
    run = _run()
    reset_spans()

    def hook(r, w, history):
        if r == 1:
            gc.collect()

    w, hist = train_rounds(run, run.init_params(), 3, after_round=hook)
    return run, hist, recent_spans()


def test_each_round_is_one_span_tiled_by_five_children(traced):
    _, _, recs = traced
    rounds = [s for s in recs if s.name == "train.round"]
    assert [s.round for s in rounds] == [0, 1, 2]
    for rnd in rounds:
        assert rnd.parent is None
        kids = [s for s in recs if s.parent == "train.round"
                and s.round == rnd.round]
        assert [k.name for k in kids] == CHILDREN
        assert all(rnd.start <= k.start <= k.end <= rnd.end for k in kids)
        assert all(a.end <= b.start for a, b in zip(kids, kids[1:]))
        # the children leave only bookkeeping of the loop itself uncovered
        covered = sum(k.end - k.start for k in kids)
        assert covered >= 0.9 * (rnd.end - rnd.start)


def test_counters_count_computed_and_participating_client_steps(traced):
    """The round trains its participants only: every computed client step
    is a participant's."""
    run, hist, _ = traced
    c = {k: v.value for k, v in run.counters.items()}
    assert c["train.rounds"] == 3
    assert c["train.client_steps_useful"] == \
        sum(int(h["participants"]) * T for h in hist)
    assert 0 < c["train.client_steps_useful"] < 3 * C * T
    assert c["train.client_steps_computed"] == c["train.client_steps_useful"]


def test_a_collection_is_a_span_of_the_round_that_ran_it(traced):
    run, _, recs = traced
    gcs = [s for s in recs if s.name == "train.gc" and s.round == 1
           and s.parent == "train.after_round"]
    assert gcs
    assert run.counters["train.gc_collections"].value >= len(gcs)
    assert run.counters["train.gc_ms"].value >= \
        1e3 * sum(s.end - s.start for s in gcs) * 0.999


def test_obs_gets_span_events_and_counters_on_close(tmp_path):
    run = _run()
    obs = Obs(tmp_path)
    train_rounds(run, run.init_params(), 2, obs=obs)
    obs.close()
    events = load_events(tmp_path / "events.jsonl")
    spans = [e["name"] for e in events if e["kind"] == "span"]
    assert spans.count("train.round") == 2
    assert all(spans.count(n) == 2 for n in CHILDREN)
    assert sum(e["kind"] == "round" for e in events) == 2
    counters = [e for e in events if e["kind"] == "metrics"][-1]["counters"]
    assert counters["train.rounds"] == 2
    assert counters["train.client_steps_computed"] == T * sum(
        e["participants"] for e in events if e["kind"] == "round")
    assert set(counters) >= set(run.counters)


@pytest.mark.parametrize("arch", ["granite-3-2b", "whisper-tiny",
                                  "olmoe-1b-7b"])
def test_round_program_carries_every_scope(arch):
    """Transformer, encoder-decoder and the dropless expert layer: each
    scope names some op of the lowered round, and in the compiled round the
    backward ops of each model scope keep it (under
    ``transpose(jvp(...))``); the expert layer's own scopes sit inside
    ``mlp``."""
    run = _run(arch)
    assert run.cfg.family == {"granite-3-2b": "dense",
                              "whisper-tiny": "encdec",
                              "olmoe-1b-7b": "moe"}[arch]
    w = jax.eval_shape(run.init_params)
    args = (run.batch_fn(0), run.p, run.E, jnp.int32(0),
            jax.random.PRNGKey(0))
    text = run.round_fn.lower(w, *args).as_text(debug_info=True)
    names = set(re.findall(r'loc\("([^"]*)"', text))
    for scope in SCOPES:
        pat = re.compile(rf"(^|[/(]){scope}([/)]|$)")
        assert any(pat.search(n) for n in names), scope
    compiled = set(re.findall(r'op_name="([^"]*)"',
                              run.round_fn.lower(w, *args).compile()
                              .as_text()))
    backward = [n[n.index("transpose(jvp("):] for n in compiled
                if "transpose(jvp(" in n]
    for scope in ("embed", "attention", "mlp", "head"):
        pat = re.compile(rf"[/(]{scope}([/)]|$)")
        assert any(pat.search(n) for n in backward), scope
    if run.cfg.family == "moe":
        for scope in MOE_SCOPES:
            assert any(f"mlp/{scope}/" in n for n in compiled), scope


@pytest.fixture(scope="module")
def moe_rounds():
    run = _run("olmoe-1b-7b")
    w, hist = train_rounds(run, run.init_params(), 3)
    return run, hist


def test_moe_counters_are_consistent(moe_rounds):
    """The dropless layer's counters over three rounds: the held
    assignments are at most every assignment of every layer and
    participant step, the busiest expert at least the mean one, and the
    expert FLOPs 6 d ff a held assignment."""
    run, hist = moe_rounds
    cfg, c = run.cfg, {k: v.value for k, v in run.counters.items()}
    steps = sum(int(h["participants"]) * T for h in hist)
    tokens = 2 * 16                              # batch x seq a step
    held = c["train.moe_assignments_held"]
    assert 0 < held <= steps * tokens * cfg.experts_per_token * cfg.num_layers
    assert c["train.moe_load_max"] >= c["train.moe_load_mean"] > 0
    assert c["train.moe_load_mean"] == pytest.approx(held / cfg.num_experts)
    assert c["train.moe_expert_flops"] == held * 6 * cfg.d_model * cfg.d_ff


@pytest.mark.parametrize("arch", ["granite-3-2b", "whisper-tiny"])
def test_rounds_without_expert_layer_count_no_moe_stats(arch):
    """No counter and no round output beyond the loss, participants and
    client steps."""
    run = _run(arch)
    assert run.stats == ()
    assert not [n for n in run.counters if n.startswith("train.moe_")]
    w = jax.eval_shape(run.init_params)
    args = (run.batch_fn(0), run.p, run.E, jnp.int32(0),
            jax.random.PRNGKey(0))
    _, metrics = jax.eval_shape(run.round_fn, w, *args)
    assert set(metrics) == {"loss", "participants", "client_steps"}
