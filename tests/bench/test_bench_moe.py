"""The OLMoE cell on the CPU at a small size: the sound run is correct
against `bench/ref/moe.py` under the committed limits, the planted faults
and the control are not, the FLOP count matches a hand count, and the
expert layer's metric readers read the launcher's counters."""
from __future__ import annotations

import time
import types

import jax
import pytest

from _bench_cells import ROOT, small_cell
from test_bench_correct import SEED, FAULTS
from bench import reference as R
from bench.harness import Context, load_cell, run_cell
from bench.metrics import moe_expert_mfu, moe_load_imbalance
from bench.ref import common
from bench.runners import fl_round

CELL = "olmoe-1b-7b.fl-paper"


def _run(cell):
    result, code = run_cell(cell, SEED, 0.0, False, time.perf_counter(),
                            require_chip=False)
    assert code == 0
    return result


def test_sound_run_is_correct():
    cell = small_cell(CELL)
    assert cell.config["router_experts"] > cell.config["num_experts"]
    result = _run(cell)
    assert result["correct"], result["checks"]
    assert result["attempted"] >= 20 and result["failed"] == 0
    assert result["metrics"] == {}          # no device metric off the chip


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_round_is_not_correct(fault, monkeypatch):
    real = fl_round.setup_training

    def broken(*args, **kwargs):
        run = real(*args, **kwargs)
        FAULTS[fault](run, kwargs)
        return run

    monkeypatch.setattr(fl_round, "setup_training", broken)
    result = _run(small_cell(CELL))
    assert not result["correct"], result["checks"]


def test_control_is_not_correct():
    """The reference with fp8 matmuls against the float32 reference, in
    the cell's bf16: some number passes its limit."""
    cell = small_cell(CELL, dtype=load_cell(CELL).config["dtype"])
    tr = cell.traffic
    _, dkey = fl_round.keys(SEED)
    make = fl_round.batch_maker(cell.config, tr, dkey)
    masks = R.schedule(tr["policy"], tr["schedule_seed"], 3,
                       fl_round.energy_cycles(tr))
    ref = fl_round.reference_readings(cell, SEED, make, masks)
    ctl = fl_round.reference_readings(cell, SEED, make, masks,
                                      ein=common.ein_fp8)
    w0 = jax.eval_shape(lambda: R.family(cell.config).init(
        cell.config, jax.random.PRNGKey(0)))
    numbers = R.compare(ctl, ref, R.leaf_names(w0))
    assert any(numbers[k] > lim for k, lim in cell.limits.items()
               if k in numbers), (numbers, cell.limits)


@pytest.mark.parametrize("seq,per_token", [
    # a layer: q, k, v, o 33,554,432; scores 4 x seq x 2048; router
    # 262,144; 2 held assignments x 6 x 2048 x 1024 = 25,165,824; 4
    # layers, then the head 2 x 2048 x 12,576 = 51,511,296
    (1024, 4 * (33_554_432 + 8_388_608 + 262_144 + 25_165_824) + 51_511_296),
    (4096, 4 * (33_554_432 + 33_554_432 + 262_144 + 25_165_824)
     + 51_511_296)])
def test_flops_by_hand(seq, per_token):
    import json

    from bench.flops import moe

    cfg = json.loads((ROOT / "bench" / "configs" / "olmoe-1b-7b.json")
                     .read_text())
    assert moe.forward(cfg, seq) == per_token * seq
    assert moe.train(cfg, seq) == 3 * moe.forward(cfg, seq)


def test_expert_layer_readers(monkeypatch):
    """moe_expert_mfu scales the counted FLOPs to the window by its share
    of the rounds; moe_load_imbalance is max over mean; both read nothing
    where the program keeps no such counters."""
    from repro.obs import metrics

    ctx = Context(types.SimpleNamespace(window_s=2.0), {"rounds": 20},
                  {"bf16_flops_per_s": 1e12})
    monkeypatch.setattr(metrics, "_COUNTER_TOTALS", {"train.rounds": 40})
    assert moe_expert_mfu.read(ctx) is None
    assert moe_load_imbalance.read(ctx) is None
    metrics._COUNTER_TOTALS.update({
        "train.moe_expert_flops": 4e11, "train.moe_load_max": 300.0,
        "train.moe_load_mean": 200.0})
    # 3 x 4e11 x 20/40 over 2 s = 3e11 FLOP/s, 30% of 1e12
    assert moe_expert_mfu.read(ctx) == pytest.approx(30.0)
    assert moe_load_imbalance.read(ctx) == pytest.approx(1.5)
