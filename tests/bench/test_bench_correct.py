"""The comparison that decides a cell's ``correct``, driven end to end on
the CPU at a small size: a sound run passes; the timed path broken
underneath (state left unchanged, half of each batch left out, the
participation mask ignored) fails; and the control, the reference
computed with fp8 matrix products, fails the cell's limits."""
from __future__ import annotations

import dataclasses
import time

import jax
import pytest

from _bench_cells import small_cell
from bench import reference as R
from bench.runners import fl_round
from bench.harness import load_cell, run_cell
from bench.ref import common

CELLS = ["whisper-tiny.fl-paper", "granite-3-2b.fl-paper",
         "granite-3-2b.fl-always"]
SEED = 2 ** 31 + 12345


def _run(cell):
    result, code = run_cell(cell, SEED, 0.0, False, time.perf_counter(),
                            require_chip=False)
    assert code == 0
    return result


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    result = _run(small_cell(name))
    assert result["correct"], result["checks"]
    assert result["attempted"] >= 20 and result["failed"] == 0
    assert result["metrics"] == {}          # no device metric off the chip


def _state_unchanged(run, launch):
    f = run.round_fn
    run.round_fn = lambda w, *a: (w, f(w, *a)[1])


def _half_batch(run, launch):
    f = run.round_fn
    run.round_fn = lambda w, b, *a: f(w, jax.tree.map(
        lambda x: x[:, :, : x.shape[2] // 2], b), *a)


def _mask_ignored(run, launch):
    """The launcher's own round, with its optimizer and learning rate, under
    the `always` policy: the one change is that the mask is not applied."""
    from functools import partial
    from repro.core import parallel_round
    from repro.launch.steps import make_optimizer_for
    fed = dataclasses.replace(run.fed, policy="always")
    opt = make_optimizer_for(run.cfg, launch["optimizer"], launch["lr"])
    run.round_fn = jax.jit(partial(
        parallel_round, lambda p, b, k: run.model.loss_fn(p, b), opt, fed))


FAULTS = {"state_unchanged": _state_unchanged, "half_batch": _half_batch,
          "mask_ignored": _mask_ignored}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_round_is_not_correct(fault, monkeypatch):
    name = "granite-3-2b.fl-paper"
    real = fl_round.setup_training

    def broken(*args, **kwargs):
        run = real(*args, **kwargs)
        FAULTS[fault](run, kwargs)
        return run

    monkeypatch.setattr(fl_round, "setup_training", broken)
    result = _run(small_cell(name))
    assert not result["correct"], result["checks"]


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name):
    """The reference with fp8 matmuls, in the program's place, against the
    float32 reference, with the cell's own dtype: some number passes its
    limit."""
    cell = small_cell(name, dtype=load_cell(name).config["dtype"])
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
    over = {k: numbers[k] for k, lim in cell.limits.items()
            if k in numbers and numbers[k] > lim}
    assert over, (numbers, cell.limits)
