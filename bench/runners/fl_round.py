"""Runner of the federated-round cells: the paper's energy-harvesting round
(Algorithm 1, `core/round.py` `parallel_round`) through the training
launcher (`launch/train.py` `setup_training` and `train_rounds`).

Set-up builds one `TrainRun`, makes the weights on the device from the
seed in one jitted call (`ref/<family>.init`), swaps the launcher's
host-side batches for batches made on the device from (seed, round), and
runs one whole schedule period through `train_rounds`: round 0 compiles
(or loads from the compile cache), rounds 0-2 are the ones compared with
the reference.  The same run and model then go on into the window, which
is whole periods of `train_rounds` until ``--seconds`` have passed.

``train_tokens_per_s`` counts the tokens that participating clients
trained on: each round, participants (from the launcher's own history) x
local steps x batch x sequence length.  Over whole periods every client
takes part exactly period / E_i times, so the count is the same for every
seed.  ``setup_s`` runs from process start to the window's start.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib
import math
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference as R
from bench.harness import Outcome
from repro.configs.base import ModelConfig
from repro.launch.train import setup_training, train_rounds

SPAN_BATCH = "bench.batch"
SPAN_ROUND = "bench.round_call"
SPAN_PERIOD = "bench.period"


def model_config(cfg: dict) -> ModelConfig:
    """The system's config from a configuration file's keys."""
    names = {f.name for f in dataclasses.fields(ModelConfig)}
    return ModelConfig(**{k: tuple(v) if isinstance(v, list) else v
                          for k, v in cfg.items() if k in names})


def keys(seed: int):
    """Weight and data keys from a seed of any size."""
    w, d = np.random.SeedSequence(seed).generate_state(2)
    return jax.random.PRNGKey(int(w)), jax.random.PRNGKey(int(d))


def energy_cycles(traffic: dict) -> np.ndarray:
    """The paper's §V profile: client i is in group i mod len(taus)."""
    taus = traffic["taus"]
    return np.asarray([taus[i % len(taus)]
                       for i in range(traffic["clients"])], np.int32)


def batch_maker(cfg: dict, traffic: dict, key):
    """round -> the (C, T, b, ...) batches of that round, made on the
    device: token ids drawn from a Zipf law over the vocabulary (exponent
    ``token_zipf``), and for the encoder-decoder family stub frame
    embeddings, normal in the configuration's dtype."""
    C, T, b, S = (traffic[k] for k in ("clients", "local_steps", "batch", "seq"))
    V = cfg["vocab_size"]

    @jax.jit
    def make(key, r):
        cdf = jnp.cumsum(jnp.arange(1, V + 1, dtype=jnp.float32)
                         ** -float(traffic["token_zipf"]))
        k1, k2 = jax.random.split(jax.random.fold_in(key, r))
        u = jax.random.uniform(k1, (C, T, b, S)) * cdf[-1]
        out = {"tokens": jnp.minimum(jnp.searchsorted(cdf, u), V - 1)
               .astype(jnp.int32)}
        if cfg["family"] == "encdec":
            out["frames"] = jax.random.normal(
                k2, (C, T, b, cfg["encoder_seq"], cfg["d_model"]),
                jnp.dtype(cfg["dtype"]))
        return out

    # the key is an argument, not a constant: one program serves every seed
    return partial(make, key)


def spanned(name: str, fn, last: dict | None = None):
    def call(*args):
        if last is not None:
            last["args"] = args
        with jax.profiler.TraceAnnotation(name):
            return fn(*args)
    return call


def launcher_run(cell):
    """The launcher's run for the cell's configuration and traffic."""
    cfg, tr = cell.config, cell.traffic
    return setup_training(
        model_config(cfg), clients=tr["clients"], local_steps=tr["local_steps"],
        batch=tr["batch"], seq=tr["seq"], taus=tuple(tr["taus"]),
        policy=tr["policy"], optimizer=tr["optimizer"], lr=tr["lr"],
        seed=tr["schedule_seed"])


def seeded(cell, seed: int, run):
    """The seed's weights, made on the device in one jitted call, and its
    batch maker; the weights must fit the system's model as they are."""
    cfg = cell.config
    wkey, dkey = keys(seed)
    w0 = jax.jit(partial(R.family(cfg).init, cfg))(wkey)
    want = jax.eval_shape(run.init_params)
    got = jax.tree.map(lambda x: (x.shape, x.dtype), w0)
    if got != jax.tree.map(lambda x: (x.shape, x.dtype), want):
        raise SystemExit(f"{cfg['family']} weights do not fit the system's "
                         f"model: {jax.tree.structure(got)} vs "
                         f"{jax.tree.structure(want)}")
    return w0, batch_maker(cfg, cell.traffic, dkey)


def first_rounds(run, w0, rounds: int):
    """Rounds 0 .. rounds-1 through the launcher, keeping what the
    reference compares: the first three losses and the per-leaf norms of
    the change after rounds 0 and 2."""
    norms = {}

    def after(r, w, history):
        if r in (0, 2):
            norms[r] = np.asarray(R.leaf_norms(w, w0))

    w, hist = train_rounds(run, w0, rounds, after_round=after)
    prog = R.Readings([h["loss"] for h in hist[:3]], norms[0], norms[2])
    return w, hist, prog


def reference_readings(cell, seed: int, make, masks, rnd=None, **variant):
    """The reference's first three rounds from the seed's weights, by the
    round ``rnd`` or a new one (``variant`` may plant the control's
    precision or a fault)."""
    cfg, tr = cell.config, cell.traffic
    wkey, _ = keys(seed)
    w0 = jax.jit(partial(R.family(cfg).init, cfg))(wkey)
    rnd = rnd or R.Round(cfg, tr, **variant)
    return R.run_reference(rnd, w0, make, masks,
                           R.scales(tr["policy"], energy_cycles(tr)))


def program_bytes(fn, args) -> int:
    """What the round program holds on the chip while it runs, by the
    compiler's `memory_analysis()`: arguments + temporaries + outputs,
    less the outputs that alias an argument.  The allocator's
    ``peak_bytes_in_use`` counts arrays only, not a program's
    temporaries."""
    if not hasattr(fn, "lower"):
        return 0
    ma = fn.lower(*args).compile().memory_analysis()
    print(f"bench-memory round program: args {ma.argument_size_in_bytes} "
          f"temp {ma.temp_size_in_bytes} output {ma.output_size_in_bytes} "
          f"alias {ma.alias_size_in_bytes}", flush=True)
    return (ma.argument_size_in_bytes + ma.temp_size_in_bytes
            + ma.output_size_in_bytes - ma.alias_size_in_bytes)


def run(cell, seed: int, seconds: float, window, *, t_start: float) -> Outcome:
    tr = cell.traffic
    T, b, S = tr["local_steps"], tr["batch"], tr["seq"]
    period = math.lcm(*tr["taus"])
    devices = jax.devices()[:cell.chips]

    run_ = launcher_run(cell)
    w0, make = seeded(cell, seed, run_)
    names = R.leaf_names(w0)
    last = {}
    round_jit = run_.round_fn
    run_.round_fn = spanned(SPAN_ROUND, round_jit, last)
    run_.batch_fn = spanned(SPAN_BATCH, make)
    w, hist, prog = first_rounds(run_, w0, period)
    del w0

    r = period
    with window() as win:
        setup_s = win.t0 - t_start
        while True:
            with jax.profiler.TraceAnnotation(SPAN_PERIOD):
                w, hist = train_rounds(run_, w, r + period, start=r,
                                       history=hist)
            r += period
            if time.perf_counter() - win.t0 >= seconds:
                break
        jax.block_until_ready(w)

    stats = [d.memory_stats() or {} for d in devices]
    arrays = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
    print(f"bench-memory after the window: {stats[0]}", flush=True)
    program = program_bytes(round_jit, last["args"])
    del w, run_, round_jit, last
    gc.collect()

    window_hist = hist[period:]
    participants = sum(h["participants"] for h in window_hist)
    flops = importlib.import_module(
        f"bench.flops.{cell.config['family']}").train(cell.config, S)
    masks = R.schedule(tr["policy"], tr["schedule_seed"], len(hist),
                       energy_cycles(tr))
    t_ref = time.perf_counter()
    ref = reference_readings(cell, seed, make, masks)
    t_ref = time.perf_counter() - t_ref
    numbers = R.compare(prog, ref, names)
    numbers["participants_gap"] = float(np.sum(
        masks.sum(axis=1) != np.asarray([h["participants"] for h in hist])))
    print(f"bench-correct worst leaves: grad1 {numbers['grad1_worst_leaf']}, "
          f"change3 {numbers['change3_worst_leaf']}; losses program "
          f"{prog.losses} reference {ref.losses}; reference "
          f"{t_ref:.1f} s", flush=True)
    failed = sum(not math.isfinite(h["loss"]) for h in window_hist)
    return Outcome(
        end_to_end={"train_tokens_per_s":
                    participants * T * b * S / win.seconds,
                    "setup_s": setup_s},
        counts={"rounds": len(window_hist),
                "useful_flops": participants * T * b * flops},
        checks={k: (numbers[k], float(lim))
                for k, lim in cell.limits.items()},
        attempted=len(window_hist), failed=failed, devices=devices,
        memory_peak_bytes=max(arrays, program),
        memory_sources={"allocator_peak_bytes_in_use": arrays,
                        "round_program_bytes": program})
