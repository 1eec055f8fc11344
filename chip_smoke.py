#!/usr/bin/env python3
"""Smoke run of the system's three main paths on a TPU, in one process.

    python3 chip_smoke.py                # one chip: train, fleet, serve
    python3 chip_smoke.py --four-chips   # four chips: the sharded paths only

Every phase goes through the entry points a user calls, with random weights
drawn from a seed:

* train  — whisper-tiny at its published widths through the training
  launcher's round loop (`repro.launch.train.train_rounds`): 4 clients, 2
  local steps, 2 sequences of 448 tokens per client, the sustainable
  schedule, 3 rounds.  Every loss must be finite and the last below the
  first.  Then one round of the float32 smoke config runs on the chip and on
  the host CPU, under ``highest`` matmul precision, and the two must agree
  within `TRAIN_LOSS_RTOL` / `TRAIN_UPDATE_RTOL`.
* fleet  — `simulate_fleet` and `simulate_serve` at 1e7 clients for 8
  rounds on an exact-arithmetic (dyadic) configuration, with the lax and the
  Pallas round step: the two must agree bit for bit in every per-client
  output and count, and within `TOTALS_RTOL` in fleet-wide float totals; the
  Pallas kernel must be compiled for the TPU (``tpu_custom_call`` in its
  program); and at 1e5 clients the chip must agree with the host CPU bit
  for bit in every per-client output and total, and within `AVERAGES_RTOL`
  in averages.
* serve  — granite-3-2b at its published widths in bfloat16 through the
  serving launcher's engine pass (`repro.launch.serve.run_engine`): 4 slots,
  a 1024-token cache, 6 staggered requests with prompts of 64, 128 and 256
  tokens and 32 new tokens each.  Every request gets exactly its budget of
  in-vocabulary tokens and the engine's step and insert compile once.  On the
  float32 smoke config, under ``highest`` matmul precision, the greedy
  engine output must equal `generate` token for token.
* four-chips — the fleet and serve scans at 1e7 clients sharded over a
  4-device ``("data",)`` mesh, lax and Pallas, against one device by the
  same rule as the fleet phase; and `launch.steps.build_train_step` for whisper-tiny on a
  ``(data=4, model=1)`` mesh against the unsharded round on one device.  The
  sharded outputs must live on 4 distinct devices.

Any failed check raises and the script exits non-zero.  It refuses to run
unless JAX's first device is a TPU.  Wall, compile seconds and peak device
memory printed along the way are smoke figures, not benchmark numbers.  The
last line, printed only when every phase passed, is one JSON object naming
the device.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from functools import partial
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config, get_smoke_config  # noqa: E402
from repro.configs.base import InputShape  # noqa: E402
from repro.core import Policy, parallel_round  # noqa: E402
from repro.energy import BatteryConfig, FleetConfig, simulate_fleet  # noqa: E402
from repro.energy import arrivals, step_ops  # noqa: E402
from repro.energy.costs import DecodeCostModel  # noqa: E402
from repro.kernels import fleet_step  # noqa: E402
from repro.launch import serve as serve_launcher  # noqa: E402
from repro.launch import train as train_launcher  # noqa: E402
from repro.launch.cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_data_mesh, make_mesh  # noqa: E402
from repro.launch.steps import build_train_step  # noqa: E402
from repro.models import get_model  # noqa: E402
from repro.serve import (BatteryGated, Constant, QoSSpec, ServeConfig,  # noqa: E402
                         TrainLoad, simulate_serve)

# one float32 round, chip vs host CPU: the loss, and the parameter update
# (new minus initial weights) as a relative L2 distance.  Adam moves each
# weight by about lr whatever the gradient's size, so a gradient that is
# zero up to rounding can flip an update's sign: the update is held to 1%,
# the loss to 1e-4.
TRAIN_LOSS_RTOL = 1e-4
TRAIN_UPDATE_RTOL = 1e-2
# one bfloat16 round, sharded over 4 chips vs unsharded on one: the same
# measures, loosened to what bfloat16 weights can resolve (one bfloat16 ulp
# is 0.4% of a weight, larger than most of one round's Adam updates)
SHARDED_LOSS_RTOL = 1e-2
SHARDED_UPDATE_RTOL = 0.1

# the exact-arithmetic fleet of tests/test_kernels.py: every charge, harvest,
# energy cost and token count is a multiple of 2**-6, so per-client state is
# bit for bit the same on any backend and device, and so is every fleet-wide
# sum of non-negative terms that stays below 2**24 grid units: at 1e5
# clients all of them do, and chip vs CPU is held bit for bit in every
# per-client output and total.  At 1e7
# clients several totals pass 2**24 grid units, where float32 rounds, and
# two reduction orders (XLA's tree for lax, per-tile partials for Pallas,
# per-shard partials when sharded) may round such a total differently in its
# last bits.  Fleet-wide totals there are held to TOTALS_RTOL; every
# per-client output stays bit for bit.
TOTALS_RTOL = 1e-5
# float32 division on a TPU v5e is not correctly rounded, so a masked
# average (a ratio of two sums that agree bit for bit) may differ from the
# CPU's in its last two bits: chip vs CPU holds averages to 2 ulps
AVERAGES_RTOL = 2.0 ** -22
BAT = BatteryConfig(capacity=2.5, leak=0.0, init_charge=0.5)
ROUND_COST = 0.75
QOS = QoSSpec(prompt_tokens=64.0, full_decode_tokens=128.0,
              short_decode_tokens=32.0)
DECODE = DecodeCostModel(2.0 ** -8, 2.0 ** -9, 2.0 ** -6)


class CompileLog:
    """Compile seconds and persistent-cache hits, fed by JAX's monitoring
    events once `register` is called."""

    def __init__(self):
        self.seconds, self.cache_hits = 0.0, 0

    def register(self):
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def check(name: str, ok: bool, detail: str = "") -> None:
    """Print one check's verdict; a failed check raises."""
    line = f"  check {name}: {'passed' if ok else 'FAILED'}"
    print(line + (f" ({detail})" if detail else ""), flush=True)
    if not ok:
        raise AssertionError(f"{name} failed: {detail}")


def _rel(a, b) -> float:
    """||a - b|| / ||b|| over all leaves of two pytrees, in float64."""
    num = den = 0.0
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        x = np.asarray(x, np.float64)
        y = np.asarray(y, np.float64)
        num += float(np.sum((x - y) ** 2))
        den += float(np.sum(y ** 2))
    return float(np.sqrt(num / max(den, 1e-300)))


def _update(w_new, w_old):
    return jax.tree.map(lambda a, b: np.asarray(a, np.float64)
                        - np.asarray(b, np.float64), w_new, w_old)


def _host(tree):
    return jax.tree.map(np.asarray, tree)


# ------------------------------------------------------------------ train ----
def phase_train(*, arch: str = "whisper-tiny", smoke: bool = False,
                clients: int = 4, local_steps: int = 2, batch: int = 2,
                seq: int = 448, rounds: int = 3) -> None:
    cfg = get_smoke_config(arch) if smoke else get_config(arch)
    run = train_launcher.setup_training(
        cfg, clients=clients, local_steps=local_steps, batch=batch, seq=seq,
        policy="sustainable")
    print(f"  {cfg.name} ({cfg.dtype}) C={clients} T={local_steps} "
          f"batch={batch} seq={seq} rounds={rounds}", flush=True)
    _, history = train_launcher.train_rounds(run, run.init_params(), rounds)
    losses = [h["loss"] for h in history]
    check("train loss finite every round", bool(np.all(np.isfinite(losses))),
          f"losses {losses}")
    check("train last loss below first", losses[-1] < losses[0],
          f"{losses[-1]} < {losses[0]}")


def phase_train_parity(*, arch: str = "whisper-tiny", clients: int = 4,
                       local_steps: int = 2, batch: int = 2,
                       seq: int = 64) -> None:
    """One round of the float32 smoke config on the chip and on the CPU,
    from the same initial weights."""
    cfg = get_smoke_config(arch)
    assert cfg.dtype == "float32", cfg.dtype
    cpu = jax.devices("cpu")[0]
    run = train_launcher.setup_training(
        cfg, clients=clients, local_steps=local_steps, batch=batch, seq=seq,
        policy="sustainable")

    def one_round(device):
        w1, history = train_launcher.train_rounds(
            run, jax.device_put(w0, device), 1)
        return _host(w1), history[0]["loss"]

    with jax.default_matmul_precision("highest"):
        with jax.default_device(cpu):
            w0 = _host(run.init_params())
            w_cpu, loss_cpu = one_round(cpu)
        w_chip, loss_chip = one_round(jax.devices()[0])
    loss_rel = abs(loss_chip - loss_cpu) / abs(loss_cpu)
    upd_rel = _rel(_update(w_chip, w0), _update(w_cpu, w0))
    check("train round chip vs cpu: loss", loss_rel <= TRAIN_LOSS_RTOL,
          f"rel diff {loss_rel:.3e} <= {TRAIN_LOSS_RTOL:g}; "
          f"{loss_chip} vs {loss_cpu}")
    check("train round chip vs cpu: update", upd_rel <= TRAIN_UPDATE_RTOL,
          f"rel L2 diff {upd_rel:.3e} <= {TRAIN_UPDATE_RTOL:g}")


# ------------------------------------------------------------------ fleet ----
def simulate(kind: str, n: int, rounds: int, backend: str, mesh=None):
    """The dyadic fleet (``kind="fleet"``) or serving fleet (``"serve"``)
    through `simulate_fleet` / `simulate_serve`."""
    harvest = arrivals.Bernoulli.create(n, prob=0.375, amount=1.25)
    if kind == "fleet":
        cfg = FleetConfig(num_clients=n, policy=Policy.SUSTAINABLE, seed=3,
                          threshold=1.5)
        return simulate_fleet(harvest, BAT, ROUND_COST, cfg, rounds,
                              E=np.arange(n) % 4 + 1, record_masks=True,
                              groups=np.arange(n) % 3, num_groups=3,
                              hist=True, backend=backend, mesh=mesh)
    return simulate_serve(Constant.create(n, rate=2.0), harvest, BAT, DECODE,
                          QOS, BatteryGated.create(n, hi=1.0, lo=1.0),
                          ServeConfig(num_clients=n, seed=3), rounds,
                          train=TrainLoad.create(np.full(n, 4), 0.25),
                          admit=0.5, record_modes=True, backend=backend,
                          mesh=mesh)


def _outputs(res) -> dict:
    """Every per-round and per-client output of a fleet or serve result."""
    out = {f"stats.{k}": v for k, v in res.stats.items()}
    out["final_charge"] = res.final_charge
    for name in ("masks", "modes", "final_streak"):
        if getattr(res, name, None) is not None:
            out[name] = getattr(res, name)
    return out


def _average_stats() -> set:
    """The ``stats`` outputs formed by a division: the step programs' masked
    averages (the serve program's are a subset of the fleet's)."""
    program, _ = step_ops.fleet_step_program(BAT, Policy.SUSTAINABLE,
                                             num_groups=3)
    return {f"stats.{s}" for s, _ in program.averages
            + program.group_averages}


def _differences(a, b, *, totals_rtol: float = 0.0,
                 averages_rtol: float = 0.0) -> tuple[dict, dict]:
    """Outputs of two results that are not bitwise equal, as name -> largest
    relative difference, split into those beyond tolerance and those within:
    fleet-wide totals (``stats`` entries) within ``totals_rtol``, averages
    within the larger of the two.  Per-client outputs are always held bit
    for bit."""
    oa, ob = _outputs(a), _outputs(b)
    assert oa.keys() == ob.keys(), (oa.keys(), ob.keys())
    averages = _average_stats()
    beyond, within = {}, {}
    for k in oa:
        x = np.asarray(oa[k], np.float64)
        y = np.asarray(ob[k], np.float64)
        if np.array_equal(x, y):
            continue
        rel = float(np.max(np.abs(x - y) / np.maximum(np.abs(y), 1e-30)))
        if k in averages:
            tol = max(totals_rtol, averages_rtol)
        else:
            tol = totals_rtol if k.startswith("stats.") else -1.0
        (within if rel <= tol else beyond)[k] = rel
    return beyond, within


def pallas_program_text(n: int) -> str:
    """The compiled program of the fleet round step's Pallas kernel, as
    `simulate_fleet(backend="pallas")` calls it (interpret mode left to the
    platform rule of `repro.kernels.platform`)."""
    program, env = step_ops.fleet_step_program(BAT, Policy.SUSTAINABLE)
    for nm in fleet_step._env_names(program, None):
        env.setdefault(nm, jax.ShapeDtypeStruct((n,), jnp.float32))
    fn = jax.jit(lambda e: fleet_step.fused_step(program, e, n=n, emit=True))
    return fn.lower(env).compile().as_text()


def phase_fleet(*, n: int = 10_000_000, rounds: int = 8,
                n_host: int = 100_000) -> None:
    for kind in ("fleet", "serve"):
        t0 = time.perf_counter()
        lax_res = simulate(kind, n, rounds, "lax")
        t1 = time.perf_counter()
        pallas_res = simulate(kind, n, rounds, "pallas")
        t2 = time.perf_counter()
        beyond, within = _differences(pallas_res, lax_res,
                                      totals_rtol=TOTALS_RTOL)
        check(f"{kind} n={n} lax == pallas", not beyond,
              f"{len(_outputs(lax_res))} outputs over {rounds} rounds, all "
              f"bitwise but float totals within {TOTALS_RTOL:g}: {within}; "
              f"beyond: {beyond}; smoke wall incl. compile lax "
              f"{t1 - t0:.2f}s, pallas {t2 - t1:.2f}s")
    on_tpu = jax.devices()[0].platform == "tpu"
    compiled = "tpu_custom_call" in pallas_program_text(n)
    check("pallas round step compiled by Mosaic on the TPU, interpreted "
          "elsewhere", compiled == on_tpu,
          f"platform {jax.devices()[0].platform}, tpu_custom_call "
          f"{'present' if compiled else 'absent'}")
    for kind in ("fleet", "serve"):
        with jax.default_device(jax.devices("cpu")[0]):
            host = simulate(kind, n_host, rounds, "lax")
        for backend in ("lax", "pallas"):
            beyond, within = _differences(
                simulate(kind, n_host, rounds, backend), host,
                averages_rtol=AVERAGES_RTOL)
            check(f"{kind} {backend} n={n_host} chip == cpu lax", not beyond,
                  f"all bitwise but averages within {AVERAGES_RTOL:.3g}: "
                  f"{within}; beyond: {beyond}")


# ------------------------------------------------------------------ serve ----
def _prompts(cfg, key, lengths) -> list[dict]:
    keys = jax.random.split(key, len(lengths))
    return [{"tokens": np.asarray(jax.random.randint(
                k, (s,), 0, cfg.vocab_size, jnp.int32))}
            for k, s in zip(keys, lengths)]


def phase_serve(*, arch: str = "granite-3-2b", smoke: bool = False,
                slots: int = 4, cache_len: int = 1024,
                prompt_lens=(64, 128, 256, 64, 128, 256), gen: int = 32,
                stagger: int = 3, seed: int = 0) -> None:
    cfg = get_smoke_config(arch) if smoke else get_config(arch)
    model = get_model(cfg)
    k_params, k_prompt, k_sample = jax.random.split(
        jax.random.PRNGKey(seed), 3)
    params = jax.jit(model.init_params)(k_params)
    prompts = _prompts(cfg, k_prompt, prompt_lens)
    done, wall, engine = serve_launcher.run_engine(
        model, params, prompts, gen=gen, slots=slots, cache_len=cache_len,
        stagger=stagger, rng=k_sample)
    print(f"  {cfg.name} ({cfg.dtype}) slots={slots} cache_len={cache_len} "
          f"prompts={list(prompt_lens)} gen={gen}: {engine.stats}; smoke "
          f"wall incl. compile {wall:.2f}s", flush=True)
    toks = [np.asarray(done[i].tokens) for i in range(len(prompts))
            if i in done]
    check("serve every request finished", len(toks) == len(prompts),
          f"{len(toks)} of {len(prompts)}")
    check("serve exactly the budget of tokens each",
          all(t.shape == (gen,) for t in toks),
          f"shapes {sorted({t.shape for t in toks})}")
    check("serve tokens within the vocabulary",
          all(((t >= 0) & (t < cfg.vocab_size)).all() for t in toks),
          f"vocab {cfg.vocab_size}")
    sizes = {nm: engine._fns[nm]._cache_size() for nm in ("step", "insert")}
    check("serve step and insert compiled once",
          sizes == {"step": 1, "insert": 1}, f"jit cache sizes {sizes}")


def phase_engine_parity(*, arch: str = "granite-3-2b", slots: int = 4,
                        cache_len: int = 1024,
                        prompt_lens=(64, 128, 256, 64, 128, 256),
                        gen: int = 32, stagger: int = 3,
                        seed: int = 0) -> None:
    """Greedy engine vs single-stream `generate` on the float32 smoke
    config under ``highest`` matmul precision, where bfloat16 ties cannot
    blur an argmax (a TPU's default precision rounds float32 matmul inputs
    to bfloat16, and the engine's batched shapes round differently from
    one stream's)."""
    cfg = get_smoke_config(arch)
    assert cfg.dtype == "float32", cfg.dtype
    model = get_model(cfg)
    k_params, k_prompt = jax.random.split(jax.random.PRNGKey(seed))
    params = model.init_params(k_params)
    prompts = _prompts(cfg, k_prompt, prompt_lens)
    with jax.default_matmul_precision("highest"):
        done, _, _ = serve_launcher.run_engine(
            model, params, prompts, gen=gen, slots=slots,
            cache_len=cache_len, stagger=stagger)
        ref = [np.asarray(serve_launcher.generate(
            model, params, {"tokens": jnp.asarray(p["tokens"])[None]}, gen,
            cache_len))[0] for p in prompts]
    first_diff = {i: int(np.argmax(done[i].tokens != r))
                  for i, r in enumerate(ref)
                  if not np.array_equal(done[i].tokens, r)}
    check("engine greedy == generate token for token", not first_diff,
          f"{len(ref) - len(first_diff)} of {len(ref)} requests identical; "
          f"first differing token by request: {first_diff}")


# ------------------------------------------------------------- four chips ----
def _devices_of(x) -> set:
    return {s.device for s in x.addressable_shards}


def phase_four_chips(*, n: int = 10_000_000, rounds: int = 8,
                     arch: str = "whisper-tiny", smoke: bool = False,
                     clients: int = 4, local_steps: int = 2, batch: int = 2,
                     seq: int = 448) -> None:
    devices = jax.devices()[:4]
    check("four devices present", len(devices) == 4, f"{jax.devices()}")
    mesh = make_data_mesh(4)
    for kind in ("fleet", "serve"):
        for backend in ("lax", "pallas"):
            one = simulate(kind, n, rounds, backend)
            sharded = simulate(kind, n, rounds, backend, mesh=mesh)
            beyond, within = _differences(sharded, one,
                                          totals_rtol=TOTALS_RTOL)
            check(f"{kind} {backend} n={n} 4-way sharded == one device",
                  not beyond, f"all bitwise but float totals within "
                  f"{TOTALS_RTOL:g}: {within}; beyond: {beyond}")
            spread = _devices_of(sharded.final_charge)
            check(f"{kind} {backend} sharded state spans 4 devices",
                  len(spread) == 4, f"{sorted(d.id for d in spread)}")

    cfg = get_smoke_config(arch) if smoke else get_config(arch)
    mesh2 = make_mesh((4, 1), ("data", "model"))
    shape = InputShape("chip_smoke_train", seq, clients * batch, "train")
    bundle = build_train_step(cfg, shape, mesh2, local_steps=local_steps)
    loss_fn, opt, fed = bundle.fn.args
    run = train_launcher.setup_training(
        cfg, clients=clients, local_steps=local_steps, batch=batch, seq=seq)
    w0 = run.init_params()
    # round 1: three of the four clients take part, so the sharded
    # aggregation sums over devices
    args = (run.batch_fn(1), run.p, run.E, jnp.int32(1),
            jax.random.fold_in(run.rng, 1))
    sharded_fn = jax.jit(bundle.fn, in_shardings=bundle.in_shardings,
                         out_shardings=bundle.out_shardings)
    w_s, m_s = sharded_fn(*jax.device_put((w0,) + args, bundle.in_shardings))
    spread = set().union(*(_devices_of(x) for x in jax.tree.leaves(w_s)))
    check("train sharded round spans 4 devices", len(spread) == 4,
          f"{sorted(d.id for d in spread)}")
    one = jax.jit(partial(parallel_round, loss_fn, opt, fed))
    w_u, m_u = one(*jax.device_put((w0,) + args, devices[0]))
    w0, w_s, w_u = _host(w0), _host(w_s), _host(w_u)
    loss_s, loss_u = float(m_s["loss"]), float(m_u["loss"])
    loss_rel = abs(loss_s - loss_u) / abs(loss_u)
    upd_rel = _rel(_update(w_s, w0), _update(w_u, w0))
    check("train 4-way sharded vs one device: participants",
          float(m_s["participants"]) == float(m_u["participants"]),
          f"{float(m_s['participants'])}")
    check("train 4-way sharded vs one device: loss",
          loss_rel <= SHARDED_LOSS_RTOL,
          f"rel diff {loss_rel:.3e} <= {SHARDED_LOSS_RTOL:g}; "
          f"{loss_s} vs {loss_u}")
    check("train 4-way sharded vs one device: update",
          upd_rel <= SHARDED_UPDATE_RTOL,
          f"rel L2 diff {upd_rel:.3e} <= {SHARDED_UPDATE_RTOL:g}")


# ------------------------------------------------------------------- main ----
ONE_CHIP_PHASES = (("train", phase_train),
                   ("train_parity", phase_train_parity),
                   ("fleet", phase_fleet),
                   ("serve", phase_serve),
                   ("engine_parity", phase_engine_parity))
FOUR_CHIP_PHASES = (("four_chips", phase_four_chips),)


def run_phase(name: str, fn, log: CompileLog) -> None:
    print(f"phase {name}", flush=True)
    secs0, hits0 = log.seconds, log.cache_hits
    t0 = time.perf_counter()
    fn()
    stats = jax.devices()[0].memory_stats() or {}
    print(f"phase {name} passed; smoke figures, not benchmark numbers: "
          f"wall {time.perf_counter() - t0:.2f}s, compile "
          f"{log.seconds - secs0:.2f}s, compile-cache hits "
          f"{log.cache_hits - hits0}, device 0 peak_bytes_in_use "
          f"{stats.get('peak_bytes_in_use', 'not reported')}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded paths, over four chips")
    args = ap.parse_args(argv)
    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX's first device is "
              f"{device.platform} ({device.device_kind})", file=sys.stderr)
        return 1
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    log = CompileLog()
    log.register()
    for name, fn in FOUR_CHIP_PHASES if args.four_chips else ONE_CHIP_PHASES:
        run_phase(name, fn, log)
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
