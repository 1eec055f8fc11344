"""Fleet-scale scheduling throughput: time `repro.energy.fleet.simulate_fleet`
(one jitted lax.scan over rounds, whole-fleet battery + arrival state) at
N in {1e3, 1e5, 1e6} clients host-local — plus, whenever more than one device
is visible (CI runs an ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
job), a ``sharded`` section timing the mesh-sharded client axis at up to 1e7
clients, and a ``controller`` section sweeping the battery-aware
`ServerController` against the static schedule under a solar drought.
A ``round_step`` section benchmarks the step-op layer itself (DESIGN.md
§11): one fleet round executed unfused (one jit per op, one launch per
telemetry stat), fused-lax (the simulators' single-jit ``backend="lax"``
body) and as the Pallas kernel (interpret mode off-TPU), at 1e6 and 1e7
clients, alongside the modeled HBM bytes-moved that explain the gap.
Everything lands in ``BENCH_fleet.json`` — the repo's perf-trajectory
artifact (uploaded per PR by CI's ``--smoke`` runs).

Reported per (N, policy): compile time, steady-state wall time, rounds/sec
and client-rounds/sec, plus mean participation so regressions in *behaviour*
(not just speed) are visible in the artifact diff.

Usage:
    PYTHONPATH=src python benchmarks/fleet_scale.py            # full sweep
    PYTHONPATH=src python benchmarks/fleet_scale.py --smoke    # CI (~seconds)
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import time

import jax
import numpy as np

from repro.core import EnergyProfile, Policy
from repro.energy import (BatteryConfig, Bernoulli, CompoundPoisson,
                          ControlBounds, DeviceCostModel, FleetConfig,
                          MarkovSolar, ServerController, run_controlled,
                          simulate_fleet)
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_data_mesh

PROCESSES = {
    "bernoulli": lambda n: Bernoulli.create(n, prob=0.35, amount=1.2),
    "solar": lambda n: MarkovSolar.create(n, p_stay_day=0.9, p_stay_night=0.9,
                                          day_mean=0.8),
    "poisson": lambda n: CompoundPoisson.create(n, rate=0.4, mean_amount=1.5),
}


def bench_one(n: int, rounds: int, policy: Policy, process: str,
              seed: int = 0, mesh=None) -> dict:
    proc = PROCESSES[process](n)
    bat = BatteryConfig(capacity=2.0, leak=0.01)
    E = np.asarray(EnergyProfile(n).cycles())  # the paper's §V profile
    cfg = FleetConfig(num_clients=n, policy=policy, seed=seed)

    def run():
        return simulate_fleet(proc, bat, 1.0, cfg, rounds, E=E, mesh=mesh)

    t0 = time.perf_counter()
    res = run()                      # compile + first run
    t1 = time.perf_counter()
    res = run()                      # steady state (jit cache hit)
    t2 = time.perf_counter()
    wall = t2 - t1
    rec = {
        "num_clients": n,
        "rounds": rounds,
        "policy": policy.value,
        "process": process,
        "compile_plus_run_s": round(t1 - t0, 4),
        "run_s": round(wall, 4),
        "rounds_per_s": round(rounds / wall, 2),
        "client_rounds_per_s": round(n * rounds / wall, 1),
        "mean_participation_rate": float(res.participation_rate.mean()),
        "total_overflowed_j": float(res.stats["overflowed"].sum()),
    }
    if mesh is not None:
        rec["mesh_devices"] = int(np.prod(list(mesh.shape.values())))
    return rec


def _time_step(fn, *args, reps: int) -> float:
    """Steady-state ms per call: one warm-up (compile), then the mean of
    ``reps`` timed calls, blocking on the whole output pytree."""
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e3


def bench_round_step(n: int, reps: int = 3) -> dict:
    """The step-op layer head-to-head (DESIGN.md §11): one THRESHOLD-policy
    fleet round (RNG-free, so only the step physics is timed) executed
    three ways over the same synthetic n-client inputs —

      * ``unfused``    — `step_ops.UnfusedRunner`: one jit per op, every
        intermediate through HBM, one reduction launch per stat (the
        pre-fusion cost model);
      * ``lax_fused``  — one jit of `step_ops.run_step_lax`, i.e. exactly
        the simulators' ``backend="lax"`` scan body;
      * ``pallas``     — `kernels.fleet_step.fused_step` (interpret mode
        off-TPU, where it measures overhead, not the TPU roofline);

    plus `step_ops.bytes_moved`'s modeled HBM traffic for the unfused chain
    vs the fused kernel.  The acceptance gate is
    ``speedup_fused_vs_unfused >= 2`` at n >= 1e7."""
    from repro.energy import step_ops
    from repro.kernels import fleet_step

    bat = BatteryConfig(capacity=2.0, leak=0.01)
    program, env = step_ops.fleet_step_program(bat, Policy.THRESHOLD)
    kc, kh = jax.random.split(jax.random.PRNGKey(0))
    env.update(
        charge=jax.random.uniform(kc, (n,), jax.numpy.float32, 0.0, 2.0),
        harvest=jax.random.uniform(kh, (n,), jax.numpy.float32, 0.0, 1.5),
        round_cost=jax.numpy.float32(1.0),
        threshold=jax.numpy.float32(1.2))
    valid = jax.numpy.ones((n,), jax.numpy.float32)

    unfused = step_ops.UnfusedRunner(program)

    @jax.jit
    def lax_fused(e, v):
        # return only what the simulators carry (state + stats): leaving the
        # intermediates dead is what lets XLA fuse the whole chain — the
        # very thing the unfused runner structurally cannot do
        out, stats = step_ops.run_step_lax(program, e, valid=v)
        return out["charge_out"], stats

    pallas = jax.jit(
        lambda e, v: fleet_step.fused_step(program, dict(e, valid=v), n=n))

    unfused_ms = _time_step(lambda e: unfused(e, valid=valid), env,
                            reps=reps)
    lax_ms = _time_step(lax_fused, env, valid, reps=reps)
    pallas_ms = _time_step(pallas, env, valid, reps=reps)

    model = step_ops.bytes_moved(program, env, n)
    return {
        "num_clients": n,
        "reps": reps,
        "policy": Policy.THRESHOLD.value,
        "unfused_ms": round(unfused_ms, 3),
        "lax_fused_ms": round(lax_ms, 3),
        "pallas_ms": round(pallas_ms, 3),
        "pallas_interpret": jax.default_backend() != "tpu",
        "speedup_fused_vs_unfused": round(unfused_ms / lax_ms, 3),
        "modeled_unfused_bytes": int(model["unfused_bytes"]),
        "modeled_fused_bytes": int(model["fused_bytes"]),
        "modeled_bytes_ratio": round(model["ratio"], 3),
    }


def bench_controller(n: int, rounds: int, control_every: int = 10,
                     checkpoint=None, resume: bool = False) -> dict:
    """Static §V schedule vs `ServerController` under a MarkovSolar drought
    (short days, 20-round nights): the controller should cut depletion AND
    lift participation by cheapening rounds / matching the ask rate."""
    proc = MarkovSolar.create(n, p_stay_day=0.6, p_stay_night=0.95,
                              day_mean=0.9)
    bat = BatteryConfig(capacity=6.0, leak=0.01, init_charge=1.0)
    cost = DeviceCostModel(joules_per_step=0.3, joules_per_upload=0.25,
                           joules_per_download=0.25)
    E0 = np.asarray(EnergyProfile(n).cycles())
    cfg = FleetConfig(num_clients=n, policy=Policy.SUSTAINABLE, seed=0,
                      local_steps=5)
    static = simulate_fleet(proc, bat, cost, cfg, rounds, E=E0)
    ctrl = ServerController(
        T0=cfg.local_steps, E0=EnergyProfile(n).taus,
        groups=np.arange(n) % len(EnergyProfile(n).taus),
        bounds=ControlBounds(t_min=1, t_max=10, e_min=1, e_max=64))
    t0 = time.perf_counter()
    res, ctrl = run_controlled(proc, bat, cost, cfg, rounds, ctrl,
                               control_every=control_every,
                               checkpoint=checkpoint, resume=resume)
    wall = time.perf_counter() - t0
    return {
        "num_clients": n,
        "rounds": rounds,
        "control_every": control_every,
        "run_s": round(wall, 4),
        "static_participation": float(static.participation_rate.mean()),
        "controlled_participation": float(res.participation_rate.mean()),
        "static_frac_depleted": float(static.stats["frac_depleted"].mean()),
        "controlled_frac_depleted": float(res.stats["frac_depleted"].mean()),
        "T_trace": [t["T"] for t in ctrl.trace],
        "E_mean_trace": [t["E_mean"] for t in ctrl.trace],
    }


def bench_dist(n: int, rounds: int, regime: str, obs=None) -> dict:
    """Distributional probe (DESIGN.md §14): one ``hist=True`` run per
    harvest regime streams per-round SoC/spend/streak histograms into the
    obs log (CI renders them with ``report dist``) and distills the
    depletion tail — p95(frac_depleted) plus the SoC/streak histogram
    quantiles — into the ``percentiles`` tripwire section, so a fattening
    tail fails bench-diff even when every mean stays flat."""
    from repro.obs import hist as hist_lib

    day_mean = {"sunny": 1.1, "drought": 0.55}[regime]
    proc = MarkovSolar.create(n, p_stay_day=0.6, p_stay_night=0.95,
                              day_mean=day_mean)
    bat = BatteryConfig(capacity=2.0, leak=0.01, init_charge=0.5)
    E = np.asarray(EnergyProfile(n).cycles())
    cfg = FleetConfig(num_clients=n, policy=Policy.SUSTAINABLE, seed=0)
    t0 = time.perf_counter()
    res = simulate_fleet(proc, bat, 1.0, cfg, rounds, E=E, obs=obs,
                         hist=True)
    wall = time.perf_counter() - t0
    fd = np.asarray(res.stats["frac_depleted"]).reshape(-1)
    rec = {
        "scan": "fleet", "regime": regime, "num_clients": n,
        "rounds": rounds, "policy": cfg.policy.value,
        "run_s": round(wall, 4),
        "mean_frac_depleted": float(fd.mean()),
        "p95_frac_depleted": float(np.percentile(fd, 95)),
    }
    for name in ("hist_soc", "hist_streak"):
        spec = hist_lib.SPECS_BY_NAME[name]
        counts = np.asarray(res.stats[name]).reshape(-1, spec.bins).sum(0)
        q = hist_lib.quantiles_from_counts(counts, spec)
        rec[f"{name}_p50"] = q["p50"]
        rec[f"{name}_p95"] = q["p95"]
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized sweep (seconds, not minutes)")
    ap.add_argument("--out", default="BENCH_fleet.json")
    ap.add_argument("--rounds", type=int, default=100)
    ap.add_argument("--history", default=None,
                    help="append this run's headline numbers (+ manifest "
                         "git rev) as one JSON line to the given "
                         "BENCH_history.jsonl — the committed bench "
                         "trajectory `repro.obs.report trend` renders")
    ap.add_argument("--obs-dir", default=None,
                    help="also stream bench progress as a repro.obs JSONL "
                         "event log (manifest + per-section spans + "
                         "per-record events)")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="persist each completed bench record so a killed "
                         "run resumes past the sections it already measured "
                         "(repro.checkpoint.SectionCheckpoint)")
    ap.add_argument("--resume", action="store_true",
                    help="replay completed records from --checkpoint-dir and "
                         "only compute the rest")
    args = ap.parse_args()
    enable_compile_cache()

    if args.resume and not args.checkpoint_dir:
        ap.error("--resume requires --checkpoint-dir")
    sc = None
    if args.checkpoint_dir:
        from repro.checkpoint import SectionCheckpoint
        from repro.obs.events import pytree_hash
        sc = SectionCheckpoint(
            args.checkpoint_dir, kind="fleet_scale",
            config_hash=pytree_hash(("fleet_scale", bool(args.smoke),
                                     int(args.rounds))),
            resume=args.resume)
        if sc.resumed:
            done = {k: len(v) for k, v in sc.sections.items()}
            print(f"resuming: replaying completed records {done}")

    def cached(section, index, fn):
        return sc.cached(section, index, fn) if sc is not None else fn()

    from repro.obs import Obs, RunManifest
    obs = Obs(args.obs_dir) if args.obs_dir else None
    # the BENCH json always carries a fresh manifest (it describes THIS
    # process), but a resumed run re-attaches to the obs stream with a
    # `resume` event instead of a second manifest (DESIGN.md §13.4)
    manifest = RunManifest.create("fleet_scale", horizon=args.rounds,
                                  smoke=args.smoke)
    if obs is not None:
        if sc is not None and sc.resumed:
            obs.event("resume", run_kind="fleet_scale", step=sc.step,
                      config_hash=sc.config_hash,
                      checkpoint_dir=args.checkpoint_dir)
        else:
            manifest = obs.write_manifest("fleet_scale", horizon=args.rounds,
                                          smoke=args.smoke)

    def _span(name):
        return obs.span(name) if obs is not None else contextlib.nullcontext()

    def _note(section, rec):
        if obs is not None:
            obs.event("bench_record", section=section,
                      **{k: v for k, v in rec.items()
                         if isinstance(v, (int, float, str, bool))})

    if args.smoke:
        sizes = [1_000, 100_000]
        combos = [(Policy.THRESHOLD, "bernoulli"), (Policy.SUSTAINABLE, "solar")]
        sharded_sizes = [200_000]
        ctrl_n = 20_000
        dist_n = 20_000
    else:
        sizes = [1_000, 100_000, 1_000_000]
        combos = [(Policy.THRESHOLD, "bernoulli"),
                  (Policy.GREEDY, "poisson"),
                  (Policy.SUSTAINABLE, "solar")]
        sharded_sizes = [1_000_000, 10_000_000]
        ctrl_n = 200_000
        dist_n = 200_000

    results = []
    for n in sizes:
        for policy, process in combos:
            with _span("results"):
                rec = cached("results", len(results),
                             lambda n=n, policy=policy, process=process:
                             bench_one(n, args.rounds, policy, process))
            results.append(rec)
            _note("results", rec)
            print(f"N={n:>9,} {policy.value:>11}/{process:<9} "
                  f"run={rec['run_s']:.3f}s  rounds/s={rec['rounds_per_s']:.1f}  "
                  f"client-rounds/s={rec['client_rounds_per_s']:.2e}  "
                  f"part={rec['mean_participation_rate']:.3f}", flush=True)

    # mesh-sharded client axis: only meaningful with >1 device (CI's
    # 8-device host-emulation job; real multi-host meshes in production)
    sharded = []
    n_dev = jax.device_count()
    if n_dev > 1:
        mesh = make_data_mesh()
        for n in sharded_sizes:
            for policy, process in combos[:2]:
                with _span("sharded"):
                    rec = cached("sharded", len(sharded),
                                 lambda n=n, policy=policy, process=process:
                                 bench_one(n, args.rounds, policy, process,
                                           mesh=mesh))
                sharded.append(rec)
                _note("sharded", rec)
                print(f"N={n:>9,} {policy.value:>11}/{process:<9} sharded/"
                      f"{n_dev}dev run={rec['run_s']:.3f}s  "
                      f"client-rounds/s={rec['client_rounds_per_s']:.2e}  "
                      f"part={rec['mean_participation_rate']:.3f}", flush=True)
    else:
        print("single device: skipping sharded section "
              "(set XLA_FLAGS=--xla_force_host_platform_device_count=8)")

    # the round-step fusion section always includes 1e7: the acceptance
    # gate (>= 2x fused-vs-unfused) is defined at >= 1e7 clients, smoke
    # runs included
    round_step = []
    for n in [1_000_000, 10_000_000]:
        with _span("round_step"):
            rec = cached("round_step", len(round_step),
                         lambda n=n: bench_round_step(
                             n, reps=3 if n <= 1_000_000 else 2))
        round_step.append(rec)
        _note("round_step", rec)
        print(f"round_step N={n:>10,}: unfused={rec['unfused_ms']:.2f}ms  "
              f"lax-fused={rec['lax_fused_ms']:.2f}ms  "
              f"pallas={rec['pallas_ms']:.2f}ms"
              f"{' (interpret)' if rec['pallas_interpret'] else ''}  "
              f"speedup={rec['speedup_fused_vs_unfused']:.2f}x  "
              f"bytes-model={rec['modeled_bytes_ratio']:.2f}x", flush=True)

    # distributional probe: sunny vs drought depletion tails — the fresh
    # side of the `percentiles` bench-diff section, and (with --obs-dir)
    # the hist-event stream behind CI's `report dist` markdown artifact
    percentiles = []
    for regime in ("sunny", "drought"):
        with _span("percentiles"):
            rec = cached("percentiles", len(percentiles),
                         lambda r=regime: bench_dist(dist_n, args.rounds, r,
                                                     obs=obs))
        percentiles.append(rec)
        _note("percentiles", rec)
        print(f"dist N={dist_n:,} {regime:>8}: frac_depleted "
              f"mean={rec['mean_frac_depleted']:.3f} "
              f"p95={rec['p95_frac_depleted']:.3f}  "
              f"soc p50={rec['hist_soc_p50']:.3f}  "
              f"streak p95={rec['hist_streak_p95']:.0f}", flush=True)

    with _span("controller"):
        # the controlled run inside the record is ALSO chunk-checkpointed
        # (its own subdirectory): a kill mid-controller-run resumes from the
        # last chunk boundary, not from the top of the section
        ctrl_rec = cached("controller", 0, lambda: bench_controller(
            ctrl_n, args.rounds,
            checkpoint=(os.path.join(args.checkpoint_dir, "controller_run")
                        if args.checkpoint_dir else None),
            resume=args.resume))
    print(f"controller N={ctrl_n:,}: participation "
          f"{ctrl_rec['static_participation']:.4f} -> "
          f"{ctrl_rec['controlled_participation']:.4f}, depleted "
          f"{ctrl_rec['static_frac_depleted']:.3f} -> "
          f"{ctrl_rec['controlled_frac_depleted']:.3f}, "
          f"T {ctrl_rec['T_trace'][:4]}...", flush=True)

    out = {"bench": "fleet_scale", "smoke": args.smoke, "rounds": args.rounds,
           "devices": n_dev, "manifest": manifest.to_dict(),
           "results": results, "sharded": sharded,
           "round_step": round_step, "percentiles": percentiles,
           "controller": ctrl_rec}
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    if obs is not None:
        obs.close()
    print(f"wrote {args.out}")

    if args.history:
        try:                              # `python -m benchmarks.fleet_scale`
            from benchmarks._fmt import append_history
        except ImportError:               # `python benchmarks/fleet_scale.py`
            from _fmt import append_history
        drought = next(r for r in percentiles if r["regime"] == "drought")
        append_history(args.history, "fleet_scale", {
            "max_client_rounds_per_s": max(r["client_rounds_per_s"]
                                           for r in results),
            "speedup_fused_vs_unfused_1e7":
                round_step[-1]["speedup_fused_vs_unfused"],
            "controlled_frac_depleted":
                ctrl_rec["controlled_frac_depleted"],
            "drought_p95_frac_depleted": drought["p95_frac_depleted"],
        }, out["manifest"], smoke=args.smoke)
        print(f"appended headline to {args.history}")


if __name__ == "__main__":
    main()
