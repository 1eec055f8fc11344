"""Serving-fleet throughput and admission-quality benchmark: time
`repro.serve.fleet_serve.simulate_serve` (one jitted lax.scan over epochs,
whole-fleet battery + traffic + harvest state) at N in {1e3, 1e5, 1e6}
clients host-local — plus, whenever more than one device is visible (CI runs
an ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` job), a
``sharded`` section sweeping the mesh-sharded client axis at >= 1e6 clients
x >= 50 epochs, and an ``admission`` section pitting battery-gated admission
against energy-agnostic serving under a solar day/night + diurnal-traffic
scenario (the acceptance comparison: shed/unanswered rate and depletion).
A ``round_step`` section benchmarks the serve step-op layer (DESIGN.md
§11): one serving epoch executed unfused (one jit per op, one launch per
ledger stat), fused-lax (the ``backend="lax"`` scan body) and as the
Pallas kernel (interpret mode off-TPU) at 1e6 and 1e7 clients, with the
modeled HBM bytes-moved alongside.
Everything lands in ``BENCH_serve.json`` — uploaded per PR by CI's
``serve-scale`` job.

Reported per (N, traffic, policy): compile time, steady-state wall time,
epochs/sec and client-epochs/sec, plus served/shed rates and joules/token so
regressions in *behaviour* (not just speed) are visible in the artifact
diff.

Usage:
    PYTHONPATH=src python benchmarks/serve_scale.py            # full sweep
    PYTHONPATH=src python benchmarks/serve_scale.py --smoke    # CI (~seconds)
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import time

import jax
import numpy as np

from repro.energy import (AdmissionRule, BatteryConfig, ControlBounds,
                          DecodeCostModel, MarkovSolar, ServerController)
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_data_mesh
from repro.serve import (BatteryGated, DiurnalPoisson, EnergyAgnostic, MMPP,
                         QoSSpec, ServeConfig, TrainLoad,
                         run_serve_controlled, simulate_serve)

QOS = QoSSpec(prompt_tokens=128.0, full_decode_tokens=256.0,
              short_decode_tokens=32.0)
# ~100M-active-param on-device model at the nominal edge constants:
# ~0.77 J per full request, ~0.32 J degraded — the same order as the solar
# harvest below, so admission decisions actually bind
COST = DecodeCostModel.from_params(1e8)

TRAFFIC = {
    "diurnal": lambda n: DiurnalPoisson.create(
        n, base=1.0, swing=0.9, phase=np.arange(n) % 24),
    "mmpp": lambda n: MMPP.create(n, calm_rate=0.3, burst_rate=2.5),
}

POLICIES = {
    "agnostic": lambda n: EnergyAgnostic(),
    "gated": lambda n: BatteryGated.create(n, hi=2.0, lo=1.5),
}


def _solar(n):
    return MarkovSolar.create(n, p_stay_day=0.9, p_stay_night=0.9,
                              day_mean=3.0)


def bench_one(n: int, epochs: int, traffic_name: str, policy_name: str,
              seed: int = 0, mesh=None) -> dict:
    traffic = TRAFFIC[traffic_name](n)
    harvest = _solar(n)
    bat = BatteryConfig(capacity=8.0, leak=0.01, init_charge=2.0)
    pol = POLICIES[policy_name](n)
    cfg = ServeConfig(num_clients=n, seed=seed)

    def run():
        return simulate_serve(traffic, harvest, bat, COST, QOS, pol, cfg,
                              epochs, mesh=mesh)

    t0 = time.perf_counter()
    res = run()                      # compile + first run
    t1 = time.perf_counter()
    res = run()                      # steady state (jit cache hit)
    t2 = time.perf_counter()
    wall = t2 - t1
    s = res.stats
    offered = max(float(s["offered"].sum()), 1e-9)
    rec = {
        "num_clients": n,
        "epochs": epochs,
        "traffic": traffic_name,
        "policy": policy_name,
        "compile_plus_run_s": round(t1 - t0, 4),
        "run_s": round(wall, 4),
        "epochs_per_s": round(epochs / wall, 2),
        "client_epochs_per_s": round(n * epochs / wall, 1),
        "served_rate": float((s["served_full"].sum()
                              + s["served_short"].sum()) / offered),
        "shed_rate": float(s["shed"].sum() / offered),
        "deadline_miss_rate": float(s["deadline_missed"].sum() / offered),
        "frac_depleted": float(s["frac_depleted"].mean()),
        "joules_per_token": res.joules_per_token,
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
    """The serve step-op layer head-to-head (the serve twin of
    `fleet_scale.bench_round_step`): one battery-gated serving epoch —
    absorb, price, admission decide, serve-drain, ledger, token totals,
    RNG-free so only the step physics is timed — executed unfused
    (`step_ops.UnfusedRunner`), as the single-jit lax backend
    (`step_ops.run_step_lax`) and as the fused Pallas kernel
    (`kernels.fleet_step.fused_step`, interpret mode off-TPU), plus the
    `step_ops.bytes_moved` HBM-traffic model for both."""
    import jax.numpy as jnp

    from repro.energy import step_ops
    from repro.kernels import fleet_step

    bat = BatteryConfig(capacity=8.0, leak=0.01, init_charge=2.0)
    pol = BatteryGated.create(n, hi=2.0, lo=1.5)
    program, env = step_ops.serve_step_program(bat, COST, QOS, pol,
                                               train=None)
    kc, kh, kr = jax.random.split(jax.random.PRNGKey(0), 3)
    env.update(
        charge=jax.random.uniform(kc, (n,), jnp.float32, 0.0, 8.0),
        harvest=jax.random.uniform(kh, (n,), jnp.float32, 0.0, 3.0),
        requests=jnp.floor(jax.random.uniform(kr, (n,), jnp.float32,
                                              0.0, 4.0)),
        admit=jnp.float32(1.0))
    valid = jnp.ones((n,), jnp.float32)

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
        "policy": "gated",
        "unfused_ms": round(unfused_ms, 3),
        "lax_fused_ms": round(lax_ms, 3),
        "pallas_ms": round(pallas_ms, 3),
        "pallas_interpret": jax.default_backend() != "tpu",
        "speedup_fused_vs_unfused": round(unfused_ms / lax_ms, 3),
        "modeled_unfused_bytes": int(model["unfused_bytes"]),
        "modeled_fused_bytes": int(model["fused_bytes"]),
        "modeled_bytes_ratio": round(model["ratio"], 3),
    }


def bench_admission(n: int, epochs: int, control_every: int = 24,
                    checkpoint=None, resume: bool = False) -> dict:
    """The acceptance comparison: solar day/night + diurnal traffic, with a
    training load competing for the same batteries.  Battery-gated admission
    (static margins, and closed-loop with `AdmissionRule`) vs the
    energy-agnostic baseline, on unanswered-request rate and depletion."""
    traffic = DiurnalPoisson.create(n, base=1.0, swing=0.9,
                                    phase=np.arange(n) % 24)
    harvest = _solar(n)
    bat = BatteryConfig(capacity=8.0, leak=0.01, init_charge=2.0)
    train_cost = 0.2   # joules per training round, same battery
    cfg = ServeConfig(num_clients=n, seed=0)

    def summarize(res):
        s = res.stats
        offered = max(float(s["offered"].sum()), 1e-9)
        return {
            "served_rate": float((s["served_full"].sum()
                                  + s["served_short"].sum()) / offered),
            "shed_rate": float(s["shed"].sum() / offered),
            "deadline_miss_rate": float(s["deadline_missed"].sum() / offered),
            "unanswered_rate": float((s["shed"].sum()
                                      + s["deadline_missed"].sum()) / offered),
            "frac_depleted": float(s["frac_depleted"].mean()),
            "train_participants": float(s["participants"].mean()),
            "joules_per_token": res.joules_per_token,
        }

    train = TrainLoad.create(np.full(n, 4), train_cost)
    out = {"num_clients": n, "epochs": epochs}
    t0 = time.perf_counter()
    out["agnostic"] = summarize(simulate_serve(
        traffic, harvest, bat, COST, QOS, EnergyAgnostic(), cfg, epochs,
        train=train))
    out["gated"] = summarize(simulate_serve(
        traffic, harvest, bat, COST, QOS,
        BatteryGated.create(n, hi=2.0, lo=1.5), cfg, epochs, train=train))
    ctrl = ServerController(T0=5, E0=4, rules=(AdmissionRule(),),
                            bounds=ControlBounds())
    res, ctrl = run_serve_controlled(
        traffic, harvest, bat, COST, QOS, BatteryGated.create(n), cfg,
        epochs, ctrl, train_cost=train_cost, control_every=control_every,
        checkpoint=checkpoint, resume=resume)
    out["controlled"] = summarize(res)
    out["controlled"]["admit_trace"] = [t["admit"] for t in ctrl.trace]
    out["run_s"] = round(time.perf_counter() - t0, 4)
    return out


def bench_dist(n: int, epochs: int, regime: str, obs=None) -> dict:
    """Distributional probe (DESIGN.md §14), serve twin of
    `fleet_scale.bench_dist`: one ``hist=True`` serving run per solar
    regime streams per-epoch SoC/spend/streak histograms into the obs log
    and distills the depletion tail into the ``percentiles`` bench-diff
    section."""
    from repro.obs import hist as hist_lib

    day_mean = {"sunny": 3.0, "drought": 1.2}[regime]
    traffic = DiurnalPoisson.create(n, base=1.0, swing=0.9,
                                    phase=np.arange(n) % 24)
    harvest = MarkovSolar.create(n, p_stay_day=0.9, p_stay_night=0.9,
                                 day_mean=day_mean)
    bat = BatteryConfig(capacity=8.0, leak=0.01, init_charge=2.0)
    pol = BatteryGated.create(n, hi=2.0, lo=1.5)
    cfg = ServeConfig(num_clients=n, seed=0)
    t0 = time.perf_counter()
    res = simulate_serve(traffic, harvest, bat, COST, QOS, pol, cfg, epochs,
                         obs=obs, hist=True)
    wall = time.perf_counter() - t0
    fd = np.asarray(res.stats["frac_depleted"]).reshape(-1)
    offered = max(float(res.stats["offered"].sum()), 1e-9)
    rec = {
        "scan": "serve", "regime": regime, "num_clients": n,
        "epochs": epochs, "policy": "gated",
        "run_s": round(wall, 4),
        "shed_rate": float(res.stats["shed"].sum() / offered),
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
    ap.add_argument("--out", default="BENCH_serve.json")
    ap.add_argument("--epochs", type=int, default=96)
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
            args.checkpoint_dir, kind="serve_scale",
            config_hash=pytree_hash(("serve_scale", bool(args.smoke),
                                     int(args.epochs))),
            resume=args.resume)
        if sc.resumed:
            done = {k: len(v) for k, v in sc.sections.items()}
            print(f"resuming: replaying completed records {done}")

    def cached(section, index, fn):
        return sc.cached(section, index, fn) if sc is not None else fn()

    from repro.obs import Obs, RunManifest
    obs = Obs(args.obs_dir) if args.obs_dir else None
    manifest = RunManifest.create("serve_scale", horizon=args.epochs,
                                  smoke=args.smoke)
    if obs is not None:
        if sc is not None and sc.resumed:
            obs.event("resume", run_kind="serve_scale", step=sc.step,
                      config_hash=sc.config_hash,
                      checkpoint_dir=args.checkpoint_dir)
        else:
            manifest = obs.write_manifest("serve_scale", horizon=args.epochs,
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
        combos = [("diurnal", "gated"), ("mmpp", "agnostic")]
        # acceptance: a >= 1e6-client x >= 50-epoch sharded sweep in CI's
        # 8-device emulated job
        sharded = [(1_000_000, max(50, args.epochs // 2))]
        adm_n = 20_000
        dist_n = 20_000
    else:
        sizes = [1_000, 100_000, 1_000_000]
        combos = [("diurnal", "gated"), ("diurnal", "agnostic"),
                  ("mmpp", "gated")]
        sharded = [(1_000_000, args.epochs), (10_000_000, args.epochs)]
        adm_n = 200_000
        dist_n = 200_000

    results = []
    for n in sizes:
        for traffic_name, policy_name in combos:
            with _span("results"):
                rec = cached(
                    "results", len(results),
                    lambda n=n, t=traffic_name, p=policy_name:
                    bench_one(n, args.epochs, t, p))
            results.append(rec)
            _note("results", rec)
            print(f"N={n:>9,} {traffic_name:>8}/{policy_name:<9} "
                  f"run={rec['run_s']:.3f}s  epochs/s={rec['epochs_per_s']:.1f}  "
                  f"client-epochs/s={rec['client_epochs_per_s']:.2e}  "
                  f"served={rec['served_rate']:.3f}", flush=True)

    sharded_results = []
    n_dev = jax.device_count()
    if n_dev > 1:
        mesh = make_data_mesh()
        for n, epochs in sharded:
            for traffic_name, policy_name in combos[:1]:
                with _span("sharded"):
                    rec = cached(
                        "sharded", len(sharded_results),
                        lambda n=n, e=epochs, t=traffic_name, p=policy_name:
                        bench_one(n, e, t, p, mesh=mesh))
                sharded_results.append(rec)
                _note("sharded", rec)
                print(f"N={n:>9,} {traffic_name:>8}/{policy_name:<9} sharded/"
                      f"{n_dev}dev epochs={epochs} run={rec['run_s']:.3f}s  "
                      f"client-epochs/s={rec['client_epochs_per_s']:.2e}",
                      flush=True)
    else:
        print("single device: skipping sharded section "
              "(set XLA_FLAGS=--xla_force_host_platform_device_count=8)")

    # round-step fusion section: 1e7 included even in --smoke (the serve
    # twin of fleet_scale's >= 2x fused-vs-unfused acceptance gate)
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
                         lambda r=regime: bench_dist(dist_n, args.epochs, r,
                                                     obs=obs))
        percentiles.append(rec)
        _note("percentiles", rec)
        print(f"dist N={dist_n:,} {regime:>8}: frac_depleted "
              f"mean={rec['mean_frac_depleted']:.3f} "
              f"p95={rec['p95_frac_depleted']:.3f}  "
              f"soc p50={rec['hist_soc_p50']:.3f}  "
              f"streak p95={rec['hist_streak_p95']:.0f}", flush=True)

    # decode-engine per-stage microbench (DESIGN.md §15): the section the
    # serve-engine CI job tripwires; smoke-config weights, so it rides in
    # the same sweep at CI scale
    try:                                  # `python -m benchmarks.serve_scale`
        from benchmarks.engine_bench import SMOKE_ARCHS, bench_engine
    except ImportError:                   # `python benchmarks/serve_scale.py`
        from engine_bench import SMOKE_ARCHS, bench_engine
    engine = []
    for arch in SMOKE_ARCHS:
        with _span("engine"):
            rec = cached("engine", len(engine),
                         lambda a=arch: bench_engine(
                             a, reps=3 if args.smoke else 5))
        engine.append(rec)
        _note("engine", rec)
        print(f"engine {arch:>16}: prefill {rec['prefill_tok_s']:.0f} tok/s  "
              f"decode step {rec['decode_step_ms']:.2f}ms  "
              f"insert {rec['insert_ms']:.2f}ms", flush=True)

    with _span("admission"):
        # the controlled run inside the record is ALSO chunk-checkpointed
        # (its own subdirectory): a kill mid-run resumes from the last
        # chunk boundary, not from the top of the section
        adm = cached("admission", 0, lambda: bench_admission(
            adm_n, args.epochs,
            checkpoint=(os.path.join(args.checkpoint_dir, "admission_run")
                        if args.checkpoint_dir else None),
            resume=args.resume))
    print(f"admission N={adm_n:,}: unanswered "
          f"{adm['agnostic']['unanswered_rate']:.3f} (agnostic) -> "
          f"{adm['gated']['unanswered_rate']:.3f} (gated) / "
          f"{adm['controlled']['unanswered_rate']:.3f} (controlled); "
          f"depleted {adm['agnostic']['frac_depleted']:.3f} -> "
          f"{adm['gated']['frac_depleted']:.3f} / "
          f"{adm['controlled']['frac_depleted']:.3f}", flush=True)

    out = {"bench": "serve_scale", "smoke": args.smoke, "epochs": args.epochs,
           "devices": n_dev, "manifest": manifest.to_dict(),
           "results": results, "sharded": sharded_results,
           "round_step": round_step, "percentiles": percentiles,
           "engine": engine, "admission": adm}
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    if obs is not None:
        obs.close()
    print(f"wrote {args.out}")

    if args.history:
        try:                              # `python -m benchmarks.serve_scale`
            from benchmarks._fmt import append_history
        except ImportError:               # `python benchmarks/serve_scale.py`
            from _fmt import append_history
        drought = next(r for r in percentiles if r["regime"] == "drought")
        append_history(args.history, "serve_scale", {
            "max_client_epochs_per_s": max(r["client_epochs_per_s"]
                                           for r in results),
            "speedup_fused_vs_unfused_1e7":
                round_step[-1]["speedup_fused_vs_unfused"],
            "controlled_unanswered_rate":
                adm["controlled"]["unanswered_rate"],
            "drought_p95_frac_depleted": drought["p95_frac_depleted"],
        }, out["manifest"], smoke=args.smoke)
        print(f"appended headline to {args.history}")


if __name__ == "__main__":
    main()
