"""Trace-replay throughput and calibration-fidelity benchmark: time
`repro.traces` replay through both fleet scans — `simulate_fleet` under
`TraceHarvest` and `simulate_serve` under `TraceTraffic` + `TraceHarvest` —
at N in {1e3, 1e5, 1e6} clients host-local, plus, whenever more than one
device is visible (CI runs an ``XLA_FLAGS=--xla_force_host_platform_
device_count=8`` job), a ``sharded`` section sweeping the mesh-sharded
client axis at >= 1e6 clients x >= 50 epochs.

A ``calibration`` section records estimator fidelity per PR: each synthetic
process is re-fit from its own sampled paths (`fit_markov_solar` /
`fit_diurnal_poisson` / `fit_mmpp`) and the true-vs-fitted parameters land
in the artifact, so a regression in recovery error (not just speed) is
visible in the ``BENCH_traces.json`` diff — uploaded per PR by CI's
``trace-scale`` job.

Usage:
    PYTHONPATH=src python benchmarks/trace_scale.py            # full sweep
    PYTHONPATH=src python benchmarks/trace_scale.py --smoke    # CI (~seconds)
"""
from __future__ import annotations

import argparse
import contextlib
import json
import time

import jax
import numpy as np

from repro.core import Policy
from repro.energy import (BatteryConfig, DecodeCostModel, FleetConfig,
                          MarkovSolar, TraceHarvest, simulate_fleet)
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_data_mesh
from repro.serve import (MMPP, BatteryGated, DiurnalPoisson, QoSSpec,
                         ServeConfig, TraceTraffic, simulate_serve)
from repro.traces import (fit_diurnal_poisson, fit_markov_solar, fit_mmpp,
                          request_profile_table, rescale, sample_paths,
                          solar_profile_table)

QOS = QoSSpec(prompt_tokens=128.0, full_decode_tokens=256.0,
              short_decode_tokens=32.0)
COST = DecodeCostModel.from_params(1e8)


def _procs(n, seed=0):
    solar = rescale(solar_profile_table(), 1.5)
    requests = rescale(request_profile_table(), 1.0)
    return (TraceHarvest.create(solar, n, seed=seed, gain_jitter=0.3),
            TraceTraffic.create(requests, n, seed=seed, gain_jitter=0.3))


def bench_fleet(n: int, rounds: int, seed: int = 0, mesh=None) -> dict:
    harvest, _ = _procs(n, seed)
    bat = BatteryConfig(capacity=4.0, leak=0.01, init_charge=1.0)
    cfg = FleetConfig(num_clients=n, policy=Policy.THRESHOLD, seed=seed)

    def run():
        return simulate_fleet(harvest, bat, 1.0, cfg, rounds, mesh=mesh)

    t0 = time.perf_counter()
    res = run()                      # compile + first run
    t1 = time.perf_counter()
    res = run()                      # steady state (jit cache hit)
    t2 = time.perf_counter()
    wall = t2 - t1
    rec = {
        "scan": "fleet", "num_clients": n, "rounds": rounds,
        "compile_plus_run_s": round(t1 - t0, 4),
        "run_s": round(wall, 4),
        "rounds_per_s": round(rounds / wall, 2),
        "client_rounds_per_s": round(n * rounds / wall, 1),
        "participation": float(res.stats["participants"].mean() / n),
        "frac_depleted": float(res.stats["frac_depleted"].mean()),
    }
    if mesh is not None:
        rec["mesh_devices"] = int(np.prod(list(mesh.shape.values())))
    return rec


def bench_serve(n: int, epochs: int, seed: int = 0, mesh=None) -> dict:
    harvest, traffic = _procs(n, seed)
    bat = BatteryConfig(capacity=8.0, leak=0.01, init_charge=2.0)
    cfg = ServeConfig(num_clients=n, seed=seed)
    pol = BatteryGated.create(n, hi=2.0, lo=1.5)

    def run():
        return simulate_serve(traffic, harvest, bat, COST, QOS, pol, cfg,
                              epochs, mesh=mesh)

    t0 = time.perf_counter()
    res = run()
    t1 = time.perf_counter()
    res = run()
    t2 = time.perf_counter()
    wall = t2 - t1
    s = res.stats
    offered = max(float(s["offered"].sum()), 1e-9)
    rec = {
        "scan": "serve", "num_clients": n, "epochs": epochs,
        "compile_plus_run_s": round(t1 - t0, 4),
        "run_s": round(wall, 4),
        "epochs_per_s": round(epochs / wall, 2),
        "client_epochs_per_s": round(n * epochs / wall, 1),
        "served_rate": float((s["served_full"].sum()
                              + s["served_short"].sum()) / offered),
        "shed_rate": float(s["shed"].sum() / offered),
        "joules_per_token": res.joules_per_token,
    }
    if mesh is not None:
        rec["mesh_devices"] = int(np.prod(list(mesh.shape.values())))
    return rec


def bench_calibration(fit_n: int, fit_r: int) -> dict:
    """Round-trip fidelity: fit each synthetic process on its own sampled
    paths and record true vs fitted parameters (+ wall time), so estimator
    regressions show in the artifact diff."""
    out = {"fit_clients": fit_n, "fit_rounds": fit_r}

    true_solar = {"p_stay_day": 0.9, "p_stay_night": 0.85, "day_mean": 1.2,
                  "night_mean": 0.05}
    proc = MarkovSolar.create(fit_n, **true_solar)
    t0 = time.perf_counter()
    fit = fit_markov_solar(sample_paths(proc, fit_r, seed=1), 1)
    out["markov_solar"] = {
        "true": true_solar, "fit_s": round(time.perf_counter() - t0, 3),
        "fitted": {k: round(float(getattr(fit, k)[0]), 4)
                   for k in true_solar}}

    true_diurnal = {"base": 1.0, "swing": 0.7, "phase": 9.0}
    proc = DiurnalPoisson.create(fit_n, **true_diurnal)
    t0 = time.perf_counter()
    fit = fit_diurnal_poisson(sample_paths(proc, fit_r, seed=2), 1)
    out["diurnal_poisson"] = {
        "true": true_diurnal, "fit_s": round(time.perf_counter() - t0, 3),
        "fitted": {k: round(float(getattr(fit, k)[0]), 4)
                   for k in true_diurnal}}

    true_mmpp = {"p_stay_calm": 0.9, "p_stay_burst": 0.7, "calm_rate": 0.4,
                 "burst_rate": 4.0}
    proc = MMPP.create(fit_n, **true_mmpp)
    t0 = time.perf_counter()
    fit = fit_mmpp(sample_paths(proc, fit_r, seed=3), 1)
    out["mmpp"] = {
        "true": true_mmpp, "fit_s": round(time.perf_counter() - t0, 3),
        "fitted": {k: round(float(getattr(fit, k)[0]), 4)
                   for k in true_mmpp}}
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized sweep (seconds, not minutes)")
    ap.add_argument("--out", default="BENCH_traces.json")
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
            args.checkpoint_dir, kind="trace_scale",
            config_hash=pytree_hash(("trace_scale", bool(args.smoke),
                                     int(args.epochs))),
            resume=args.resume)
        if sc.resumed:
            done = {k: len(v) for k, v in sc.sections.items()}
            print(f"resuming: replaying completed records {done}")

    def cached(section, index, fn):
        return sc.cached(section, index, fn) if sc is not None else fn()

    from repro.obs import Obs, RunManifest
    obs = Obs(args.obs_dir) if args.obs_dir else None
    manifest = RunManifest.create("trace_scale", horizon=args.epochs,
                                  smoke=args.smoke)
    if obs is not None:
        if sc is not None and sc.resumed:
            obs.event("resume", run_kind="trace_scale", step=sc.step,
                      config_hash=sc.config_hash,
                      checkpoint_dir=args.checkpoint_dir)
        else:
            manifest = obs.write_manifest("trace_scale", horizon=args.epochs,
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
        # acceptance: a >= 1e6-client x >= 50-epoch sharded sweep in CI's
        # 8-device emulated job
        sharded = [(1_000_000, max(50, args.epochs // 2))]
        fit_n, fit_r = 128, 192
    else:
        sizes = [1_000, 100_000, 1_000_000]
        sharded = [(1_000_000, args.epochs), (10_000_000, args.epochs)]
        fit_n, fit_r = 256, 480

    results = []
    for n in sizes:
        for bench in (bench_fleet, bench_serve):
            with _span("results"):
                rec = cached("results", len(results),
                             lambda n=n, bench=bench: bench(n, args.epochs))
            results.append(rec)
            _note("results", rec)
            per_s = rec.get("client_rounds_per_s",
                            rec.get("client_epochs_per_s"))
            print(f"N={n:>9,} {rec['scan']:>6} run={rec['run_s']:.3f}s  "
                  f"client-steps/s={per_s:.2e}", flush=True)

    sharded_results = []
    n_dev = jax.device_count()
    if n_dev > 1:
        mesh = make_data_mesh()
        for n, epochs in sharded:
            with _span("sharded"):
                rec = cached("sharded", len(sharded_results),
                             lambda n=n, e=epochs:
                             bench_serve(n, e, mesh=mesh))
            sharded_results.append(rec)
            _note("sharded", rec)
            print(f"N={n:>9,}  serve sharded/{n_dev}dev epochs={epochs} "
                  f"run={rec['run_s']:.3f}s  "
                  f"client-epochs/s={rec['client_epochs_per_s']:.2e}",
                  flush=True)
    else:
        print("single device: skipping sharded section "
              "(set XLA_FLAGS=--xla_force_host_platform_device_count=8)")

    with _span("calibration"):
        cal = cached("calibration", 0,
                     lambda: bench_calibration(fit_n, fit_r))
    for name in ("markov_solar", "diurnal_poisson", "mmpp"):
        print(f"calibration {name}: true={cal[name]['true']} "
              f"fitted={cal[name]['fitted']} ({cal[name]['fit_s']}s)",
              flush=True)

    out = {"bench": "trace_scale", "smoke": args.smoke, "epochs": args.epochs,
           "devices": n_dev, "manifest": manifest.to_dict(),
           "results": results, "sharded": sharded_results,
           "calibration": cal}
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    if obs is not None:
        obs.close()
    print(f"wrote {args.out}")

    if args.history:
        try:                              # `python -m benchmarks.trace_scale`
            from benchmarks._fmt import append_history
        except ImportError:               # `python benchmarks/trace_scale.py`
            from _fmt import append_history
        fleet = [r for r in results if r["scan"] == "fleet"]
        serve = [r for r in results if r["scan"] == "serve"]
        append_history(args.history, "trace_scale", {
            "max_client_rounds_per_s": max(r["client_rounds_per_s"]
                                           for r in fleet),
            "max_client_epochs_per_s": max(r["client_epochs_per_s"]
                                           for r in serve),
            "solar_day_mean_abs_err": round(abs(
                cal["markov_solar"]["fitted"]["day_mean"]
                - cal["markov_solar"]["true"]["day_mean"]), 4),
        }, out["manifest"], smoke=args.smoke)
        print(f"appended headline to {args.history}")


if __name__ == "__main__":
    main()
