"""Battery-aware server control under a solar drought.

The paper's server is energy-blind: it fixes the round cadence ``T`` and the
per-group renewal cycles ``E`` up front and never looks back.  This example
puts a 50k-client solar fleet through a *drought* (short days, ~20-round
nights) and compares that static schedule against the closed-loop
`ServerController` (hysteresis + AIMD, `repro.energy.control`), which reads
the fleet's per-round telemetry — depleted fraction, wasted overflow,
realized participation — and adapts ``T`` and per-group ``E`` online:

* rounds get cheaper (``T`` backs off multiplicatively) while batteries are
  depleted, so more clients can afford their scheduled slot;
* groups are asked less often (``E`` grows) only while asked slots are
  actually being *missed*, so the ask rate settles at what the harvest
  sustains instead of oscillating.

Run:  PYTHONPATH=src python examples/battery_control.py

Add more devices to shard the client axis, e.g.
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` — `run_controlled`
passes ``mesh=`` straight through to the sharded fleet path.  Pass
``--checkpoint-dir DIR`` to checkpoint the controlled run at chunk
boundaries and ``--resume`` to pick an interrupted run back up, bit-exactly
(DESIGN.md §13).
"""
import argparse

import jax
import numpy as np

from repro.core import EnergyProfile, Policy
from repro.energy import (BatteryConfig, ControlBounds, DeviceCostModel,
                          FleetConfig, MarkovSolar, ServerController,
                          run_controlled, simulate_fleet)
from repro.launch.mesh import make_data_mesh

ap = argparse.ArgumentParser(description=__doc__)
ap.add_argument("--checkpoint-dir", default=None,
                help="save chunk-boundary checkpoints of the controlled run "
                     "here (repro.checkpoint.resume)")
ap.add_argument("--resume", action="store_true",
                help="resume the controlled run from the newest intact "
                     "checkpoint in --checkpoint-dir")
ap.add_argument("--hist", action="store_true",
                help="distributional telemetry (DESIGN.md §14): in-scan "
                     "SoC/spend/depletion-streak histograms; prints the "
                     "controlled run's SoC sparkline + tail quantiles")
ap.add_argument("--depletion-signal", choices=("mean", "p95"),
                default="mean",
                help="which depletion statistic the control rules act on: "
                     "the per-period mean (default) or the p95 over the "
                     "period's rounds — the tail-aware controller reacts to "
                     "droughts the mean smooths away")
args = ap.parse_args()
if args.resume and not args.checkpoint_dir:
    raise SystemExit("--resume requires --checkpoint-dir")

N, ROUNDS, CONTROL_EVERY = 50_000, 200, 10

# drought solar: expected day length 2.5 rounds, night length 20 rounds
process = MarkovSolar.create(N, p_stay_day=0.6, p_stay_night=0.95,
                             day_mean=0.9)
battery = BatteryConfig(capacity=6.0, leak=0.01, init_charge=1.0)
# rounds are priced by the cost model, so the controller's T moves real joules
cost = DeviceCostModel(joules_per_step=0.3, joules_per_upload=0.25,
                       joules_per_download=0.25)
profile = EnergyProfile(N)
E0 = np.asarray(profile.cycles())
cfg = FleetConfig(num_clients=N, policy=Policy.SUSTAINABLE, seed=0,
                  local_steps=5)

mesh = None
if jax.device_count() > 1:
    mesh = make_data_mesh()
    print(f"sharding the client axis over {jax.device_count()} devices\n")

print(f"fleet: N={N:,}, {ROUNDS} rounds of solar drought "
      f"(T0={cfg.local_steps} -> {cost.round_cost(cfg.local_steps):.1f} J/round)\n")

static = simulate_fleet(process, battery, cost, cfg, ROUNDS, E=E0, mesh=mesh)

from repro.energy.control import BudgetRule, CadenceRule  # noqa: E402

controller = ServerController(
    T0=cfg.local_steps, E0=profile.taus,
    groups=np.arange(N) % len(profile.taus),
    rules=(CadenceRule(signal=args.depletion_signal),
           BudgetRule(signal=args.depletion_signal)),
    bounds=ControlBounds(t_min=1, t_max=10, e_min=1, e_max=64))
controlled, controller = run_controlled(
    process, battery, cost, cfg, ROUNDS, controller,
    control_every=CONTROL_EVERY, mesh=mesh, hist=args.hist,
    checkpoint=args.checkpoint_dir, resume=args.resume)

print(f"{'':>12} {'part%':>7} {'depleted%':>9} {'spent J':>10} {'wasted J':>10}")
for name, res in [("static", static), ("controlled", controlled)]:
    s = res.stats
    print(f"{name:>12} {100 * res.participation_rate.mean():7.2f} "
          f"{100 * s['frac_depleted'].mean():9.2f} "
          f"{s['consumed'].sum():10.0f} {s['overflowed'].sum():10.0f}")

print("\ncontroller trajectory (per control period):")
print("  T      :", [t["T"] for t in controller.trace])
print("  E mean :", [round(t["E_mean"], 1) for t in controller.trace])
print("  depl%  :", [round(100 * t["telemetry"].frac_depleted, 1)
                     for t in controller.trace])

gain = (controlled.participation_rate.mean()
        / max(static.participation_rate.mean(), 1e-9) - 1)
print(f"\nparticipation gain vs static schedule: {100 * gain:+.1f}%")

if args.hist:
    # whole-run SoC + drought-streak distributions from the in-scan
    # histograms (DESIGN.md §14) — the tail the per-round means hide
    from repro.obs.hist import SPECS_BY_NAME, quantiles_from_counts, \
        sparkline
    print("\ndistributional telemetry (controlled run, whole horizon):")
    for name in ("hist_soc", "hist_streak"):
        spec = SPECS_BY_NAME[name]
        counts = np.asarray(controlled.stats[name]).reshape(
            -1, spec.bins).sum(0)
        q = quantiles_from_counts(counts, spec)
        print(f"  {spec.buf:>10} [{spec.lo:g},{spec.hi:g}) "
              f"|{sparkline(counts)}|  p50={q['p50']:g} p95={q['p95']:g} "
              f"p99={q['p99']:g}")
