"""Fleet-scale energy scenario sweep: 200k solar-harvesting clients.

Compares the battery-gated scheduling policies (Algorithm 1's sustainable
slot draw, greedy, threshold-greedy) under a day/night "solar" harvest with
a compound-Poisson ambient-RF side channel — scenarios the static
renewal-cycle model cannot express.  The whole fleet (battery charge,
process state, telemetry) advances in ONE jitted lax.scan per policy; no
per-client Python loops.

  PYTHONPATH=src python examples/energy_fleet.py              # synthetic
  PYTHONPATH=src python examples/energy_fleet.py --trace      # NSRDB-style
                                                              # profile replay

``--trace``/``--synthetic``, ``--seed`` and ``--trace-path`` are the shared
scenario flags (`examples/_cli.py`): both modes run the SAME scenario scale
and seed plumbing, so the only difference is the *shape* of the arrival law
— replayed measured day profiles vs their calibratable synthetic twin
(`examples/trace_fleet.py` closes that loop with `repro.traces.fit`).

Also shows the closed-loop training hook: `core.simulate(..., energy=
EnergyLoop(...))` drives an actual (tiny) training run from realized
harvests instead of assumed cycles.

Follow-ons: ``examples/battery_control.py`` closes the *server* loop too
(`ServerController` adapting T/E from this telemetry), and any
`simulate_fleet` call here takes ``mesh=`` to shard the client axis
(`repro.dist.sharding.fleet_spec`) over multi-device meshes.
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

from _cli import add_scenario_flags, make_obs, scenario_name, solar_harvest
from repro.core import EnergyProfile, FedConfig, Policy, simulate
from repro.energy import (BatteryConfig, CompoundPoisson, EnergyLoop,
                          FleetConfig, MarkovSolar, Scaled, Sum,
                          simulate_fleet)
from repro.optim import sgd

args = add_scenario_flags(argparse.ArgumentParser(description=__doc__), clients=200_000) \
    .parse_args()
N, ROUNDS = args.clients, 150

# solar panel (replayed or Markov day/night regime) + a weak always-on
# ambient-RF scavenger; per-client panel gain spread of 4x — `Sum`/`Scaled`
# composition works identically over trace and synthetic base processes
rs = np.random.RandomState(args.seed)
process = Sum((
    Scaled.create(solar_harvest(args, N, day_mean=0.9, p_stay=0.92),
                  gain=rs.uniform(0.5, 2.0, N).astype(np.float32)),
    CompoundPoisson.create(N, rate=0.1, mean_amount=0.3),
))
battery = BatteryConfig(capacity=2.5, leak=0.02, init_charge=0.5)
E = np.asarray(EnergyProfile(N).cycles())  # the paper's §V profile
obs = make_obs(args)

print(f"fleet: N={N:,} clients, {ROUNDS} rounds, "
      f"{scenario_name(args)} solar + RF harvest, seed={args.seed}\n")
print(f"{'policy':>12} {'part%':>7} {'spent J':>10} {'wasted J':>10} "
      f"{'leaked J':>9} {'depleted%':>9}")
for policy, thr in [(Policy.SUSTAINABLE, 1.0), (Policy.GREEDY, 1.0),
                    (Policy.THRESHOLD, 1.5)]:
    cfg = FleetConfig(num_clients=N, policy=policy, threshold=thr,
                      seed=args.seed)
    res = simulate_fleet(process, battery, 1.0, cfg, ROUNDS, E=E,
                         backend=args.backend, obs=obs, hist=args.hist)
    s = res.stats
    print(f"{policy.value:>12} {100*res.participation_rate.mean():7.2f} "
          f"{s['consumed'].sum():10.0f} {s['overflowed'].sum():10.0f} "
          f"{s['leaked'].sum():9.0f} {100*s['frac_depleted'].mean():9.2f}")

# --- closed-loop training: masks from realized harvests ---------------------
print("\nclosed-loop training (8 clients, threshold policy):")
C = 8
loop = EnergyLoop(MarkovSolar.create(C, day_mean=0.8),
                  BatteryConfig(capacity=3.0, leak=0.01), 1.0)
b = jnp.linspace(-1.0, 1.0, C)


def loss(params, batch, rng):
    return 0.5 * jnp.sum((params["w"] - b[batch["client"]]) ** 2), {}


def batch_fn(rnd, i):
    return {"client": jnp.full((2,), i, jnp.int32)}


fed = FedConfig(num_clients=C, local_steps=2, policy=Policy.THRESHOLD,
                seed=args.seed)
res = simulate(loss, sgd(0.2), fed, {"w": jnp.zeros(())}, batch_fn,
               np.ones(C) / C, np.ones(C, np.int32), 20,
               jax.random.PRNGKey(args.seed), energy=loop)
for h in res.history[::5]:
    print(f"  round {h['round']:2d}: participants={h['participants']} "
          f"mean_charge={h['energy_mean_charge']:.2f} "
          f"loss={h.get('loss', float('nan')):.4f}")
if obs is not None:
    obs.close()
    print(f"\nobs events -> {obs.log.path}")
