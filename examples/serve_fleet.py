"""Battery-gated serving under solar day/night harvest + diurnal traffic.

The paper's devices spend most of their life *answering queries*, not
training — and the energy-footprint literature (Savazzi et al. 2022) shows
inference traffic dominates a deployed FL fleet's lifetime joules.  This
example puts a 100k-client solar fleet under a day/night harvest cycle and
time-zone-scattered diurnal query traffic (`repro.serve`), with a federated
training schedule competing for the same batteries, and compares three
admission strategies:

* **energy-agnostic** — serve every request at full generation length; the
  battery is discovered empty mid-epoch (deadline misses) and training
  starves;
* **battery-gated** — `BatteryGated` admission with hedging margins:
  degrade to short generations early, shed only when truly broke;
* **controlled** — the same gated policy with the closed-loop
  `AdmissionRule` (`energy.control.ServerController`) adapting the
  admission-threshold scale from shed/miss/depletion telemetry each day.

Run:  PYTHONPATH=src python examples/serve_fleet.py           # synthetic
      PYTHONPATH=src python examples/serve_fleet.py --trace   # replay the
                                          # bundled solar + request-log
                                          # day profiles (repro.traces)

``--trace``/``--synthetic``, ``--seed`` and ``--trace-path`` are the shared
scenario flags (`examples/_cli.py`, same plumbing as
`examples/energy_fleet.py`): both modes run the same scenario scale and
seeds, so trace and synthetic results are directly comparable.

Add devices to shard the client axis, e.g.
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` — `simulate_serve`
passes ``mesh=`` straight through to the sharded fleet path.
`benchmarks/serve_scale.py` records this comparison (plus throughput sweeps)
in ``BENCH_serve.json`` per PR.
"""
import argparse

import jax
import numpy as np

from _cli import (add_scenario_flags, assistant_traffic, checkpoint_args,
                  make_obs, scenario_name, solar_harvest)
from repro.energy import (AdmissionRule, BatteryConfig, ControlBounds,
                          DecodeCostModel, ServerController)
from repro.launch.mesh import make_data_mesh
from repro.serve import (BatteryGated, EnergyAgnostic, QoSSpec, ServeConfig,
                         TrainLoad, run_serve_controlled, simulate_serve)

ap = add_scenario_flags(argparse.ArgumentParser(description=__doc__),
                        clients=100_000)
ap.add_argument("--microbench", metavar="ARCH", nargs="?",
                const="mamba2-1.3b", default=None,
                help="price requests from *measured* decode-engine stage "
                     "timings (repro.serve.microbench) on this smoke arch "
                     "instead of the analytic 2N-FLOPs model; on the host "
                     "CPU the numbers price a proxy of the edge device")
args = ap.parse_args()
N, EPOCHS, CONTROL_EVERY = args.clients, 192, 24

# query traffic: ~1 request/client/epoch, day/night modulated (replayed
# request-log profiles under --trace, the DiurnalPoisson twin otherwise)
traffic = assistant_traffic(args, N, base=1.0)
# solar harvest: ~50% day fraction, 3 J mean per daytime epoch
harvest = solar_harvest(args, N, day_mean=3.0)
battery = BatteryConfig(capacity=8.0, leak=0.01, init_charge=2.0)
# ~100M-active-param on-device model: ~0.77 J per full request (256 generated
# tokens), ~0.32 J degraded (32 tokens)
cost = DecodeCostModel.from_params(1e8)
if args.microbench:
    # measured pricing: time the engine's prefill/decode/insert stages warm
    # on materialized outputs and convert s/token -> J/token at the nominal
    # device wattage (DecodeCostModel.from_microbench)
    from repro.configs import get_smoke_config
    from repro.models import get_model
    from repro.serve import engine_microbench, measured_cost

    mcfg = get_smoke_config(args.microbench)
    mmodel = get_model(mcfg)
    rec = engine_microbench(mmodel, mmodel.init_params(jax.random.PRNGKey(0)))
    cost = measured_cost(rec)
    print(f"microbench pricing ({mcfg.name}, {rec['device_watts']:.1f} W "
          f"host proxy): decode "
          f"{rec['joules_per_decode_token_measured']:.2e} J/tok measured "
          f"vs {rec['joules_per_decode_token_analytic']:.2e} analytic; "
          f"prefill {rec['prefill_tok_s']:.0f} tok/s, decode step "
          f"{rec['decode_step_ms']:.2f} ms, insert {rec['insert_ms']:.2f} ms\n")
qos = QoSSpec(prompt_tokens=128.0, full_decode_tokens=256.0,
              short_decode_tokens=32.0)
# a federated training round every ~4 epochs, 0.2 J, from the SAME battery
train = TrainLoad.create(np.full(N, 4), 0.2)
cfg = ServeConfig(num_clients=N, seed=args.seed)

mesh = None
if jax.device_count() > 1:
    mesh = make_data_mesh()
    print(f"sharding the client axis over {jax.device_count()} devices\n")

full_j = float(np.asarray(qos.request_cost(cost)))
short_j = float(np.asarray(qos.request_cost(cost, degraded=True)))
print(f"fleet: N={N:,}, {EPOCHS} epochs, {scenario_name(args)} scenario, "
      f"seed={args.seed}; request={full_j:.2f} J full / "
      f"{short_j:.2f} J degraded; training round=0.2 J every ~4 epochs\n")

runs = {
    "agnostic": simulate_serve(traffic, harvest, battery, cost, qos,
                               EnergyAgnostic(), cfg, EPOCHS, train=train,
                               mesh=mesh, backend=args.backend),
    "gated": simulate_serve(traffic, harvest, battery, cost, qos,
                            BatteryGated.create(N, hi=2.0, lo=1.5), cfg,
                            EPOCHS, train=train, mesh=mesh,
                            backend=args.backend),
}
controller = ServerController(T0=5, E0=4, rules=(AdmissionRule(),),
                              bounds=ControlBounds())
obs = make_obs(args)
runs["controlled"], controller = run_serve_controlled(
    traffic, harvest, battery, cost, qos, BatteryGated.create(N), cfg,
    EPOCHS, controller, train_cost=0.2, control_every=CONTROL_EVERY,
    mesh=mesh, backend=args.backend, obs=obs, hist=args.hist,
    **checkpoint_args(args))
if obs is not None:
    obs.close()
    print(f"obs events (controlled run) -> {obs.log.path}"
          + ("  (python -m repro.obs.report dist for SoC/streak quantiles)"
             if args.hist else "") + "\n")

print(f"{'':>12} {'served%':>8} {'degr%':>6} {'shed%':>6} {'miss%':>6} "
      f"{'depl%':>6} {'train%':>7} {'J/tok':>8}")
for name, res in runs.items():
    s = res.stats
    off = max(s["offered"].sum(), 1e-9)
    print(f"{name:>12} {100 * (s['served_full'].sum() + s['served_short'].sum()) / off:8.2f} "
          f"{100 * s['served_short'].sum() / off:6.2f} "
          f"{100 * s['shed'].sum() / off:6.2f} "
          f"{100 * s['deadline_missed'].sum() / off:6.2f} "
          f"{100 * s['frac_depleted'].mean():6.2f} "
          f"{100 * s['participants'].mean() / N:7.2f} "
          f"{res.joules_per_token:8.4f}")

print("\nadmission-controller trajectory (per day):")
print("  admit :", [round(t["admit"], 2) for t in controller.trace])
print("  shed% :", [round(100 * t["telemetry"].shed_rate, 1)
                    for t in controller.trace])
print("  depl% :", [round(100 * t["telemetry"].frac_depleted, 1)
                    for t in controller.trace])

agn, gated = runs["agnostic"].stats, runs["gated"].stats
off_a = max(agn["offered"].sum(), 1e-9)
off_g = max(gated["offered"].sum(), 1e-9)
un_a = (agn["shed"].sum() + agn["deadline_missed"].sum()) / off_a
un_g = (gated["shed"].sum() + gated["deadline_missed"].sum()) / off_g
print(f"\nunanswered requests: {100 * un_a:.1f}% (agnostic) -> "
      f"{100 * un_g:.1f}% (gated), depletion "
      f"{100 * agn['frac_depleted'].mean():.1f}% -> "
      f"{100 * gated['frac_depleted'].mean():.1f}%")
