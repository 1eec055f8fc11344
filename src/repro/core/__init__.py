"""Core: the paper's contribution (energy-aware scheduling + scaled
aggregation).

Scheduling here is *stateless* (assumed renewal cycles ``E``); the physical
energy layer — stochastic harvest arrivals, battery dynamics, device cost
models, and the fleet-scale battery-gated simulator — lives in
``repro.energy`` and plugs into ``simulate`` via its ``energy=`` hook.
"""
from repro.core.scheduling import (
    EnergyProfile,
    Policy,
    aggregation_scale,
    always_schedule,
    energy_feasible,
    greedy_schedule,
    participation_mask,
    sustainable_schedule,
    wait_all_schedule,
)
from repro.core.aggregation import (
    aggregate,
    accumulate_client_delta,
    apply_accumulated,
    fedavg_aggregate,
    scaled_delta_aggregate,
    zeros_like_fp32,
)
from repro.core.round import (
    FedConfig,
    finish_sequential_round,
    local_update,
    parallel_round,
    participant_round,
    run_rounds,
    sequential_client_step,
)
from repro.core.convergence import Theorem1Constants
from repro.core.simulate import SimResult, simulate

__all__ = [
    "EnergyProfile", "Policy", "aggregation_scale", "always_schedule",
    "energy_feasible", "greedy_schedule", "participation_mask",
    "sustainable_schedule", "wait_all_schedule",
    "aggregate", "accumulate_client_delta", "apply_accumulated",
    "fedavg_aggregate", "scaled_delta_aggregate", "zeros_like_fp32",
    "FedConfig", "finish_sequential_round", "local_update", "parallel_round",
    "participant_round", "run_rounds", "sequential_client_step",
    "Theorem1Constants",
    "SimResult", "simulate",
]
