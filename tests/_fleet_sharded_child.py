"""Child process for ``test_fleet_sharded.py``: runs under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the tier-1 pytest
process must keep the real single CPU device — see conftest) and asserts
mesh-sharded vs host-local bit-exactness of `simulate_fleet` for every
fleet policy, on N both divisible and not divisible by the client-axis
size, plus jit-cache reuse on the sharded path.  Exits non-zero on any
failure; the parent test checks the return code.
"""
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import EnergyProfile, Policy
from repro.energy import (BatteryConfig, Bernoulli, FleetConfig, MarkovSolar,
                          TraceHarvest, simulate_fleet)
from repro.energy.fleet import FLEET_POLICIES, _run_fleet_scan
from repro.launch.mesh import make_data_mesh, make_mesh


def check_parity(mesh, n, rounds=30):
    """Bit-exact masks AND telemetry: exact-arithmetic config (zero leak,
    dyadic packet/cost/threshold grid), so every fp32 partial sum is exact
    and the 8-way reduction tree cannot round differently than the
    single-device one."""
    E = np.asarray(EnergyProfile(n).cycles())
    proc = Bernoulli.create(n, prob=0.375, amount=1.25)
    bat = BatteryConfig(capacity=2.5, leak=0.0, init_charge=0.5)
    for pol in FLEET_POLICIES:
        cfg = FleetConfig(num_clients=n, policy=pol, threshold=1.5, seed=3)
        kw = dict(E=E, record_masks=True)
        host = simulate_fleet(proc, bat, 0.75, cfg, rounds, **kw)
        shard = simulate_fleet(proc, bat, 0.75, cfg, rounds, mesh=mesh, **kw)
        assert np.array_equal(np.asarray(host.masks),
                              np.asarray(shard.masks)), (n, pol, "masks")
        assert np.array_equal(np.asarray(host.final_charge),
                              np.asarray(shard.final_charge)), (n, pol)
        for k in host.stats:
            assert np.array_equal(host.stats[k], shard.stats[k]), \
                (n, pol, k, host.stats[k] - shard.stats[k])


def check_stochastic(mesh, n, rounds=40):
    """Leaky battery + Markov solar: masks/charge stay bit-exact (all
    per-client state evolution is elementwise); telemetry reductions agree
    to float tolerance."""
    E = np.asarray(EnergyProfile(n).cycles())
    proc = MarkovSolar.create(n, day_mean=0.8)
    bat = BatteryConfig(capacity=2.5, leak=0.03, init_charge=0.5)
    cfg = FleetConfig(num_clients=n, policy=Policy.THRESHOLD, threshold=1.2,
                      seed=1)
    host = simulate_fleet(proc, bat, 1.0, cfg, rounds, E=E, record_masks=True)
    shard = simulate_fleet(proc, bat, 1.0, cfg, rounds, E=E,
                           record_masks=True, mesh=mesh)
    assert np.array_equal(np.asarray(host.masks), np.asarray(shard.masks))
    assert np.array_equal(np.asarray(host.final_charge),
                          np.asarray(shard.final_charge))
    for k in host.stats:
        assert np.allclose(host.stats[k], shard.stats[k], rtol=1e-5), k


def check_trace_parity(mesh, n, rounds=30):
    """`TraceHarvest` replay on the sharded client axis: dyadic table values
    and zero leak keep every quantity on the exact fp32 grid, so masks AND
    telemetry must be bit-exact with host-local — the trace table (T=12, P=3)
    carries no client axis and rides along replicated."""
    E = np.asarray(EnergyProfile(n).cycles())
    table = np.asarray([[0.25, 2.0, 0.5], [1.5, 0.0, 1.0], [3.0, 0.5, 0.0],
                        [0.0, 1.25, 2.5]] * 3, np.float32)   # (12, 3) dyadic
    proc = TraceHarvest.create(table, n, seed=5)
    bat = BatteryConfig(capacity=2.5, leak=0.0, init_charge=0.5)
    for pol in FLEET_POLICIES:
        cfg = FleetConfig(num_clients=n, policy=pol, threshold=1.5, seed=3)
        kw = dict(E=E, record_masks=True)
        host = simulate_fleet(proc, bat, 0.75, cfg, rounds, **kw)
        shard = simulate_fleet(proc, bat, 0.75, cfg, rounds, mesh=mesh, **kw)
        assert np.array_equal(np.asarray(host.masks),
                              np.asarray(shard.masks)), (n, pol, "masks")
        assert np.array_equal(np.asarray(host.final_charge),
                              np.asarray(shard.final_charge)), (n, pol)
        for k in host.stats:
            assert np.array_equal(host.stats[k], shard.stats[k]), \
                (n, pol, k, host.stats[k] - shard.stats[k])


def check_kernel_parity(mesh, n, rounds=20):
    """The fused-kernel sharded parity oracle: ``backend="pallas"`` on the
    8-device mesh (per-shard Pallas tile grids + psum-ed stat partials,
    interpret mode) must be bit-exact with the host-local lax reference on
    the exact-arithmetic config, masks, charge, fleet-wide AND per-group
    telemetry, for every fleet policy."""
    E = np.asarray(EnergyProfile(n).cycles())
    proc = Bernoulli.create(n, prob=0.375, amount=1.25)
    bat = BatteryConfig(capacity=2.5, leak=0.0, init_charge=0.5)
    groups = np.arange(n) % 3
    for pol in FLEET_POLICIES:
        cfg = FleetConfig(num_clients=n, policy=pol, threshold=1.5, seed=3)
        kw = dict(E=E, record_masks=True, groups=groups, num_groups=3)
        host = simulate_fleet(proc, bat, 0.75, cfg, rounds, **kw)
        fused = simulate_fleet(proc, bat, 0.75, cfg, rounds, mesh=mesh,
                               backend="pallas", **kw)
        assert np.array_equal(np.asarray(host.masks),
                              np.asarray(fused.masks)), (n, pol, "masks")
        assert np.array_equal(np.asarray(host.final_charge),
                              np.asarray(fused.final_charge)), (n, pol)
        for k in host.stats:
            assert np.array_equal(host.stats[k], fused.stats[k]), \
                (n, pol, k, host.stats[k] - fused.stats[k])


def check_hist_parity(mesh, n, rounds=20):
    """The DESIGN.md §14 histogram contract on the mesh: ``hist=True``
    sharded (lax AND pallas backends) must be bit-exact with host-local —
    the psum of per-shard validity-weighted bincounts is a sum of {0,1}
    weights, so the counts are exact integers in fp32 regardless of the
    reduction tree, and padded phantom lanes (n=21 -> 24) must contribute
    zero counts.  The carried depletion streak is per-client elementwise
    state, so `final_streak` must match bit-exactly too."""
    E = np.asarray(EnergyProfile(n).cycles())
    proc = Bernoulli.create(n, prob=0.375, amount=1.25)
    bat = BatteryConfig(capacity=2.5, leak=0.0, init_charge=0.5)
    for pol in FLEET_POLICIES:
        cfg = FleetConfig(num_clients=n, policy=pol, threshold=1.5, seed=3)
        kw = dict(E=E, hist=True)
        host = simulate_fleet(proc, bat, 0.75, cfg, rounds, **kw)
        for backend in ("lax", "pallas"):
            shard = simulate_fleet(proc, bat, 0.75, cfg, rounds, mesh=mesh,
                                   backend=backend, **kw)
            for k in host.stats:
                assert np.array_equal(host.stats[k], shard.stats[k]), \
                    (n, pol, backend, k)
            assert np.array_equal(np.asarray(host.final_charge),
                                  np.asarray(shard.final_charge)), \
                (n, pol, backend)
            assert np.array_equal(np.asarray(host.final_streak),
                                  np.asarray(shard.final_streak)), \
                (n, pol, backend, "streak")
            # every histogram row counts exactly the n real clients —
            # phantom padding lanes carry valid=0 and land in no bin
            for hk in ("hist_soc", "hist_spend", "hist_streak"):
                sums = np.asarray(shard.stats[hk]).sum(axis=-1)
                assert np.array_equal(sums, np.full_like(sums, n)), \
                    (n, pol, backend, hk, sums)


def check_sharded_cache_reuse(mesh, n):
    """Repeat sharded calls with different seeds/thresholds must hit the jit
    cache (same shapes, same shardings), and flipping ``backend`` costs
    exactly one extra entry."""
    E = np.asarray(EnergyProfile(n).cycles())
    proc = Bernoulli.create(n, prob=0.4)
    bat = BatteryConfig(capacity=2.0, leak=0.01)

    def run(seed, threshold, backend="lax"):
        cfg = FleetConfig(num_clients=n, policy=Policy.THRESHOLD, seed=seed,
                          threshold=threshold)
        return simulate_fleet(proc, bat, 1.0, cfg, 10, E=E, mesh=mesh,
                              backend=backend)

    run(0, 1.0)
    size = _run_fleet_scan._cache_size()
    run(7, 1.3)
    run(11, 0.8)
    assert _run_fleet_scan._cache_size() == size, \
        "sharded simulate_fleet retraced on a seed/threshold sweep"
    run(0, 1.0, backend="pallas")
    assert _run_fleet_scan._cache_size() == size + 1, \
        "sharded backend='pallas' cost more than one extra cache entry"
    run(7, 1.3, backend="pallas")
    run(11, 0.8, backend="pallas")
    assert _run_fleet_scan._cache_size() == size + 1, \
        "sharded simulate_fleet retraced on a backend/seed sweep"


def check_obs_noop(mesh, n, big_n=1_000_000):
    """The PR-7 obs contract on the sharded path: `run_controlled` with an
    `Obs` (manifest + per-chunk round/control/span events) is bit-exact with
    ``obs=None`` and adds ZERO `_run_fleet_scan` cache entries, at fleet
    scale (``big_n`` clients); the in-scan `io_callback` tap (small n) also
    leaves results and the un-tapped scan's cache untouched."""
    import tempfile

    from repro.energy import ControlBounds, ServerController, run_controlled
    from repro.obs import Obs, load_events

    proc = MarkovSolar.create(big_n, day_mean=0.9)
    bat = BatteryConfig(capacity=4.0, leak=0.01, init_charge=1.0)
    cfg = FleetConfig(num_clients=big_n, policy=Policy.SUSTAINABLE, seed=2,
                      local_steps=5)

    def controller():
        return ServerController(
            T0=cfg.local_steps, E0=4,
            bounds=ControlBounds(t_min=1, t_max=10, e_min=1, e_max=64))

    base, _ = run_controlled(proc, bat, 0.4, cfg, 30, controller(),
                             control_every=10, mesh=mesh)
    size = _run_fleet_scan._cache_size()
    with tempfile.TemporaryDirectory() as d:
        with Obs(d) as obs:
            res, _ = run_controlled(proc, bat, 0.4, cfg, 30, controller(),
                                    control_every=10, mesh=mesh, obs=obs)
        events = load_events(obs.log.path)
    assert _run_fleet_scan._cache_size() == size, \
        "obs= grew the fleet scan's jit cache on the sharded path"
    assert np.array_equal(np.asarray(base.final_charge),
                          np.asarray(res.final_charge))
    for k in base.stats:
        assert np.array_equal(base.stats[k], res.stats[k]), k
    kinds = [e["kind"] for e in events]
    assert kinds[0] == "manifest" and events[0]["run_kind"] \
        == "fleet_controlled"
    assert sum(k == "round" for k in kinds) == 30
    assert sum(k == "control" for k in kinds) == 3
    assert sum(k == "retrace_warning" for k in kinds) == 0

    # in-scan io_callback tap (small n): bit-exact, un-tapped cache unmoved
    E = np.asarray(EnergyProfile(n).cycles())
    proc = Bernoulli.create(n, prob=0.375, amount=1.25)
    bat = BatteryConfig(capacity=2.5, leak=0.0, init_charge=0.5)
    cfg = FleetConfig(num_clients=n, policy=Policy.THRESHOLD, threshold=1.5,
                      seed=3)
    host = simulate_fleet(proc, bat, 0.75, cfg, 20, E=E, mesh=mesh)
    size = _run_fleet_scan._cache_size()
    with tempfile.TemporaryDirectory() as d:
        with Obs(d, tap=True) as obs:
            tapped = simulate_fleet(proc, bat, 0.75, cfg, 20, E=E, mesh=mesh,
                                    obs=obs)
        events = load_events(obs.log.path)
    assert _run_fleet_scan._cache_size() == size, \
        "the io_callback tap touched the un-tapped scan's jit cache"
    for k in host.stats:
        assert np.array_equal(host.stats[k], tapped.stats[k]), k
    rounds = sorted((e for e in events if e["kind"] == "round"),
                    key=lambda e: e["round"])
    assert [e["round"] for e in rounds] == list(range(20))
    assert all(abs(r["participants"] - float(host.stats["participants"][i]))
               < 1e-6 for i, r in enumerate(rounds))


def main():
    n_dev = len(jax.devices())
    assert n_dev == 8, f"expected 8 emulated CPU devices, got {n_dev}"
    mesh = make_data_mesh(8)
    check_parity(mesh, n=24)    # divisible by the 8-way client axis
    check_parity(mesh, n=21)    # padded 21 -> 24 (phantom-lane path)
    check_stochastic(mesh, n=24)
    check_stochastic(mesh, n=21)
    check_trace_parity(mesh, n=24)
    check_trace_parity(mesh, n=21)
    check_kernel_parity(mesh, n=24)
    check_kernel_parity(mesh, n=21)
    check_hist_parity(mesh, n=24)
    check_hist_parity(mesh, n=21)
    check_sharded_cache_reuse(mesh, n=32)
    check_obs_noop(mesh, n=24)
    # a mesh with a model axis: fleet state shards over data axes only
    mesh2 = make_mesh((4, 2), ("data", "model"))
    check_parity(mesh2, n=21)   # padded 21 -> 24 (4-way data axis)
    check_kernel_parity(mesh2, n=21)
    print("sharded parity OK")


if __name__ == "__main__":
    main()
