"""Child process for ``test_serve.py``: runs under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the tier-1 pytest
process must keep the real single CPU device — see conftest) and asserts
mesh-sharded vs host-local bit-exactness of `simulate_serve` for every
admission policy, on N both divisible and not divisible by the client-axis
size, plus jit-cache reuse on the sharded path.  Exits non-zero on any
failure; the parent test checks the return code.
"""
import numpy as np

import jax

from repro.energy import (BatteryConfig, Bernoulli, DecodeCostModel,
                          MarkovSolar, TraceHarvest)
from repro.launch.mesh import make_data_mesh, make_mesh
from repro.serve import (BatteryGated, ChargeGated, Constant, DiurnalPoisson,
                         EnergyAgnostic, QoSSpec, ServeConfig, TraceTraffic,
                         TrainLoad, simulate_serve)
from repro.serve.fleet_serve import _run_serve_scan

QOS = QoSSpec(prompt_tokens=64.0, full_decode_tokens=128.0,
              short_decode_tokens=32.0)


def _policies(n):
    return [EnergyAgnostic(), BatteryGated.create(n, hi=1.0, lo=1.0),
            ChargeGated.create(n, hi=1.0, lo=0.25)]


def check_parity(mesh, n, epochs=30):
    """Bit-exact modes AND telemetry: exact-arithmetic config (zero leak,
    integer request counts, dyadic per-token joules), so every fp32 partial
    sum is exact and the 8-way reduction tree cannot round differently than
    the single-device one."""
    traffic = Constant.create(n, rate=2.0)
    harvest = Bernoulli.create(n, prob=0.375, amount=1.25)
    bat = BatteryConfig(capacity=2.5, leak=0.0, init_charge=0.5)
    cost = DecodeCostModel(2.0 ** -8, 2.0 ** -9, 2.0 ** -6)
    train = TrainLoad.create(np.full(n, 4), 0.25)
    for pol in _policies(n):
        cfg = ServeConfig(num_clients=n, seed=3)
        kw = dict(record_modes=True, train=train)
        host = simulate_serve(traffic, harvest, bat, cost, QOS, pol, cfg,
                              epochs, **kw)
        shard = simulate_serve(traffic, harvest, bat, cost, QOS, pol, cfg,
                               epochs, mesh=mesh, **kw)
        assert np.array_equal(np.asarray(host.modes),
                              np.asarray(shard.modes)), (n, pol, "modes")
        assert np.array_equal(np.asarray(host.final_charge),
                              np.asarray(shard.final_charge)), (n, pol)
        for k in host.stats:
            assert np.array_equal(host.stats[k], shard.stats[k]), \
                (n, pol, k, host.stats[k] - shard.stats[k])


def check_stochastic(mesh, n, epochs=40):
    """Diurnal Poisson traffic + Markov solar + leaky battery: modes/charge
    stay bit-exact (all per-client state evolution is elementwise);
    telemetry reductions agree to float tolerance."""
    traffic = DiurnalPoisson.create(n, base=1.5, swing=0.9,
                                    phase=np.arange(n) % 24)
    harvest = MarkovSolar.create(n, day_mean=0.8)
    bat = BatteryConfig(capacity=2.5, leak=0.03, init_charge=0.5)
    cost = DecodeCostModel(1e-3, 2e-3, 5e-2)
    cfg = ServeConfig(num_clients=n, seed=1)
    pol = BatteryGated.create(n, hi=1.2, lo=1.0)
    host = simulate_serve(traffic, harvest, bat, cost, QOS, pol, cfg, epochs,
                          record_modes=True)
    shard = simulate_serve(traffic, harvest, bat, cost, QOS, pol, cfg, epochs,
                           record_modes=True, mesh=mesh)
    assert np.array_equal(np.asarray(host.modes), np.asarray(shard.modes))
    assert np.array_equal(np.asarray(host.final_charge),
                          np.asarray(shard.final_charge))
    for k in host.stats:
        assert np.allclose(host.stats[k], shard.stats[k], rtol=1e-5), k


def check_trace_parity(mesh, n, epochs=30):
    """`TraceTraffic` (deterministic integer-rate replay) + `TraceHarvest`
    (dyadic solar table) on the sharded client axis: the exact-arithmetic
    trace config, so modes AND the full serving ledger must be bit-exact
    with host-local for every admission policy; the (T, P) tables carry no
    client axis and ride along replicated."""
    req_table = np.asarray([[1.0, 3.0], [2.0, 0.0], [0.0, 1.0],
                            [4.0, 2.0]] * 3, np.float32)     # (12, 2) ints
    sol_table = np.asarray([[0.25, 2.0, 0.5], [1.5, 0.0, 1.0],
                            [3.0, 0.5, 0.0], [0.0, 1.25, 2.5]] * 3,
                           np.float32)                        # (12, 3) dyadic
    traffic = TraceTraffic.create(req_table, n, seed=7, poisson=False)
    harvest = TraceHarvest.create(sol_table, n, seed=5)
    bat = BatteryConfig(capacity=2.5, leak=0.0, init_charge=0.5)
    cost = DecodeCostModel(2.0 ** -8, 2.0 ** -9, 2.0 ** -6)
    train = TrainLoad.create(np.full(n, 4), 0.25)
    for pol in _policies(n):
        cfg = ServeConfig(num_clients=n, seed=3)
        kw = dict(record_modes=True, train=train)
        host = simulate_serve(traffic, harvest, bat, cost, QOS, pol, cfg,
                              epochs, **kw)
        shard = simulate_serve(traffic, harvest, bat, cost, QOS, pol, cfg,
                               epochs, mesh=mesh, **kw)
        assert np.array_equal(np.asarray(host.modes),
                              np.asarray(shard.modes)), (n, pol, "modes")
        assert np.array_equal(np.asarray(host.final_charge),
                              np.asarray(shard.final_charge)), (n, pol)
        for k in host.stats:
            assert np.array_equal(host.stats[k], shard.stats[k]), \
                (n, pol, k, host.stats[k] - shard.stats[k])


def check_kernel_parity(mesh, n, epochs=20):
    """The fused-kernel sharded parity oracle, serve side: ``backend=
    "pallas"`` on the 8-device mesh (per-shard Pallas tile grids + psum-ed
    stat partials, interpret mode) must be bit-exact with the host-local lax
    reference on the exact-arithmetic config — modes, charge and the full
    serving ledger, for every admission policy, training load included."""
    traffic = Constant.create(n, rate=2.0)
    harvest = Bernoulli.create(n, prob=0.375, amount=1.25)
    bat = BatteryConfig(capacity=2.5, leak=0.0, init_charge=0.5)
    cost = DecodeCostModel(2.0 ** -8, 2.0 ** -9, 2.0 ** -6)
    train = TrainLoad.create(np.full(n, 4), 0.25)
    for pol in _policies(n):
        cfg = ServeConfig(num_clients=n, seed=3)
        kw = dict(record_modes=True, train=train)
        host = simulate_serve(traffic, harvest, bat, cost, QOS, pol, cfg,
                              epochs, **kw)
        fused = simulate_serve(traffic, harvest, bat, cost, QOS, pol, cfg,
                               epochs, mesh=mesh, backend="pallas", **kw)
        assert np.array_equal(np.asarray(host.modes),
                              np.asarray(fused.modes)), (n, pol, "modes")
        assert np.array_equal(np.asarray(host.final_charge),
                              np.asarray(fused.final_charge)), (n, pol)
        for k in host.stats:
            assert np.array_equal(host.stats[k], fused.stats[k]), \
                (n, pol, k, host.stats[k] - fused.stats[k])


def check_hist_parity(mesh, n, epochs=20):
    """The DESIGN.md §14 histogram contract on the sharded serve path:
    ``hist=True`` (lax AND pallas backends) must be bit-exact with
    host-local — psum-ed validity-weighted bincounts are exact-integer f32
    sums, padded phantom lanes contribute zero counts, and the carried
    depletion streak (elementwise per-client state) matches bit-exactly."""
    traffic = Constant.create(n, rate=2.0)
    harvest = Bernoulli.create(n, prob=0.375, amount=1.25)
    bat = BatteryConfig(capacity=2.5, leak=0.0, init_charge=0.5)
    cost = DecodeCostModel(2.0 ** -8, 2.0 ** -9, 2.0 ** -6)
    train = TrainLoad.create(np.full(n, 4), 0.25)
    for pol in _policies(n):
        cfg = ServeConfig(num_clients=n, seed=3)
        kw = dict(train=train, hist=True)
        host = simulate_serve(traffic, harvest, bat, cost, QOS, pol, cfg,
                              epochs, **kw)
        for backend in ("lax", "pallas"):
            shard = simulate_serve(traffic, harvest, bat, cost, QOS, pol,
                                   cfg, epochs, mesh=mesh, backend=backend,
                                   **kw)
            for k in host.stats:
                assert np.array_equal(host.stats[k], shard.stats[k]), \
                    (n, pol, backend, k)
            assert np.array_equal(np.asarray(host.final_charge),
                                  np.asarray(shard.final_charge)), \
                (n, pol, backend)
            assert np.array_equal(np.asarray(host.final_streak),
                                  np.asarray(shard.final_streak)), \
                (n, pol, backend, "streak")
            for hk in ("hist_soc", "hist_spend", "hist_streak"):
                sums = np.asarray(shard.stats[hk]).sum(axis=-1)
                assert np.array_equal(sums, np.full_like(sums, n)), \
                    (n, pol, backend, hk, sums)


def check_sharded_cache_reuse(mesh, n):
    """Repeat sharded calls with different seeds/admission scales must hit
    the jit cache (same shapes, same shardings)."""
    traffic = DiurnalPoisson.create(n, base=1.0)
    harvest = Bernoulli.create(n, prob=0.4)
    bat = BatteryConfig(capacity=2.0, leak=0.01)
    cost = DecodeCostModel(1e-3, 2e-3, 5e-2)
    pol = BatteryGated.create(n)

    def run(seed, admit, backend="lax"):
        cfg = ServeConfig(num_clients=n, seed=seed)
        return simulate_serve(traffic, harvest, bat, cost, QOS, pol, cfg, 10,
                              admit=admit, mesh=mesh, backend=backend)

    run(0, 1.0)
    size = _run_serve_scan._cache_size()
    run(7, 1.5)
    run(11, 0.5)
    assert _run_serve_scan._cache_size() == size, \
        "sharded simulate_serve retraced on a seed/admit sweep"
    run(0, 1.0, backend="pallas")
    assert _run_serve_scan._cache_size() == size + 1, \
        "sharded backend='pallas' cost more than one extra cache entry"
    run(7, 1.5, backend="pallas")
    run(11, 0.5, backend="pallas")
    assert _run_serve_scan._cache_size() == size + 1, \
        "sharded simulate_serve retraced on a backend/seed sweep"


def check_obs_noop(mesh, n, big_n=1_000_000):
    """The PR-7 obs contract on the sharded serve path: `run_serve_controlled`
    with an `Obs` (manifest + per-chunk round/control/span events) is
    bit-exact with ``obs=None`` and adds ZERO `_run_serve_scan` cache
    entries, at fleet scale (``big_n`` clients); the in-scan `io_callback`
    tap (small n) also leaves results and the un-tapped scan's cache
    untouched."""
    import tempfile

    from repro.energy import AdmissionRule, ServerController
    from repro.obs import Obs, load_events
    from repro.serve import run_serve_controlled

    traffic = DiurnalPoisson.create(big_n, base=1.5, swing=0.8)
    harvest = MarkovSolar.create(big_n, day_mean=0.7)
    bat = BatteryConfig(capacity=2.5, leak=0.02, init_charge=0.4)
    cost = DecodeCostModel(1e-3, 2e-3, 5e-2)
    cfg = ServeConfig(num_clients=big_n, seed=11)
    pol = BatteryGated.create(big_n, hi=1.2, lo=1.0)

    def controller():
        return ServerController(T0=5, E0=1, rules=(AdmissionRule(),))

    base, _ = run_serve_controlled(traffic, harvest, bat, cost, QOS, pol,
                                   cfg, 30, controller(), control_every=10,
                                   mesh=mesh)
    size = _run_serve_scan._cache_size()
    with tempfile.TemporaryDirectory() as d:
        with Obs(d) as obs:
            res, _ = run_serve_controlled(traffic, harvest, bat, cost, QOS,
                                          pol, cfg, 30, controller(),
                                          control_every=10, mesh=mesh,
                                          obs=obs)
        events = load_events(obs.log.path)
    assert _run_serve_scan._cache_size() == size, \
        "obs= grew the serve scan's jit cache on the sharded path"
    assert np.array_equal(np.asarray(base.final_charge),
                          np.asarray(res.final_charge))
    for k in base.stats:
        assert np.array_equal(base.stats[k], res.stats[k]), k
    kinds = [e["kind"] for e in events]
    assert kinds[0] == "manifest" and events[0]["run_kind"] \
        == "serve_controlled"
    assert sum(k == "round" for k in kinds) == 30
    assert sum(k == "control" for k in kinds) == 3
    assert sum(k == "retrace_warning" for k in kinds) == 0

    # in-scan io_callback tap (small n): bit-exact, un-tapped cache unmoved
    traffic = Constant.create(n, rate=2.0)
    harvest = Bernoulli.create(n, prob=0.375, amount=1.25)
    bat = BatteryConfig(capacity=2.5, leak=0.0, init_charge=0.5)
    cost = DecodeCostModel(2.0 ** -8, 2.0 ** -9, 2.0 ** -6)
    cfg = ServeConfig(num_clients=n, seed=3)
    pol = BatteryGated.create(n, hi=1.0, lo=1.0)
    host = simulate_serve(traffic, harvest, bat, cost, QOS, pol, cfg, 20,
                          mesh=mesh)
    size = _run_serve_scan._cache_size()
    with tempfile.TemporaryDirectory() as d:
        with Obs(d, tap=True) as obs:
            tapped = simulate_serve(traffic, harvest, bat, cost, QOS, pol,
                                    cfg, 20, mesh=mesh, obs=obs)
        events = load_events(obs.log.path)
    assert _run_serve_scan._cache_size() == size, \
        "the io_callback tap touched the un-tapped serve scan's jit cache"
    for k in host.stats:
        assert np.array_equal(host.stats[k], tapped.stats[k]), k
    epochs = sorted((e for e in events if e["kind"] == "round"),
                    key=lambda e: e["round"])
    assert [e["round"] for e in epochs] == list(range(20))
    assert all(abs(r["offered"] - float(host.stats["offered"][i])) < 1e-6
               for i, r in enumerate(epochs))


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
    # a mesh with a model axis: serve state shards over data axes only
    mesh2 = make_mesh((4, 2), ("data", "model"))
    check_parity(mesh2, n=21)   # padded 21 -> 24 (4-way data axis)
    print("serve sharded parity OK")


if __name__ == "__main__":
    main()
