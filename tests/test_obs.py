"""`repro.obs` observability layer (PR 7): the event log / manifest
machinery, the strict no-op contract of ``obs=`` on the simulators and
chunked controller loops (bit-exact results, zero jit-cache growth), the
opt-in in-scan `io_callback` tap, the retrace sentinel, the degenerate
`Telemetry` reductions, and the `bench-diff` perf tripwire + report CLI.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import EnergyProfile, Policy
from repro.energy import (AdmissionRule, BatteryConfig, Bernoulli,
                          ControlBounds, DecodeCostModel, FleetConfig,
                          MarkovSolar, ServerController, Telemetry,
                          run_controlled, simulate_fleet)
from repro.energy.fleet import _run_fleet_scan
from repro.obs import (EventLog, Obs, RunManifest, bench_diff, load_events,
                       pytree_hash, summarize)
from repro.serve import (BatteryGated, Constant, DiurnalPoisson, QoSSpec,
                         ServeConfig, run_serve_controlled, simulate_serve)
from repro.serve.fleet_serve import _run_serve_scan

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

QOS = QoSSpec(prompt_tokens=64.0, full_decode_tokens=128.0,
              short_decode_tokens=32.0)
COST = DecodeCostModel(joules_per_prefill_token=1e-3,
                       joules_per_decode_step=2e-3,
                       joules_per_response_upload=5e-2)


def _fleet_args(n, seed=3):
    E = np.asarray(EnergyProfile(n).cycles())
    proc = Bernoulli.create(n, prob=0.375, amount=1.25)
    bat = BatteryConfig(capacity=2.5, leak=0.0, init_charge=0.5)
    cfg = FleetConfig(num_clients=n, policy=Policy.THRESHOLD, threshold=1.5,
                      seed=seed)
    return proc, bat, 0.75, cfg, E


def _serve_args(n, seed=3):
    traffic = Constant.create(n, rate=2.0)
    harvest = Bernoulli.create(n, prob=0.375, amount=1.25)
    bat = BatteryConfig(capacity=2.5, leak=0.0, init_charge=0.5)
    cfg = ServeConfig(num_clients=n, seed=seed)
    pol = BatteryGated.create(n, hi=1.0, lo=1.0)
    return traffic, harvest, bat, cfg, pol


# ------------------------------------------------------- events / manifest --

def test_event_log_roundtrip(tmp_path):
    """Emit -> load round trip: monotone seq, kinds preserved, numpy
    scalars/arrays JSON-able, and a torn trailing line (crash mid-write) is
    skipped rather than poisoning the whole log."""
    path = tmp_path / "events.jsonl"
    log = EventLog(path)
    log.emit("a", x=1, f=np.float32(2.5), arr=np.arange(3))
    log.emit("b", nested={"k": [1, 2]})
    log.emit("c")
    log.close()
    with open(path, "a") as f:
        f.write('{"seq": 99, "kind": "torn', )   # no newline, invalid JSON
    ev = load_events(path)
    assert [e["kind"] for e in ev] == ["a", "b", "c"]
    assert [e["seq"] for e in ev] == [0, 1, 2]
    assert ev[0]["f"] == 2.5 and ev[0]["arr"] == [0, 1, 2]
    assert all("ts" in e for e in ev)


def test_pytree_hash_stable_and_discriminating():
    proc, bat, cost, cfg, E = _fleet_args(8)
    h1 = pytree_hash((proc, bat, cost))
    h2 = pytree_hash((proc, bat, cost))
    assert h1 == h2 and len(h1) == 16
    proc2, *_ = _fleet_args(8, seed=4)
    proc2 = Bernoulli.create(8, prob=0.5, amount=1.25)
    assert pytree_hash((proc2, bat, cost)) != h1


def test_manifest_first_call_wins_and_phase_events(tmp_path):
    """One Obs shared across several runs is ONE run: the first
    `write_manifest` emits the manifest (run kind riding as ``run_kind`` —
    ``kind`` is the stream discriminator), later calls emit ``phase``
    delimiter events instead."""
    with Obs(tmp_path) as obs:
        m1 = obs.write_manifest("fleet", seed=7, num_clients=16, horizon=5)
        m2 = obs.write_manifest("serve", seed=7, num_clients=16, horizon=5)
    assert m1 is m2 and m1.kind == "fleet"
    ev = load_events(obs.log.path)
    assert ev[0]["kind"] == "manifest" and ev[0]["run_kind"] == "fleet"
    assert ev[0]["seed"] == 7 and ev[0]["device_count"] >= 1
    assert "jax" in ev[0]["packages"]
    phases = [e for e in ev if e["kind"] == "phase"]
    assert len(phases) == 1 and phases[0]["phase"] == "serve"
    # close() flushed the metric snapshot as the trailing event
    assert ev[-1]["kind"] == "metrics"


def test_manifest_to_dict_roundtrips_config_hash():
    proc, bat, cost, cfg, E = _fleet_args(8)
    man = RunManifest.create("fleet", config=(proc, bat, cost), seed=1,
                             num_clients=8, horizon=4)
    d = man.to_dict()
    assert d["config_hash"] == pytree_hash((proc, bat, cost))
    assert d["kind"] == "fleet" and d["num_clients"] == 8


# ------------------------------------------------- simulator no-op contract --

def test_fleet_obs_noop_and_tap(tmp_path):
    """`simulate_fleet` with obs (host-side and io_callback tap) is
    bit-exact with obs=None and leaves the un-tapped scan's jit cache
    untouched; the streamed round events carry the energy seven."""
    n, rounds = 16, 12
    proc, bat, cost, cfg, E = _fleet_args(n)
    base = simulate_fleet(proc, bat, cost, cfg, rounds, E=E)
    size = _run_fleet_scan._cache_size()

    with Obs(tmp_path / "host") as obs:
        host = simulate_fleet(proc, bat, cost, cfg, rounds, E=E, obs=obs)
    with Obs(tmp_path / "tap", tap=True) as obs_t:
        tapped = simulate_fleet(proc, bat, cost, cfg, rounds, E=E, obs=obs_t)

    assert _run_fleet_scan._cache_size() == size
    for res in (host, tapped):
        assert np.array_equal(np.asarray(base.final_charge),
                              np.asarray(res.final_charge))
        for k in base.stats:
            assert np.array_equal(base.stats[k], res.stats[k]), k
    for path in (obs.log.path, obs_t.log.path):
        ev = load_events(path)
        assert ev[0]["kind"] == "manifest" and ev[0]["run_kind"] == "fleet"
        rnds = sorted((e for e in ev if e["kind"] == "round"),
                      key=lambda e: e["round"])
        assert [e["round"] for e in rnds] == list(range(rounds))
        for i, e in enumerate(rnds):
            assert e["scan"] == "fleet"
            for k in ("participants", "harvested", "mean_charge",
                      "frac_depleted"):
                assert abs(e[k] - float(base.stats[k][i])) < 1e-6, (k, i)


def test_serve_obs_noop_and_tap(tmp_path):
    """Serve twin of the no-op contract: ledger round events, bit-exact
    results, zero `_run_serve_scan` cache growth."""
    n, epochs = 16, 12
    traffic, harvest, bat, cfg, pol = _serve_args(n)
    base = simulate_serve(traffic, harvest, bat, COST, QOS, pol, cfg, epochs)
    size = _run_serve_scan._cache_size()

    with Obs(tmp_path / "host") as obs:
        host = simulate_serve(traffic, harvest, bat, COST, QOS, pol, cfg,
                              epochs, obs=obs)
    with Obs(tmp_path / "tap", tap=True) as obs_t:
        tapped = simulate_serve(traffic, harvest, bat, COST, QOS, pol, cfg,
                                epochs, obs=obs_t)

    assert _run_serve_scan._cache_size() == size
    for res in (host, tapped):
        assert np.array_equal(np.asarray(base.final_charge),
                              np.asarray(res.final_charge))
        for k in base.stats:
            assert np.array_equal(base.stats[k], res.stats[k]), k
    for path in (obs.log.path, obs_t.log.path):
        ev = load_events(path)
        assert ev[0]["run_kind"] == "serve"
        rnds = sorted((e for e in ev if e["kind"] == "round"),
                      key=lambda e: e["round"])
        assert [e["round"] for e in rnds] == list(range(epochs))
        for i, e in enumerate(rnds):
            for k in ("offered", "served_full", "shed", "tokens_decoded"):
                assert abs(e[k] - float(base.stats[k][i])) < 1e-6, (k, i)


def test_run_controlled_streams_during_execution(tmp_path):
    """The chunked fleet controller loop with obs=: bit-exact vs obs=None,
    zero cache growth, manifest first, one round event per round, one
    control event per chunk, per-chunk spans, no retrace warnings."""
    n, rounds, every = 20, 30, 10
    proc = MarkovSolar.create(n, day_mean=0.9)
    bat = BatteryConfig(capacity=4.0, leak=0.01, init_charge=1.0)
    cfg = FleetConfig(num_clients=n, policy=Policy.SUSTAINABLE, seed=2)

    def ctrl():
        return ServerController(
            T0=cfg.local_steps, E0=2,
            bounds=ControlBounds(t_min=1, t_max=10, e_min=1, e_max=64))

    base, _ = run_controlled(proc, bat, 0.4, cfg, rounds, ctrl(),
                             control_every=every)
    size = _run_fleet_scan._cache_size()
    with Obs(tmp_path) as obs:
        res, _ = run_controlled(proc, bat, 0.4, cfg, rounds, ctrl(),
                                control_every=every, obs=obs)
    assert _run_fleet_scan._cache_size() == size
    for k in base.stats:
        assert np.array_equal(base.stats[k], res.stats[k]), k

    ev = load_events(obs.log.path)
    assert ev[0]["kind"] == "manifest" \
        and ev[0]["run_kind"] == "fleet_controlled"
    s = summarize(ev)
    assert s["scans"]["fleet"]["rounds"] == rounds
    assert s["scans"]["fleet"]["first_round"] == 0
    assert s["scans"]["fleet"]["last_round"] == rounds - 1
    assert len(s["controls"]) == rounds // every
    assert s["spans"]["fleet_chunk"]["count"] == rounds // every
    assert s["retrace_warnings"] == []


def test_run_serve_controlled_streams_during_execution(tmp_path):
    n, epochs, every = 18, 30, 10
    traffic = DiurnalPoisson.create(n, base=1.5, swing=0.8)
    harvest = MarkovSolar.create(n, day_mean=0.7)
    bat = BatteryConfig(capacity=2.5, leak=0.02, init_charge=0.4)
    cfg = ServeConfig(num_clients=n, seed=11)
    pol = BatteryGated.create(n, hi=1.2, lo=1.0)

    def ctrl():
        return ServerController(T0=5, E0=1, rules=(AdmissionRule(),))

    base, _ = run_serve_controlled(traffic, harvest, bat, COST, QOS, pol,
                                   cfg, epochs, ctrl(), control_every=every)
    size = _run_serve_scan._cache_size()
    with Obs(tmp_path) as obs:
        res, _ = run_serve_controlled(traffic, harvest, bat, COST, QOS, pol,
                                      cfg, epochs, ctrl(),
                                      control_every=every, obs=obs)
    assert _run_serve_scan._cache_size() == size
    for k in base.stats:
        assert np.array_equal(base.stats[k], res.stats[k]), k

    ev = load_events(obs.log.path)
    assert ev[0]["run_kind"] == "serve_controlled"
    s = summarize(ev)
    assert s["scans"]["serve"]["rounds"] == epochs
    assert len(s["controls"]) == epochs // every
    assert s["spans"]["serve_chunk"]["count"] == epochs // every
    assert s["retrace_warnings"] == []
    # the admit knob trajectory is readable back from the stream
    assert all("admit" in c for c in s["controls"])


# ------------------------------------------------------------- profiling ----

def test_span_totals_fold(tmp_path):
    from repro.obs import reset_spans, span, span_totals
    reset_spans()
    with Obs(tmp_path) as obs:
        with span("outer", obs=obs):
            pass
        with span("outer", obs=obs):
            pass
    totals = span_totals()
    assert totals["outer"]["count"] == 2 and totals["outer"]["total_ms"] >= 0
    ev = load_events(obs.log.path)
    assert sum(e["kind"] == "span" and e["name"] == "outer"
               for e in ev) == 2
    reset_spans()


def test_retrace_sentinel_detects_growth(tmp_path):
    """A deliberate shape change between checks must be reported exactly
    once (the sentinel re-snapshots), and a cache-stable window reports
    nothing."""
    from repro.obs import RetraceSentinel
    proc, bat, cost, cfg, E = _fleet_args(16)
    simulate_fleet(proc, bat, cost, cfg, 8, E=E)
    with Obs(tmp_path) as obs:
        sentinel = RetraceSentinel(obs)
        sentinel.snapshot()
        assert sentinel.check(context="stable window") == []
        # a NEW client count -> new shapes -> the fleet scan must retrace
        proc2, bat2, cost2, cfg2, E2 = _fleet_args(17)
        simulate_fleet(proc2, bat2, cost2, cfg2, 8, E=E2)
        grown = sentinel.check(context="deliberate shape change")
        assert grown and grown[0]["delta"] >= 1
        assert "fleet" in grown[0]["fn"]
        # re-snapshotted: the same growth is not reported twice
        assert sentinel.check() == []
    ev = load_events(obs.log.path)
    warns = [e for e in ev if e["kind"] == "retrace_warning"]
    assert len(warns) == 1 \
        and warns[0]["context"] == "deliberate shape change"


# --------------------------------------------------- degenerate telemetry ---

def test_telemetry_zero_denominators_are_defined():
    """Satellite regression: a period with zero scheduled slots, zero
    offered requests, zero harvest, or empty/zero-size groups must reduce
    to finite 0.0 signals (dead-bands hold the knobs) — never NaN, never a
    numpy divide warning."""
    stats = {
        "participants": np.zeros(4), "harvested": np.zeros(4),
        "consumed": np.zeros(4), "leaked": np.zeros(4),
        "overflowed": np.zeros(4), "mean_charge": np.zeros(4),
        "frac_depleted": np.zeros(4),
        "offered": np.zeros(4), "shed": np.zeros(4),
        "deadline_missed": np.zeros(4),
        "group_frac_depleted": np.zeros((4, 3)),
        "group_participants": np.zeros((4, 3)),
    }
    with np.errstate(all="raise"):
        t = Telemetry.from_stats(stats, num_clients=10,
                                 group_sizes=[5, 5, 0])
        empty = Telemetry.from_stats(
            {k: np.asarray(v)[:0] for k, v in stats.items()}, num_clients=10)
    for tel in (t, empty):
        assert tel.participation_rate == 0.0
        assert tel.overflow_frac == 0.0
        assert tel.shed_rate == 0.0 and tel.deadline_miss_rate == 0.0
        assert np.isfinite(tel.mean_charge)
    assert np.array_equal(t.group_participation_rate, [0.0, 0.0, 0.0])
    assert np.all(np.isfinite(empty.group_frac_depleted))
    # zero clients: participation is defined as 0, not a division blow-up
    with np.errstate(all="raise"):
        z = Telemetry.from_stats(stats, num_clients=0)
    assert z.participation_rate == 0.0


def test_emit_rounds_groups_inline_and_hists_split(tmp_path):
    """`MetricStream.emit_rounds`: per-group columns (`GROUP_KEYS`) ride
    inline in round events as G-length lists and survive into
    ``summarize``'s ``group_means``; ``hist_*`` matrices are split out as
    one exact-integer ``hist`` event per (round, histogram) behind a single
    ``hist_spec``; (R, N) recordings (`_SKIP_KEYS`) never leak into the
    stream."""
    from repro.obs import summarize
    from repro.obs.metrics import EventLog, MetricStream

    R, G = 3, 2
    stats = {
        "participants": np.asarray([4.0, 5.0, 6.0]),
        "frac_depleted": np.asarray([0.0, 0.5, 0.25]),
        "group_participants": np.arange(R * G, dtype=np.float64
                                        ).reshape(R, G),
        "group_frac_depleted": np.asarray([[0.0, 1.0], [0.5, 0.5],
                                           [0.25, 0.75]]),
        "hist_soc": np.tile(np.eye(1, 32, 3, dtype=np.float64) * 8, (R, 1)),
        "mask": np.ones((R, 100)),
    }
    log = EventLog(tmp_path / "events.jsonl")
    assert MetricStream(log).emit_rounds("fleet", 10, stats) == R
    log.close()
    ev = load_events(tmp_path / "events.jsonl")

    rounds = [e for e in ev if e["kind"] == "round"]
    assert [e["round"] for e in rounds] == [10, 11, 12]
    assert rounds[1]["group_frac_depleted"] == [0.5, 0.5]
    assert all("mask" not in e and "hist_soc" not in e for e in rounds)
    hists = [e for e in ev if e["kind"] == "hist"]
    assert [(e["round"], e["name"]) for e in hists] == \
        [(10 + i, "hist_soc") for i in range(R)]
    assert hists[0]["counts"][3] == 8 \
        and all(isinstance(c, int) for c in hists[0]["counts"])
    specs = [e for e in ev if e["kind"] == "hist_spec"]
    assert len(specs) == 1 and specs[0]["bins"] == 32 \
        and specs[0]["buf"] == "soc"

    s = summarize(ev)
    assert s["scans"]["fleet"]["group_means"]["group_frac_depleted"] == \
        [0.25, 0.75]
    assert s["hists"]["fleet"]["hist_soc"] == R


def test_grouped_fleet_streams_group_columns(tmp_path):
    """End to end: a grouped `simulate_fleet` run streams
    ``group_frac_depleted`` per round and ``report summary`` surfaces the
    per-group mean row."""
    n, rounds, num_groups = 16, 8, 4
    proc, bat, cost, cfg, E = _fleet_args(n)
    groups = np.arange(n) % num_groups
    with Obs(tmp_path) as obs:
        res = simulate_fleet(proc, bat, cost, cfg, rounds, E=E,
                             groups=groups, obs=obs)
    ev = load_events(obs.log.path)
    rnds = sorted((e for e in ev if e["kind"] == "round"),
                  key=lambda e: e["round"])
    for i, e in enumerate(rnds):
        assert np.allclose(e["group_frac_depleted"],
                           np.asarray(res.stats["group_frac_depleted"][i],
                                      np.float64), atol=1e-6), i
    from repro.obs import summarize
    gm = summarize(ev)["scans"]["fleet"]["group_means"]
    assert len(gm["group_frac_depleted"]) == num_groups
    out = _run_cli(["summary", str(tmp_path)], cwd=_REPO)
    assert out.returncode == 0, out.stderr
    assert "group_frac_depleted (per-group mean):" in out.stdout


def test_summary_degenerate_streams(tmp_path):
    """Satellite hardening: manifest-only and resume-only event streams
    must summarize cleanly — both via `summarize`/`render_summary` and
    through the CLI (exit 0), never a traceback."""
    from repro.obs import EventLog, render_summary, summarize

    with Obs(tmp_path / "manifest_only") as obs:
        obs.write_manifest("fleet", seed=0, num_clients=4, horizon=0)
    s = summarize(load_events(obs.log.path))
    assert s["scans"] == {} and s["manifest"] is not None
    text = render_summary(s)
    assert "(no round events)" in text
    out = _run_cli(["summary", str(tmp_path / "manifest_only")], cwd=_REPO)
    assert out.returncode == 0, out.stderr
    assert "(no round events)" in out.stdout

    # a resumed run's fresh log: resume event first, no manifest, no rounds
    d = tmp_path / "resume_only"
    d.mkdir()
    log = EventLog(d / "events.jsonl")
    log.emit("resume", run_kind="fleet_controlled", round=12, horizon=36,
             checkpoint_dir="ckpts/run1")
    log.close()
    s = summarize(load_events(d / "events.jsonl"))
    text = render_summary(s)
    assert "starts at a resume" in text
    assert "resumed fleet_controlled at round 12/36" in text
    assert "(no round events)" in text
    out = _run_cli(["summary", str(d)], cwd=_REPO)
    assert out.returncode == 0, out.stderr
    assert "starts at a resume" in out.stdout


# ------------------------------------------------------------ bench-diff ----

def _fleet_bench():
    path = os.path.join(_REPO, "BENCH_fleet.json")
    if not os.path.exists(path):
        pytest.skip("no committed BENCH_fleet.json")
    with open(path) as f:
        return json.load(f)


def test_bench_diff_self_pass_and_manifest():
    bench = _fleet_bench()
    assert bench_diff(bench, bench) == []
    # PR-7 baselines embed their manifest for provenance
    assert isinstance(bench.get("manifest"), dict)
    assert bench["manifest"]["kind"] == "fleet_scale"


def test_bench_diff_catches_regressions():
    bench = _fleet_bench()
    if not bench.get("round_step"):
        pytest.skip("baseline has no round_step section")
    slow = json.loads(json.dumps(bench))
    slow["round_step"][0]["lax_fused_ms"] *= 2.0            # timing blow-up
    slow["round_step"][0]["speedup_fused_vs_unfused"] *= 0.4  # ratio collapse
    v = bench_diff(bench, slow, sections=["round_step"])
    metrics = {x["metric"] for x in v}
    assert metrics == {"lax_fused_ms", "speedup_fused_vs_unfused"}
    assert all(x["section"] == "round_step" for x in v)
    # within tolerance passes: +20% < the 30% round_step tripwire
    ok = json.loads(json.dumps(bench))
    ok["round_step"][0]["lax_fused_ms"] *= 1.2
    assert bench_diff(bench, ok, sections=["round_step"]) == []


def test_bench_diff_missing_section_semantics():
    bench = _fleet_bench()
    # absent from the FRESH side = violation (a deleted bench is deliberate)
    gutted = {k: v for k, v in bench.items() if k != "round_step"}
    v = bench_diff(bench, gutted, sections=["round_step"])
    assert len(v) == 1 and v[0]["reason"] == "section missing from fresh run"
    # absent from the BASELINE side = skipped (pre-PR-7 files stay diffable)
    assert bench_diff(gutted, bench, sections=["round_step"]) == []
    pre_pr7 = {"bench": "fleet_scale", "results": []}
    assert bench_diff(pre_pr7, bench) == []
    with pytest.raises(ValueError):
        bench_diff(bench, bench, sections=["no_such_section"])


def test_fmt_manifest_line_tolerates_pre_pr7():
    from benchmarks._fmt import manifest_line
    assert "pre-PR-7" in manifest_line({"bench": "fleet_scale"})
    bench = _fleet_bench()
    line = manifest_line(bench)
    assert bench["manifest"]["run_id"] in line and "git=" in line


# ------------------------------------------------------------ report CLI ----

def _run_cli(args, cwd):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(_REPO, "src")]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    env.setdefault("JAX_PLATFORMS", "cpu")
    return subprocess.run([sys.executable, "-m", "repro.obs.report", *args],
                          env=env, cwd=cwd, capture_output=True, text=True,
                          timeout=240)


def test_report_cli_summary_and_bench_diff(tmp_path):
    """End to end through the module CLI: ``summary`` renders a streamed
    run dir (exit 0), ``bench-diff`` exits 0 on a within-tolerance pair and
    1 on a perturbed one — the exact contract the CI tripwire step relies
    on."""
    n, rounds = 12, 8
    proc, bat, cost, cfg, E = _fleet_args(n)
    with Obs(tmp_path / "run") as obs:
        simulate_fleet(proc, bat, cost, cfg, rounds, E=E, obs=obs)
    out = _run_cli(["summary", str(tmp_path / "run")], cwd=_REPO)
    assert out.returncode == 0, out.stderr
    assert "[fleet]" in out.stdout and "participants" in out.stdout
    out = _run_cli(["summary", str(tmp_path / "run"), "--json"], cwd=_REPO)
    assert out.returncode == 0
    assert json.loads(out.stdout)["scans"]["fleet"]["rounds"] == rounds

    bench = _fleet_bench()
    base_p = tmp_path / "base.json"
    base_p.write_text(json.dumps(bench))
    out = _run_cli(["bench-diff", str(base_p), str(base_p),
                    "--sections", "round_step"], cwd=_REPO)
    assert out.returncode == 0 and "bench-diff OK" in out.stdout
    if bench.get("round_step"):
        slow = json.loads(json.dumps(bench))
        slow["round_step"][0]["unfused_ms"] *= 3.0
        slow_p = tmp_path / "slow.json"
        slow_p.write_text(json.dumps(slow))
        out = _run_cli(["bench-diff", str(base_p), str(slow_p),
                        "--sections", "round_step"], cwd=_REPO)
        assert out.returncode == 1 and "FAILED" in out.stdout
        assert "unfused_ms" in out.stdout


# ------------------------------------------- span records, gc, counters --

def test_span_records_parent_and_inherited_round():
    from repro.obs import recent_spans, reset_spans, span, span_totals
    reset_spans()
    with span("outer", round=7):
        with span("inner"):
            pass
    with span("lone"):
        pass
    recs = recent_spans()
    assert [(r.name, r.parent, r.round) for r in recs] == [
        ("inner", "outer", 7), ("outer", None, 7), ("lone", None, None)]
    outer, inner = recs[1], recs[0]
    assert outer.start <= inner.start <= inner.end <= outer.end
    assert span_totals()["outer"]["count"] == 1
    reset_spans()
    assert recent_spans() == [] and span_totals() == {}


def test_span_record_is_bounded_and_survives_an_exception():
    from repro.obs import profile
    profile.reset_spans()
    for i in range(profile.RECENT_SPANS + 5):
        with profile.span("s", round=i):
            pass
    recs = profile.recent_spans()
    assert len(recs) == profile.RECENT_SPANS and recs[-1].round == \
        profile.RECENT_SPANS + 4
    with pytest.raises(RuntimeError):
        with profile.span("boom"):
            raise RuntimeError
    with profile.span("after"):
        pass
    # the failed span's frame is gone: "after" has no parent
    assert profile.recent_spans()[-1].parent is None
    profile.reset_spans()


def test_gc_spans_record_each_collection_under_the_open_span():
    import gc
    from repro.obs import gc_spans, recent_spans, reset_spans, span
    reset_spans()
    seen = []
    with gc_spans("t.gc", seen.append):
        with span("work", round=3):
            gc.collect()
    gc.collect()                      # outside the block: not recorded
    gcs = [r for r in recent_spans() if r.name == "t.gc"]
    assert len(gcs) == len(seen) >= 1
    assert all(r.parent == "work" and r.round == 3 for r in gcs)
    assert all(ms >= 0 for ms in seen)
    reset_spans()


def test_counter_totals_fold_every_counter_of_a_name(tmp_path):
    from repro.obs import Counter, counter_totals, reset_counters
    reset_counters()
    a, b = Counter("x"), Counter("x")
    a.inc(2)
    b.inc()
    assert (a.value, b.value) == (2, 1)
    assert counter_totals() == {"x": 3}
    obs = Obs(tmp_path)
    obs.metrics.attach(a)
    a.inc(5)
    obs.close()
    ev = [e for e in load_events(tmp_path / "events.jsonl")
          if e["kind"] == "metrics"][-1]
    assert ev["counters"]["x"] == 7
    reset_counters()
    assert counter_totals() == {}


def test_report_summary_prints_counters_and_useful_share(tmp_path):
    from repro.obs import render_summary
    from repro.obs.metrics import Counter
    obs = Obs(tmp_path)
    obs.write_manifest("train", seed=0, num_clients=4, horizon=2)
    for name, v in [("train.client_steps_computed", 16),
                    ("train.client_steps_useful", 6),
                    ("train.gc_ms", 12.5)]:
        c = Counter(name)
        c.inc(v)
        obs.metrics.attach(c)
    obs.close()
    s = summarize(load_events(tmp_path / "events.jsonl"))
    assert s["counters"]["train.client_steps_useful"] == 6
    text = render_summary(s)
    assert "train.gc_ms" in text and "12.5" in text
    assert "useful share of computed client steps: 37.50%" in text
