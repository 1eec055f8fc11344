"""Run observability: streaming JSONL telemetry, run manifests, profiler
spans, the retrace sentinel, and the bench-regression tripwire.

See DESIGN.md §12.  The ``obs=`` hook accepted by `simulate_fleet` /
`simulate_serve` / `run_controlled` / `run_serve_controlled` (and the
``--obs-dir`` flag on the examples, `repro.launch.train` and the
benchmarks) is an `Obs`: one run directory, one ``events.jsonl``, one
`RunManifest`.  ``obs=None`` — the default everywhere — is bit-exact with
the un-instrumented code path and adds zero jit-cache entries (tested).

    from repro.obs import Obs
    obs = Obs("runs/exp1")
    res, ctrl = run_controlled(..., obs=obs, hist=True)  # per-chunk JSONL
    # python -m repro.obs.report summary runs/exp1
    # python -m repro.obs.report dist runs/exp1 --out dist.md
    # python -m repro.obs.report bench-diff BENCH_fleet.json fresh.json

Distributional telemetry (DESIGN.md §14) lives in `repro.obs.hist`: the
fixed-bin `HistSpec` contract, the in-scan `masked_bincount` reduction the
simulators run under ``hist=True``, and the host-side
`quantiles_from_counts` / `sparkline` readout that ``report dist`` and
`energy.control.Telemetry` share.
"""
from repro.obs.events import (
    EventLog,
    RunManifest,
    git_revision,
    load_events,
    pytree_hash,
)
from repro.obs.hist import (
    FLEET_HIST_SPECS,
    SERVE_HIST_SPECS,
    HistSpec,
    masked_bincount,
    quantiles_from_counts,
    sparkline,
)
from repro.obs.metrics import (
    ENERGY_SEVEN,
    GROUP_KEYS,
    SERVE_LEDGER,
    Counter,
    Gauge,
    MetricStream,
    Obs,
    counter_totals,
    reset_counters,
)
from repro.obs.profile import (
    RetraceSentinel,
    SpanRecord,
    gc_spans,
    recent_spans,
    reset_spans,
    span,
    span_totals,
)
from repro.obs.report import bench_diff, dist, render_dist, render_summary, \
    summarize

__all__ = [
    "EventLog", "RunManifest", "git_revision", "load_events", "pytree_hash",
    "FLEET_HIST_SPECS", "SERVE_HIST_SPECS", "HistSpec", "masked_bincount",
    "quantiles_from_counts", "sparkline",
    "ENERGY_SEVEN", "GROUP_KEYS", "SERVE_LEDGER", "Counter", "Gauge",
    "MetricStream", "Obs", "counter_totals", "reset_counters",
    "RetraceSentinel", "SpanRecord", "gc_spans", "recent_spans",
    "reset_spans", "span", "span_totals",
    "bench_diff", "dist", "render_dist", "render_summary", "summarize",
]
