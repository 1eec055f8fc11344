"""Live run metrics: counters/gauges, the per-round `MetricStream`, and the
`Obs` hook the simulators accept as ``obs=`` (DESIGN.md §12).

Two tap points, both OFF the jitted hot path:

* **Chunk boundaries** (the default): `energy.control.run_controlled` and
  `serve.fleet_serve.run_serve_controlled` already surface each chunk's
  per-round stats on the host between jitted scans — `Obs.rounds` streams
  them to JSONL there, so a 2-minute 1e7-client sweep reports every
  ``control_every`` rounds instead of only at the end.  Zero effect on the
  compiled programs (no new jit-cache entries; tested).
* **`io_callback` round tap** (opt-in, ``Obs(..., tap=True)``): un-chunked
  `simulate_fleet`/`simulate_serve` runs one scan for the whole horizon, so
  streaming from inside requires a host callback.  The tapped scan is a
  SEPARATE jitted function (`_run_fleet_scan_tapped`) — the un-tapped
  scans' programs and `_cache_size()` are untouched — and the callback only
  *reads* the per-round stats dict, so results are bit-exact with the
  un-tapped run (tested, host-local and 8-device sharded).

Emitted per round: the fleet "energy seven" (participants / harvested /
consumed / leaked / overflowed / mean_charge / frac_depleted), the serve
ledger (offered / served_full / served_short / shed / deadline_missed /
tokens_decoded / consumed_serve / consumed_train) and any per-group
telemetry — whatever subset the producing simulator computed.  Runs with
``hist=True`` additionally stream each round's fixed-bin histogram counts
as separate ``hist`` events (exact integers; one ``hist_spec`` event per
stream pins the bin-edge contract — DESIGN.md §14).
"""
from __future__ import annotations

import functools
import os
from typing import Any

import numpy as np

from repro.obs import hist as hist_lib
from repro.obs.events import EventLog, RunManifest

# the per-round stats vocabulary, in emission order (DESIGN.md §12)
ENERGY_SEVEN = ("participants", "harvested", "consumed", "leaked",
                "overflowed", "mean_charge", "frac_depleted")
SERVE_LEDGER = ("offered", "served_full", "served_short", "shed",
                "deadline_missed", "tokens_decoded", "consumed_serve",
                "consumed_train")
# (R, G) per-group telemetry (simulate_fleet(..., groups=)); streamed inline
# in round events as G-length lists
GROUP_KEYS = ("group_participants", "group_frac_depleted")
# (R, N) per-client recordings never belong in an event stream
_SKIP_KEYS = ("mask", "mode")


def _scalarize(v):
    """Telemetry value -> JSON-able: 0-d arrays to floats, small per-group
    vectors to lists."""
    a = np.asarray(v)
    if a.ndim == 0:
        return float(a)
    return a.tolist()


# name -> the sum of every Counter of that name; survives its counters
_COUNTER_TOTALS: dict[str, float] = {}


def counter_totals() -> dict[str, float]:
    """What every counter of each name counted since the last
    `reset_counters` (the counterpart of `profile.span_totals`)."""
    return dict(_COUNTER_TOTALS)


def reset_counters() -> None:
    _COUNTER_TOTALS.clear()


class Counter:
    """Monotone event counter (rounds seen, chunks, retraces...)."""

    def __init__(self, name: str):
        self.name = name
        self.value = 0

    def inc(self, by: int = 1) -> int:
        self.value += by
        _COUNTER_TOTALS[self.name] = _COUNTER_TOTALS.get(self.name, 0) + by
        return self.value


class Gauge:
    """Last-write-wins instantaneous value (mean charge, admit scale...)."""

    def __init__(self, name: str):
        self.name = name
        self.value: float | None = None

    def set(self, v) -> None:
        self.value = float(v)


class MetricStream:
    """Counters/gauges plus the per-round telemetry emitter over one
    `EventLog`."""

    def __init__(self, log: EventLog):
        self.log = log
        self._counters: dict[str, Counter] = {}
        self._gauges: dict[str, Gauge] = {}
        self._specs_emitted: set[str] = set()

    def counter(self, name: str) -> Counter:
        return self._counters.setdefault(name, Counter(name))

    def attach(self, counter: Counter) -> None:
        """Snapshot a counter kept elsewhere with this stream's own."""
        self._counters[counter.name] = counter

    def gauge(self, name: str) -> Gauge:
        return self._gauges.setdefault(name, Gauge(name))

    def emit_hist(self, scan: str, rnd: int, key: str, counts) -> None:
        """One round's histogram counts as a ``hist`` event (and, once per
        stream, the ``hist_spec`` event pinning the bin-edge contract the
        counts were produced under — DESIGN.md §14)."""
        spec = hist_lib.SPECS_BY_NAME.get(key)
        if spec is not None and key not in self._specs_emitted:
            self._specs_emitted.add(key)
            self.log.emit("hist_spec", scan=scan, name=spec.name,
                          buf=spec.buf, lo=spec.lo, hi=spec.hi,
                          bins=spec.bins)
        self.log.emit("hist", scan=scan, round=int(rnd), name=key,
                      counts=[int(c) for c in
                              np.asarray(counts).reshape(-1)])

    def emit_rounds(self, scan: str, offset: int, stats: dict) -> int:
        """Stream one ``round`` event per round from a stats dict of (R,)
        (or (R, G) per-group) arrays — the simulators' native output shape;
        per-group columns (`GROUP_KEYS`) ride inline as G-length lists.
        ``hist_*`` (R, bins) count matrices are split out as one ``hist``
        event per (round, histogram) instead — exact integer counts, never
        means.  Returns the number of rounds emitted."""
        arrs = {k: np.asarray(stats[k]) for k in stats
                if k not in _SKIP_KEYS}
        if not arrs:
            return 0
        keys = [k for k in arrs if not hist_lib.is_hist_key(k)]
        hist_keys = [k for k in arrs if hist_lib.is_hist_key(k)]
        r_len = next(iter(arrs.values())).shape[0]
        for i in range(r_len):
            if keys:
                self.log.emit("round", scan=scan, round=int(offset) + i,
                              **{k: _scalarize(arrs[k][i]) for k in keys})
            for k in hist_keys:
                self.emit_hist(scan, int(offset) + i, k, arrs[k][i])
        self.counter(f"{scan}_rounds").inc(r_len)
        if "mean_charge" in arrs and r_len:
            self.gauge(f"{scan}_mean_charge").set(arrs["mean_charge"][-1])
        return r_len

    def flush(self) -> None:
        """Snapshot every counter/gauge as one ``metrics`` event."""
        self.log.emit(
            "metrics",
            counters={c.name: c.value for c in self._counters.values()},
            gauges={g.name: g.value for g in self._gauges.values()})


class Obs:
    """The ``obs=`` hook: one run directory, one JSONL event log, one
    manifest.

    Threaded through `simulate_fleet`/`simulate_serve` (manifest + round
    events, opt-in `io_callback` live tap), `run_controlled`/
    `run_serve_controlled` (chunk-boundary streaming + control events +
    retrace sentinel), `repro.launch.train` and the examples/benchmarks
    (``--obs-dir``).  ``obs=None`` everywhere is a strict no-op — the
    default path is bit-identical to an un-instrumented build.

    Args:
      out_dir: directory for ``events.jsonl`` (created if missing).
      run_id: optional stable id recorded in the manifest.
      tap: enable the in-scan `io_callback` round tap for un-chunked
        simulator runs (chunked runs stream at chunk boundaries regardless).
    """

    def __init__(self, out_dir: str | os.PathLike, *,
                 run_id: str | None = None, tap: bool = False):
        self.dir = os.fspath(out_dir)
        os.makedirs(self.dir, exist_ok=True)
        self.log = EventLog(os.path.join(self.dir, "events.jsonl"))
        self.metrics = MetricStream(self.log)
        self.tap = bool(tap)
        self.run_id = run_id
        self.manifest: RunManifest | None = None
        self._taps: dict[str, Any] = {}

    # ------------------------------------------------------------ manifest --
    def write_manifest(self, kind: str, **kwargs) -> RunManifest:
        """Create + emit the run manifest.  First call wins — a multi-phase
        run (several simulator calls sharing one Obs) is ONE run with one
        manifest; later calls record a lightweight ``phase`` event instead
        so each sub-run is still delimited in the stream."""
        if self.manifest is None:
            self.manifest = RunManifest.create(kind, run_id=self.run_id,
                                               **kwargs)
            self.run_id = self.manifest.run_id
            fields = self.manifest.to_dict()
            # the manifest's run kind rides as ``run_kind`` — ``kind`` is
            # the event-type discriminator on every line of the stream
            fields["run_kind"] = fields.pop("kind")
            self.log.emit("manifest", **fields)
        else:
            config = kwargs.pop("config", None)
            from repro.obs.events import pytree_hash
            self.log.emit(
                "phase", phase=kind,
                config_hash=None if config is None else pytree_hash(config),
                **{k: v for k, v in kwargs.items()
                   if isinstance(v, (int, float, str, bool, type(None)))})
        return self.manifest

    # ----------------------------------------------------------- emitters --
    def event(self, kind: str, **fields) -> dict:
        return self.log.emit(kind, **fields)

    def rounds(self, scan: str, offset: int, stats: dict) -> int:
        return self.metrics.emit_rounds(scan, offset, stats)

    def span(self, name: str):
        from repro.obs.profile import span
        return span(name, obs=self)

    # ------------------------------------------------------ io_callback tap --
    def round_tap(self, scan: str):
        """Host callback for the in-scan `io_callback` tap, memoized per
        scan name: jit treats static callables by identity, so re-using the
        same Obs across runs must hand back the same object or every call
        would recompile the tapped scan."""
        if scan not in self._taps:
            self._taps[scan] = functools.partial(self._on_round, scan)
        return self._taps[scan]

    def _on_round(self, scan: str, r, stats: dict) -> None:
        rnd = int(np.asarray(r))
        row = {k: _scalarize(v) for k, v in stats.items()
               if k not in _SKIP_KEYS and not hist_lib.is_hist_key(k)}
        if row:
            self.log.emit("round", scan=scan, round=rnd, **row)
        for k, v in stats.items():
            if hist_lib.is_hist_key(k):
                self.metrics.emit_hist(scan, rnd, k, v)
        self.metrics.counter(f"{scan}_rounds").inc()

    # -------------------------------------------------------------- close --
    def close(self) -> None:
        if self.log._f is not None:
            self.metrics.flush()
        self.log.close()

    def __enter__(self) -> "Obs":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
