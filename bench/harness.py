"""One run of one benchmark cell, driven by `BENCHMARK.json` and the files
it names.

A cell (an entry of ``workloads``) names a configuration and a traffic
mix.  Everything else is found by name:

* ``bench/configs/<config>.json``  the configuration as run (``file``);
* ``bench/traffic/<traffic>.json`` the traffic's parameters, with the
  ``runner`` that runs it (``bench/runners/<runner>.py``);
* ``bench/limits/<cell>.json``     the numbers that decide ``correct``,
  each with its limit (a number the file does not name is not compared);
* ``bench/metrics/<metric>.py``    one reader per per-layer metric;
* ``bench/peaks.json``             the chip's peaks, by ``device_kind``.

A runner's ``run(cell, seed, seconds, window)`` sets the system up, drives
the measured window inside ``with window():``, checks what the window's
path produced, and returns an `Outcome`.  With ``--trace 0`` the result
line carries each end-to-end metric that the runner measured; with
``--trace 1`` the window runs under the profiler and the line carries each
per-layer metric that its reader finds in the trace and counts.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import importlib
import json
import math
import shutil
import sys
import time
from pathlib import Path

import jax

from bench import trace as trace_mod
from bench.compile_log import CompileLog

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
TRACE_DIR = ROOT / ".bench_trace"


@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    limits: dict
    chips: int
    end_to_end: list        # BENCHMARK.json metric entries
    per_layer: list


def load_cell(name: str, root: Path = ROOT) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    work = {w["name"]: w for w in spec["workloads"]}
    if name not in work:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(work)}")
    w = work[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    read = lambda p: json.loads(Path(p).read_text())
    return Cell(name=name, config=read(root / conf["file"]),
                traffic=read(root / "bench" / "traffic" / f"{w['traffic']}.json"),
                limits=read(root / "bench" / "limits" / f"{name}.json"),
                chips=int(w["chips"]),
                end_to_end=spec["end_to_end"], per_layer=spec["per_layer"])


@dataclasses.dataclass
class Outcome:
    """What a runner hands back.  ``end_to_end``: value by metric name;
    ``counts``: what the per-layer readers read (``rounds``,
    ``useful_flops``, ...); ``checks``: name -> (number, limit), each
    number correct when it is at most its limit."""

    end_to_end: dict
    counts: dict
    checks: dict
    attempted: int
    failed: int
    devices: list
    memory_peak_bytes: int  # read after the window, before the reference
    memory_sources: dict    # each reading it is the largest of, by source


class Window:
    """The measured window: host clock, a ``bench.window`` span, and with
    ``trace`` the profiler around it.  Compilations inside it are
    counted."""

    def __init__(self, log: CompileLog, trace: bool):
        self.log, self.trace = log, trace
        self.t0 = self.t1 = None
        self.before = self.after = None

    @contextlib.contextmanager
    def __call__(self):
        prof = contextlib.nullcontext()
        if self.trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            prof = jax.profiler.trace(str(TRACE_DIR), profiler_options=opts)
        with prof:
            self.before = self.log.snapshot()
            with jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN):
                self.t0 = time.perf_counter()
                yield self
                self.t1 = time.perf_counter()
            self.after = self.log.snapshot()

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


@dataclasses.dataclass
class Context:
    """What a per-layer metric's reader sees: the trace's reduction, the
    runner's counts and the chip's peaks."""

    reduction: trace_mod.Reduction
    counts: dict
    peak: dict


def _peaks(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise SystemExit(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return table[kind]


def _device(out: Outcome, trace_red=None) -> dict:
    d = out.devices[0]
    dev = {"platform": d.platform, "kind": d.device_kind,
           "count": len(jax.devices()),
           "memory_peak_bytes": out.memory_peak_bytes,
           "memory_peak_of": out.memory_sources}
    if trace_red is not None:
        dev.update(busy_s=trace_red.busy_s, window_s=trace_red.window_s)
    return dev


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, *, require_chip: bool = True) -> tuple[dict, int]:
    """Run one cell; returns (result line, exit code)."""
    devices = jax.devices()
    on_chip = devices[0].platform == "tpu"
    if require_chip:
        if not on_chip or len(devices) < cell.chips:
            print(f"bench: needs {cell.chips} TPU chip(s); JAX found "
                  f"{len(devices)} {devices[0].platform} device(s)",
                  file=sys.stderr)
            return {}, 3
        peak = _peaks(devices[0].device_kind)
    log = CompileLog()
    window = Window(log, trace)
    runner = importlib.import_module(f"bench.runners.{cell.traffic['runner']}")
    out: Outcome = runner.run(cell, seed, seconds, window, t_start=t_start)
    info = {"compile_setup": window.before, "compile_window": {
        k: window.after[k] - window.before[k] for k in window.before}}
    print("bench-info " + json.dumps(info), flush=True)

    metrics, red = {}, None
    if trace:
        red = trace_mod.reduce(trace_mod.load(str(TRACE_DIR)))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    if on_chip and not trace:
        for m in cell.end_to_end:
            if m["name"] in out.end_to_end:
                metrics[m["name"]] = {"value": out.end_to_end[m["name"]],
                                      "unit": m["unit"]}
    elif on_chip:
        ctx = Context(red, out.counts, peak)
        for m in cell.per_layer:
            v = importlib.import_module(f"bench.metrics.{m['name']}").read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    checks = {k: {"value": v, "limit": lim}
              for k, (v, lim) in out.checks.items()}
    correct = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
                  for c in checks.values())
    result = {"correct": correct, "attempted": out.attempted,
              "failed": out.failed, "metrics": metrics,
              "device": _device(out, red)}
    if red is not None:
        result["breakdown"] = {"device_ops": red.device_ops,
                               "idle_gaps": red.idle_gaps}
    result["checks"] = checks
    return result, 0


def main(argv=None, *, t_start: float | None = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    result, code = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                            t_start)
    if code:
        return code
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0
