#!/usr/bin/env python3
"""Run one benchmark cell under the profiler, as ``bench/run.py --trace 1``
does, and print what the program's own marks say about the window:

    python3 bench/explain.py --workload <cell> --seed <n> --seconds <s>

The last line of standard output is one JSON object: the harness's
``breakdown`` (``device_ops``, ``idle_gaps``) with ``launch_gaps`` and
``device_scopes`` (`bench/program_trace.py`) beside it, the window's idle
seconds, the per-round metrics those give and ``round_useful_share``, and
the slowest window round taken apart by its ``train.*`` spans.  The
operations' scopes come from the round program as the runner builds it,
compiled again after the window (a hit in the compile cache).
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

from bench import harness, program_trace as P, trace as T  # noqa: E402
from bench.compile_log import CompileLog  # noqa: E402
from bench.metrics import round_useful_share  # noqa: E402


def round_hlo(runner, cell, seed: int) -> str:
    """The compiled round program's HLO text, from the launcher run, the
    seed's weights and batches the runner uses."""
    run = runner.launcher_run(cell)
    w0, make = runner.seeded(cell, seed, run)
    args = (make(0), run.p, run.E, jnp.int32(0),
            jax.random.fold_in(run.rng, 0))
    return run.round_fn.lower(w0, *args).compile().as_text()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    window = harness.Window(CompileLog(), trace=True)
    runner = importlib.import_module(
        f"bench.runners.{cell.traffic['runner']}")
    out = runner.run(cell, args.seed, args.seconds, window, t_start=T_START)
    red = T.reduce(T.load(str(harness.TRACE_DIR)))
    prog = P.load(str(harness.TRACE_DIR))
    shutil.rmtree(harness.TRACE_DIR, ignore_errors=True)
    hlo = round_hlo(runner, cell, args.seed)
    prog = P.scopes_from_hlo(prog, hlo)

    from repro.obs import recent_spans
    rounds = out.counts["rounds"]
    brk = P.breakdown(prog)
    metrics = P.round_metrics(brk, rounds)
    metrics["round_useful_share"] = round_useful_share.read(
        harness.Context(red, out.counts, {}))
    print(json.dumps({
        "workload": cell.name, "seed": args.seed, "rounds": rounds,
        "window_s": red.window_s, "busy_s": red.busy_s,
        "idle_s": red.window_s - red.busy_s,
        "correct": all(math.isfinite(v) and v <= lim
                       for v, lim in out.checks.values()),
        "metrics": metrics,
        "slowest_round": P.slowest_round(recent_spans(), rounds),
        "breakdown": {"device_ops": red.device_ops,
                      "idle_gaps": red.idle_gaps, **brk},
        "round_module": P.HLO_MODULE.search(hlo).group(1),
        "modules": {k: collections.Counter(m.name.split("(")[0]
                                           for m in v)
                    for k, v in prog.modules.items()},
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
