#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, for one cell, over
many seeds in one process (the benchmark's own runs never run this):

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 [--out f.jsonl]

For each seed: the program's first three rounds through the launcher at
the cell's own size, compared with the float32 reference (the lower
readings); the control, the reference with fp8 matrix products in the
program's place (the upper readings); and the faults planted in the
reference in the program's place: half of each batch left out, and the
participation mask ignored (every client aggregated).  A state left
unchanged reads 1 on both norm gaps by construction.  ``--rounds N`` also
runs the first seed on to round N and records its losses, to see that
the cell's learning rate trains without diverging.
"""
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import argparse  # noqa: E402
import json  # noqa: E402

import jax  # noqa: E402
import numpy as np  # noqa: E402

from bench import harness  # noqa: E402
from bench import reference as R  # noqa: E402
from bench.runners import fl_round  # noqa: E402
from bench.ref import common  # noqa: E402

def rounds(cell) -> dict:
    """The reference's round and each planted variant's, built once so that
    each compiles once for all seeds."""
    tr = cell.traffic
    out = {"reference": R.Round(cell.config, tr),
           "control_fp8": R.Round(cell.config, tr, ein=common.ein_fp8),
           "fault_half_batch": R.Round(cell.config, tr, half_batch=True)}
    if tr["policy"] != "always":
        out["fault_mask_ignored"] = out["reference"]
    return out


def readings(cell, seed, run, rnds, rounds_more: int = 0,
             variants: bool = True) -> dict:
    tr = cell.traffic
    w0, make = fl_round.seeded(cell, seed, run)
    names = R.leaf_names(w0)
    run.batch_fn = make
    t = time.perf_counter()
    w, hist, prog = fl_round.first_rounds(run, w0, max(3, rounds_more))
    prog_s = time.perf_counter() - t
    del w, w0
    masks = R.schedule(tr["policy"], tr["schedule_seed"], 3,
                       fl_round.energy_cycles(tr))
    out = {"seed": seed, "lr": tr["lr"], "program_s": prog_s,
           "losses": [h["loss"] for h in hist]}
    t = time.perf_counter()
    ref = fl_round.reference_readings(cell, seed, make, masks,
                                      rnds["reference"])
    out["reference_s"] = time.perf_counter() - t
    out["program"] = R.compare(prog, ref, names)
    for name, rnd in rnds.items():
        if name == "reference" or not variants:
            continue
        planted = np.ones_like(masks) if name == "fault_mask_ignored" else masks
        t = time.perf_counter()
        got = fl_round.reference_readings(cell, seed, make, planted, rnd)
        out[name] = R.compare(got, ref, names)
        out[name + "_s"] = time.perf_counter() - t
    out["ref_losses"] = ref.losses
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--rounds", type=int, default=0)
    ap.add_argument("--variant-seeds", type=int, default=3,
                    help="run the control and the faults for the first N "
                         "seeds only")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if jax.devices()[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 3
    cell = harness.load_cell(args.workload)
    run = fl_round.launcher_run(cell)
    rnds = rounds(cell)
    sink = open(args.out, "a") if args.out else None
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        rec = readings(cell, seed, run, rnds, args.rounds if i == 0 else 0,
                       variants=i < args.variant_seeds)
        rec["workload"] = cell.name
        line = json.dumps(rec)
        print(line, flush=True)
        if sink:
            sink.write(line + "\n")
            sink.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
