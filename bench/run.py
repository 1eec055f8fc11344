#!/usr/bin/env python3
"""Run one benchmark cell on the chip this process finds:

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result, one JSON object; the last
lines of standard error are the numbers that decide ``correct``, each
beside its limit.  Exits non-zero, with no result, where JAX finds no TPU
or fewer chips than the cell asks for.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# the compile cache lives at a fixed path inside the checkout; the
# system's own cache set-up takes it from this variable
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
if not (ROOT / "src" / "repro").is_dir():
    sys.exit(f"bench: the system under test is missing ({ROOT / 'src'})")
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402

jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

from bench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
