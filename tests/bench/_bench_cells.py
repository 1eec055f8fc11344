"""Small copies of the benchmark's cells for tests on the CPU: each cell's
traffic and limits as committed, with the system's smoke configuration of
the same model (float32 unless ``dtype`` is given), the cell's own choice
of head, positions and attention biases, and short sequences.
In float32 the program agrees with the reference to rounding, so a sound
run passes limits set for bf16 at full size; in the cell's own bf16 the
small leaves' rounding reads higher than the full-size limits allow."""
from __future__ import annotations

import dataclasses
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402


# the cell's architecture choices that a smoke configuration keeps as they
# are at full size
ARCH_KEYS = ("tie_embeddings", "pos_type", "max_position", "qkv_bias")


def small_cell(name: str, seq: int = 16, dtype: str | None = None
               ) -> harness.Cell:
    from repro.configs import get_smoke_config

    cell = harness.load_cell(name, ROOT)
    cfg = dataclasses.asdict(get_smoke_config(cell.config["name"]))
    cfg = {k: list(v) if isinstance(v, tuple) else v for k, v in cfg.items()}
    cfg["dtype"] = dtype or cfg["dtype"]
    for k in ARCH_KEYS:
        cfg[k] = cell.config.get(k, cfg[k])
    return dataclasses.replace(cell, config=cfg,
                               traffic=dict(cell.traffic, seq=seq))
