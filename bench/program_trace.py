"""The program's own marks in a profiler trace: the launcher's ``train.*``
host spans, the device's module executions, and each operation's
innermost named scope, with what they say about the measured window.

`load` reads the same ``.xplane.pb`` as `bench.trace.load`, into a
`ProgramTrace`: operations of line ``XLA Ops``, executions of line
``XLA Modules``, and the host spans whose names start with ``train.``
(with the window span).  A TPU operation's event carries no op-name stat
(only ``device_duration_ps``, ``device_offset_ps`` and a time scale), so
`scopes_from_hlo` reads each operation's scope from the op-name metadata
of the compiled program's HLO text, by instruction name.  The
functions after `load` work on plain intervals, so tests feed them
synthetic ones.  `bench/explain.py` runs a cell under the profiler and
prints them.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import math
import os
import re
from collections import defaultdict

from bench import trace as T

SPAN_PREFIX = "train."
MODULES_LINE = "XLA Modules"
# the named scopes the round engine (`core/round.py`) and the models set
SCOPES = ("schedule", "broadcast", "local_step", "optimizer", "aggregate",
          "embed", "attention", "mlp", "head")
UNSCOPED = "unscoped"
INSIDE = "inside programs"
HLO_MODULE = re.compile(r"^HloModule ([^\s,]+)", re.M)
HLO_OP_NAME = re.compile(r'^\s*(?:ROOT )?%?([^\s=]+) = .*?op_name="([^"]*)"',
                         re.M)


@dataclasses.dataclass(frozen=True)
class Op(T.Event):
    scope: str = ""
    inst: str = ""      # the HLO instruction's name, ``fusion.12``


@dataclasses.dataclass
class ProgramTrace:
    ops: dict[str, list[Op]]            # device plane -> its leaf operations
    modules: dict[str, list[T.Event]]   # device plane -> module executions
    spans: list[T.Event]                # ``train.*`` host spans
    window: tuple[float, float]


def scope_of(op_name: str) -> str:
    """The innermost of `SCOPES` on an op-name path, ``""`` where none:
    ``jit(f)/while/body/local_step/vmap(transpose(jvp(attention)))/dot``
    -> ``attention``."""
    for word in reversed(re.findall(r"[A-Za-z_]\w*", op_name or "")):
        if word in SCOPES:
            return word
    return ""


def load(log_dir: str) -> ProgramTrace:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(paths[-1])
    ops, modules, spans, window = {}, {}, [], []
    interval = lambda e: (e.start_ns * 1e-9,
                          (e.start_ns + e.duration_ns) * 1e-9)
    for plane in data.planes:
        if T.DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                evs = list(line.events)
                if line.name == T.OPS_LINE:
                    ops[plane.name] = T.leaves([
                        Op(T._op_name(e.name), *interval(e), "",
                           e.name.partition(" = ")[0].strip().lstrip("%"))
                        for e in evs])
                elif line.name == MODULES_LINE:
                    modules[plane.name] = [T.Event(e.name, *interval(e))
                                           for e in evs]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append(T.Event(e.name, *interval(e)))
                    elif e.name == T.WINDOW_SPAN:
                        window.append(interval(e))
    if len(window) != 1:
        raise ValueError(f"expected one {T.WINDOW_SPAN} span, found "
                         f"{len(window)}")
    return ProgramTrace(ops, modules, spans, window[0])


def scopes_from_hlo(trace: ProgramTrace, hlo_text: str) -> ProgramTrace:
    """Give each operation that runs inside an execution of the compiled
    program ``hlo_text`` the scope of its instruction's op-name metadata
    there.  Where no module execution bears the program's name, every
    operation is looked up."""
    module = HLO_MODULE.search(hlo_text).group(1)
    names = dict(HLO_OP_NAME.findall(hlo_text))
    ops = {}
    for plane, evs in trace.ops.items():
        mods = trace.modules.get(plane, [])
        own = [m for m in mods if m.name.split("(")[0].strip() == module]
        runs = T.merged(own, -math.inf, math.inf)
        starts = [s for s, _ in runs]

        def inside(e):
            i = bisect.bisect_right(starts, e.start) - 1
            return not own or (i >= 0 and e.end <= runs[i][1])

        ops[plane] = [
            dataclasses.replace(e, scope=scope_of(names.get(e.inst, "")))
            if inside(e) else e for e in evs]
    return dataclasses.replace(trace, ops=ops)


def intersect(a: list[tuple], b: list[tuple]) -> list[tuple]:
    """The intersection of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, t = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < t:
            out.append((s, t))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def launch_gaps(ops, modules, spans, lo: float, hi: float) -> dict:
    """Device idle seconds in [lo, hi]: the idle time outside every module
    execution, by the innermost ``train.*`` span that holds each stretch's
    midpoint (`bench.trace.attribute`), and the idle time inside one as
    `INSIDE`.  The values sum to the window's idle time."""
    idle = T.idle_gaps(ops, lo, hi)
    inside = intersect(idle, T.merged(modules, lo, hi))
    out = T.attribute(intersect(idle, T.idle_gaps(modules, lo, hi)), spans)
    out[INSIDE] = sum(t - s for s, t in inside)
    return out


def device_scopes(ops, lo: float, hi: float) -> dict:
    """Device-busy seconds in [lo, hi] by the operations' innermost scope
    (the union of each scope's intervals), `UNSCOPED` for the rest."""
    by = defaultdict(list)
    for e in ops:
        by[e.scope or UNSCOPED].append(e)
    return {k: T.busy_seconds(v, lo, hi) for k, v in by.items()}


def breakdown(trace: ProgramTrace, top: int = 3) -> dict:
    """``launch_gaps`` and ``device_scopes``, averaged over the devices,
    as ``[[name, seconds], ...]`` longest first, and ``scope_ops``: each
    scope's ``top`` operation groups by their summed seconds."""
    lo, hi = trace.window
    gaps, scopes = defaultdict(float), defaultdict(float)
    per_op = defaultdict(lambda: defaultdict(float))
    n = len(trace.ops)
    for plane, ops in trace.ops.items():
        mods = trace.modules.get(plane, [])
        for k, v in launch_gaps(ops, mods, trace.spans, lo, hi).items():
            gaps[k] += v / n
        for k, v in device_scopes(ops, lo, hi).items():
            scopes[k] += v / n
        for e in ops:
            per_op[e.scope or UNSCOPED][e.name] += max(
                0.0, min(e.end, hi) - max(e.start, lo)) / n
    rank = lambda d: [[k, v] for k, v in sorted(d.items(),
                                                key=lambda kv: -kv[1])]
    return {"launch_gaps": rank(gaps), "device_scopes": rank(scopes),
            "scope_ops": {k: rank(v)[:top] for k, v in per_op.items()}}


def round_metrics(brk: dict, rounds: int) -> dict:
    """Per round of the window: ``round_launch_gap_ms`` (idle outside
    every module execution), ``round_attention_ms`` and
    ``round_aggregate_ms`` (busy under those scopes; left out where no
    operation carries the scope)."""
    if not rounds:
        return {}
    gaps, scopes = dict(brk["launch_gaps"]), dict(brk["device_scopes"])
    out = {"round_launch_gap_ms":
           1e3 * sum(v for k, v in gaps.items() if k != INSIDE) / rounds}
    for scope in ("attention", "aggregate"):
        if scopes.get(scope):
            out[f"round_{scope}_ms"] = 1e3 * scopes[scope] / rounds
    return out


def slowest_round(records, rounds: int) -> dict | None:
    """Of the last ``rounds`` ``train.round`` records (`repro.obs`
    `recent_spans`), the slowest: its index and ms, the ms of each span
    inside it by name, and the window's ``train.gc`` ms."""
    rs = [r for r in records if r.name == "train.round"][-rounds:]
    if not rs:
        return None
    worst = max(rs, key=lambda r: r.end - r.start)
    inner = defaultdict(float)
    gc_ms = 0.0
    for r in records:
        if r.name == "train.gc" and rs[0].start <= r.start <= rs[-1].end:
            gc_ms += 1e3 * (r.end - r.start)
        if r.name != "train.round" and worst.start <= r.start \
                and r.end <= worst.end:
            inner[r.name] += 1e3 * (r.end - r.start)
    return {"round": worst.round, "ms": 1e3 * (worst.end - worst.start),
            "spans_ms": dict(inner), "window_gc_ms": gc_ms}
