"""From a profiler trace to device busy time, idle gaps and what the host
was doing in them.

`load` reads the ``.xplane.pb`` that `jax.profiler.trace` writes, with
nothing but JAX, into plain intervals: the operations of each TPU (line
``XLA Ops`` of planes ``/device:TPU:<n>``, containers such as while loops
left out, as their bodies' operations are there too) and the benchmark's
own host spans (``TraceAnnotation`` names that start with ``bench.``).  Host and
device events share the trace's clock.  Everything after `load` works on
those intervals alone, so the tests feed it synthetic ones.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float    # seconds on the trace's clock
    end: float


@dataclasses.dataclass
class Trace:
    ops: dict[str, list[Event]]     # device plane -> its operations
    spans: list[Event]              # the benchmark's host spans


def _op_name(text: str) -> str:
    """An operation's group from its HLO text (``%fusion.12 = bf16[4,384]
    {1,0} fusion(...)``): the instruction's name without its number, and
    the first shape it returns without the layout."""
    name, _, rest = text.partition(" = ")
    shape = re.sub(r"\{[^}]*\}", "", rest.split(" ", 1)[0]).strip("(,")
    return f"{re.sub(r'[.][0-9]+$', '', name.lstrip('%'))} {shape}".strip()


def leaves(events: list[Event]) -> list[Event]:
    """Drop the operations that hold others: a while loop's event spans
    the events of its body on the same line."""
    evs = sorted(events, key=lambda e: (e.start, -e.end))
    return [e for e, nxt in zip(evs, evs[1:] + [None])
            if nxt is None or not (nxt.start < e.end and nxt.end <= e.end)]


def load(log_dir: str) -> Trace:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(paths[-1])
    ops, spans = {}, []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            ops[plane.name] = leaves([
                Event(_op_name(e.name), e.start_ns * 1e-9,
                      (e.start_ns + e.duration_ns) * 1e-9)
                for line in plane.lines if line.name == OPS_LINE
                for e in line.events])
        elif plane.name.startswith("/host:"):
            spans += [Event(e.name, e.start_ns * 1e-9,
                            (e.start_ns + e.duration_ns) * 1e-9)
                      for line in plane.lines for e in line.events
                      if e.name.startswith(SPAN_PREFIX)]
    return Trace(ops, spans)


def window_of(trace: Trace) -> tuple[float, float]:
    wins = [s for s in trace.spans if s.name == WINDOW_SPAN]
    if len(wins) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found {len(wins)}")
    return wins[0].start, wins[0].end


def merged(events: list[Event], lo: float, hi: float) -> list[tuple]:
    """The union of the events' intervals, clipped to [lo, hi], as sorted
    disjoint (start, end) pairs."""
    out: list[list[float]] = []
    for e in sorted(events, key=lambda e: e.start):
        s, t = max(e.start, lo), min(e.end, hi)
        if t <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t)
        else:
            out.append([s, t])
    return [tuple(x) for x in out]


def busy_seconds(events: list[Event], lo: float, hi: float) -> float:
    return sum(t - s for s, t in merged(events, lo, hi))


def idle_gaps(events: list[Event], lo: float, hi: float) -> list[tuple]:
    """The stretches of [lo, hi] in which no operation ran."""
    gaps, at = [], lo
    for s, t in merged(events, lo, hi):
        if s > at:
            gaps.append((at, s))
        at = t
    if hi > at:
        gaps.append((at, hi))
    return gaps


def attribute(gaps: list[tuple], spans: list[Event]) -> dict[str, float]:
    """Seconds of idle device time by what the host was doing: each gap
    goes to the innermost benchmark span that holds its midpoint (the
    window span itself when no other does)."""
    if not gaps:
        return {}
    g = np.asarray(gaps, np.float64)
    mid, length = g.mean(axis=1), g[:, 1] - g[:, 0]
    owner = np.full(len(g), -1)
    # longest span first, so that an inner span overwrites its parents
    order = sorted(range(len(spans)), key=lambda i: spans[i].start
                   - spans[i].end)
    for i in order:
        owner[(mid >= spans[i].start) & (mid <= spans[i].end)] = i
    out: dict[str, float] = defaultdict(float)
    for i in np.unique(owner):
        name = spans[i].name if i >= 0 else "outside any span"
        out[name] += float(length[owner == i].sum())
    return dict(out)


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float                # averaged over the devices traced
    device_ops: list             # [[name, seconds], ...] longest first
    idle_gaps: list              # [[host span, seconds], ...] longest first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def reduce(trace: Trace, top: int = 10) -> Reduction:
    lo, hi = window_of(trace)
    if not trace.ops:
        raise ValueError("the trace holds no TPU plane")
    busy, per_op, idle = [], defaultdict(float), defaultdict(float)
    for events in trace.ops.values():
        busy.append(busy_seconds(events, lo, hi))
        for e in events:
            per_op[e.name] += max(0.0, min(e.end, hi) - max(e.start, lo))
        for name, secs in attribute(idle_gaps(events, lo, hi),
                                    trace.spans).items():
            idle[name] += secs / len(trace.ops)
    rank = lambda d: [[k, v] for k, v in
                      sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return Reduction(hi - lo, sum(busy) / len(busy),
                     rank({k: v / len(trace.ops) for k, v in per_op.items()}),
                     rank(idle))
