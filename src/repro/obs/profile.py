"""Span timers, garbage-collection spans and the retrace sentinel
(DESIGN.md §12).

`span` is the workhorse: a context manager timing a named region on the
host clock, mirrored into `jax.profiler.TraceAnnotation` so the same names
line up in a TensorBoard/XPlane trace when one is being captured, and
emitted as a ``span`` event when an `Obs` log is attached.  Module-level
totals (`span_totals`) survive without any log so ad-hoc scripts can print
a breakdown.  Each span knows its parent (the span open around it in the
same thread) and a round index (its own, else its parent's); the last
`RECENT_SPANS` finished spans stay in memory (`recent_spans`), so a
caller can take one slow round apart after the fact.

`gc_spans` records each Python garbage collection inside its block as a
span of its own, in memory and on the trace.

`RetraceSentinel` watches the fleet/serve scans' ``_cache_size()`` deltas
at runtime: chunked controller sweeps are DESIGNED to hit the jit cache
after their first chunk (T/E/admit/offset are traced scalars), so any
mid-run growth is a perf bug — the sentinel logs a ``retrace_warning``
event and a Python warning naming the grown function instead of letting a
silent 100x slowdown ride to the end of the run.
"""
from __future__ import annotations

import collections
import contextlib
import gc
import logging
import threading
import time
from typing import Callable, NamedTuple

logger = logging.getLogger("repro.obs")

# name -> [count, total_ms]; the no-log fallback store
_SPAN_TOTALS: dict[str, list] = {}
RECENT_SPANS = 8192


class SpanRecord(NamedTuple):
    """One finished span; times in `time.perf_counter` seconds."""

    name: str
    start: float
    end: float
    parent: str | None
    round: int | None


_RECENT: collections.deque = collections.deque(maxlen=RECENT_SPANS)
_OPEN = threading.local()       # .stack: [(name, round)] of open spans


def _open_spans() -> list:
    if not hasattr(_OPEN, "stack"):
        _OPEN.stack = []
    return _OPEN.stack


def span_totals() -> dict[str, dict]:
    """Accumulated span timings since the last `reset_spans`."""
    return {k: {"count": v[0], "total_ms": round(v[1], 3)}
            for k, v in _SPAN_TOTALS.items()}


def recent_spans() -> list[SpanRecord]:
    """The last `RECENT_SPANS` finished spans, oldest first."""
    return list(_RECENT)


def reset_spans() -> None:
    _SPAN_TOTALS.clear()
    _RECENT.clear()


def _annotation(name: str, **meta):
    try:
        import jax.profiler
        return jax.profiler.TraceAnnotation(name, **meta)
    except Exception:                                    # pragma: no cover
        return contextlib.nullcontext()


def _finish(name: str, t0: float, t1: float, parent, rnd, obs) -> float:
    ms = (t1 - t0) * 1e3
    agg = _SPAN_TOTALS.setdefault(name, [0, 0.0])
    agg[0] += 1
    agg[1] += ms
    _RECENT.append(SpanRecord(name, t0, t1, parent, rnd))
    if obs is not None:
        obs.event("span", name=name, ms=round(ms, 3))
    return ms


@contextlib.contextmanager
def span(name: str, obs=None, *, round: int | None = None):
    """``with span("round_step"):`` — host wall time + profiler annotation.

    Emits ``{"kind": "span", "name": ..., "ms": ...}`` to ``obs`` (when
    given) on exit, always folds into `span_totals` and `recent_spans`.
    ``round`` rides on the profiler annotation too.  Never raises from
    instrumentation: a missing profiler backend degrades to timing only.
    """
    stack = _open_spans()
    parent, parent_round = stack[-1] if stack else (None, None)
    rnd = parent_round if round is None else round
    annotation = _annotation(name) if round is None else \
        _annotation(name, round=round)
    stack.append((name, rnd))
    t0 = time.perf_counter()
    try:
        with annotation:
            yield
    finally:
        t1 = time.perf_counter()
        stack.pop()
    _finish(name, t0, t1, parent, rnd, obs)


@contextlib.contextmanager
def gc_spans(name: str, on_collect: Callable[[float], None]):
    """Inside the block, each Python garbage collection becomes a span
    ``name`` (in `recent_spans`, `span_totals` and the profiler trace),
    child of the span open in the thread that collected;
    ``on_collect(ms)`` follows each one."""
    started: list = []

    def hook(phase, info):
        if phase == "start":
            stack = _open_spans()
            parent, rnd = stack[-1] if stack else (None, None)
            annotation = _annotation(name)
            annotation.__enter__()
            started.append((annotation, parent, rnd, time.perf_counter()))
        elif started:
            annotation, parent, rnd, t0 = started.pop()
            t1 = time.perf_counter()
            annotation.__exit__(None, None, None)
            on_collect(_finish(name, t0, t1, parent, rnd, None))

    gc.callbacks.append(hook)
    try:
        yield
    finally:
        gc.callbacks.remove(hook)


def _default_watch() -> dict[str, Callable[[], int]]:
    """The two scan caches every production run flows through.  Imported
    lazily: `repro.obs` must stay importable without dragging the simulator
    stack in (and vice versa — the simulators never import obs)."""
    from repro.energy.fleet import _run_fleet_scan
    from repro.serve.fleet_serve import _run_serve_scan
    return {"_run_fleet_scan": _run_fleet_scan._cache_size,
            "_run_serve_scan": _run_serve_scan._cache_size}


class RetraceSentinel:
    """Watches jit-cache sizes between `snapshot` and `check` calls.

    >>> sentinel = RetraceSentinel(obs)
    >>> sentinel.snapshot()          # after the warm-up chunk
    >>> ...                          # more chunks
    >>> sentinel.check()             # [] if cache-stable, else warns

    ``check(expect=k)`` tolerates exactly ``k`` new entries (e.g. +1 for a
    deliberate backend flip); anything beyond logs a ``retrace_warning``
    event and `logging` warning per grown function and re-snapshots so one
    regression is reported once, not once per subsequent chunk.
    """

    def __init__(self, obs=None,
                 watch: dict[str, Callable[[], int]] | None = None):
        self.obs = obs
        self.watch = _default_watch() if watch is None else dict(watch)
        self._base: dict[str, int] | None = None

    def sizes(self) -> dict[str, int]:
        return {name: int(size()) for name, size in self.watch.items()}

    def snapshot(self) -> dict[str, int]:
        self._base = self.sizes()
        return dict(self._base)

    def check(self, expect: int = 0, context: str = "") -> list[dict]:
        """Compare against the last snapshot; returns the offending deltas
        (empty list == cache-stable)."""
        if self._base is None:
            self.snapshot()
            return []
        grown = []
        now = self.sizes()
        for name, size in now.items():
            delta = size - self._base.get(name, size)
            if delta > expect:
                grown.append({"fn": name, "delta": delta, "size": size,
                              "context": context})
                logger.warning(
                    "unexpected retrace: %s grew by %d jit-cache entries%s "
                    "(traced-scalar sweeps should hit the cache — a config "
                    "pytree's structure, a shape, or a static arg changed "
                    "mid-run)", name, delta,
                    f" during {context}" if context else "")
                if self.obs is not None:
                    self.obs.event("retrace_warning", **grown[-1])
        self._base = now
        return grown
