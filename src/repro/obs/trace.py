"""Structured tracing: per-query span trees over the mining pipeline.

A ``Tracer`` records a tree of ``Span``s per traced query:

    query(triangle, seq)
    ├─ compile
    ├─ schedule            (batch queries)
    └─ execute
       ├─ feed_bucket      (host bucketing of one level-1 feed pass)
       ├─ feed  L1         (one per edge-feed chunk: cap, items)
       │  └─ level L2 expand
       │     ├─ dispatch   (host enqueue of one level executable)
       │     ├─ sync       (a blocking device->host read; attr ``site``)
       │     └─ level L3 count
       │        └─ dispatch
       └─ finalize         (one read of the count accumulators)

Every span is also a ``jax.profiler.TraceAnnotation`` named ``ix.<name>``
(its cheap attributes ride along as annotation metadata), entered whether
or not the tree is on. With no profiler running it costs about a
microsecond; under ``jax.profiler`` the span lands on the host plane of
the same trace as the device ops, on the same clock, so an idle stretch of
the device can be matched with what the host was doing.

The in-memory tree is kept only when the tracer is *enabled*: it feeds the
Chrome-trace export (``repro.obs.export``), ``Telemetry.snapshot()`` and
``level_seconds()``. Each span there records ``perf_counter`` start/end, a
category, and free-form attributes. An attribute given as a zero-argument
callable is evaluated only when the tree is on (an attribute that costs
work, such as a device count summed on the host), and never reaches the
profiler.

Nothing here synchronises with the device: a ``dispatch`` span measures
the host's enqueue (trace and compile included on a fresh executable),
not device time, which is the profiler's to report. Host time spent
waiting on the device sits in ``sync`` and ``finalize`` spans. A span is
opened and closed within one step of a generator, never held across a
``yield``, so the tree and the profiler nest by time alike.

``self_seconds`` is a span's exclusive time (duration minus direct
children), which makes per-level attribution sum-consistent: the exclusive
times of every span under ``execute`` add up to the query's execute wall
time minus untracked gaps.
"""
from __future__ import annotations

import time
from contextlib import contextmanager

from jax.profiler import TraceAnnotation

__all__ = ["Span", "Tracer"]


class Span:
    """One timed node of the trace tree."""

    __slots__ = ("name", "cat", "attrs", "t0", "t1", "children")

    def __init__(self, name: str, cat: str = "span",
                 attrs: dict | None = None):
        self.name = name
        self.cat = cat
        self.attrs = attrs or {}
        self.t0 = time.perf_counter()
        self.t1 = None
        self.children: list[Span] = []

    def close(self) -> None:
        if self.t1 is None:
            self.t1 = time.perf_counter()

    @property
    def seconds(self) -> float:
        return (self.t1 if self.t1 is not None
                else time.perf_counter()) - self.t0

    @property
    def self_seconds(self) -> float:
        """Exclusive time: duration minus direct children's durations."""
        return self.seconds - sum(c.seconds for c in self.children)

    def walk(self):
        yield self
        for c in self.children:
            yield from c.walk()

    def find(self, name: str):
        """All descendant spans (incl. self) with ``name``."""
        return [s for s in self.walk() if s.name == name]

    def to_dict(self) -> dict:
        return {"name": self.name, "cat": self.cat,
                "t0": self.t0, "seconds": self.seconds,
                "attrs": dict(self.attrs),
                "children": [c.to_dict() for c in self.children]}

    def __repr__(self) -> str:
        return (f"Span({self.name!r}, cat={self.cat!r}, "
                f"{self.seconds * 1e3:.3f}ms, "
                f"{len(self.children)} children)")


class Tracer:
    """Span-tree recorder. ``enabled=False`` (the default) keeps no tree:
    ``span()`` then only enters its profiler annotation. Finished root
    spans accumulate in ``self.finished``."""

    def __init__(self, enabled: bool = False):
        self.enabled = enabled
        self.finished: list[Span] = []
        self._stack: list[Span] = []

    # ----------------------------------------------------------- recording
    @property
    def current(self) -> Span | None:
        return self._stack[-1] if self._stack else None

    @contextmanager
    def span(self, name: str, cat: str = "span", **attrs):
        """One span: an ``ix.<name>`` profiler annotation always, and a
        tree node (yielded) when enabled, else None. Callable attribute
        values are evaluated only for the tree."""
        with TraceAnnotation(f"ix.{name}", **{
                k: v for k, v in attrs.items() if not callable(v)}):
            if not self.enabled:
                yield None
                return
            sp = Span(name, cat, {k: (v() if callable(v) else v)
                                  for k, v in attrs.items()})
            parent = self.current
            if parent is not None:
                parent.children.append(sp)
            self._stack.append(sp)
            try:
                yield sp
            finally:
                sp.close()
                self._stack.pop()
                if parent is None:
                    self.finished.append(sp)

    # ------------------------------------------------------------- queries
    def spans(self, name: str | None = None) -> list[Span]:
        """All recorded spans (across finished roots), depth-first;
        filtered by ``name`` when given."""
        out: list[Span] = []
        for root in self.finished:
            out.extend(root.walk() if name is None else root.find(name))
        return out

    def seconds(self, name: str) -> float:
        """Total wall seconds across every span named ``name``."""
        return sum(s.seconds for s in self.spans(name))

    def last(self, name: str) -> Span | None:
        sp = self.spans(name)
        return sp[-1] if sp else None

    def clear(self) -> None:
        self.finished.clear()
        self._stack.clear()

    # ---------------------------------------------------------- aggregates
    def level_seconds(self) -> dict[str, float]:
        """Exclusive (self) seconds aggregated by span name — the
        "where did this query's time go" per-level accounting. Summing the
        values over all spans of a query reproduces the query wall time
        minus untracked host gaps."""
        agg: dict[str, float] = {}
        for sp in self.spans():
            agg[sp.name] = agg.get(sp.name, 0.0) + sp.self_seconds
        return agg
