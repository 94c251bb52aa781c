"""Mining telemetry: metrics registry + structured tracing + exporters.

One ``Telemetry`` object carries everything a session needs to answer
"where did this query's time go":

* ``telemetry.metrics`` — a ``MetricsRegistry`` of typed counters /
  gauges / histograms. Always on: the registry IS the backing store of
  the engine's legacy ``stats`` dicts (derived views, bit-identical to
  the dicts they replaced), so metrics cost what the old dict mutations
  cost.
* ``telemetry.tracer`` — a ``Tracer``. Every span is a
  ``jax.profiler.TraceAnnotation`` named ``ix.<name>``, so under the
  profiler the spans share the device trace's clock. Enabled, it also
  keeps per-query span trees in memory (query → compile/schedule/execute
  → feed bucketing, per-level spans, per-dispatch spans with op kind,
  items, capacities and cache hit/miss, and ``sync`` spans around each
  blocking device→host read). A ``dispatch`` span times the host's
  enqueue, never device time: nothing here synchronises with the device,
  so tracing on or off runs the same work (tested in tests/test_obs.py).
* exporters — Chrome-trace/Perfetto JSON (``--trace out.json`` on
  ``launch/mine.py`` / ``launch/serve.py``), a Prometheus text snapshot,
  and ``snapshot()`` (metrics + per-span aggregates) consumed by
  ``benchmarks/bench_mining.py``.
* ``jax_profile(logdir)`` — optional ``jax.profiler`` start/stop hook
  around a traced query (XLA-level profile to go with the span tree).

Construction: ``Telemetry()`` is disabled tracing + live metrics (what
every ``WaveRunner``/``Miner`` builds when not handed one);
``Telemetry(enabled=True)`` turns the span tree on. Sessions share one
``Telemetry`` across Miner + runner so a query's spans and counters land
in one place.
"""
from __future__ import annotations

from contextlib import contextmanager

from .export import chrome_trace, prometheus_text, write_chrome_trace
from .registry import (Counter, Gauge, Histogram, LegacyStatsView,
                       MetricsRegistry)
from .trace import Span, Tracer

__all__ = ["Telemetry", "Counter", "Gauge", "Histogram", "MetricsRegistry",
           "LegacyStatsView", "Span", "Tracer", "chrome_trace",
           "prometheus_text", "write_chrome_trace"]


class Telemetry:
    """Registry + tracer + export surface for one mining session."""

    def __init__(self, enabled: bool = False,
                 registry: MetricsRegistry | None = None):
        self.metrics = registry if registry is not None else MetricsRegistry()
        self.tracer = Tracer(enabled=enabled)

    # ------------------------------------------------------------- control
    @property
    def enabled(self) -> bool:
        return self.tracer.enabled

    def enable(self) -> None:
        self.tracer.enabled = True

    def disable(self) -> None:
        self.tracer.enabled = False

    # ------------------------------------------------------------- export
    def snapshot(self) -> dict:
        """Everything an external consumer wants in one dict: the metrics
        snapshot, per-span-name wall/self-time aggregates, and the root
        span summaries (name, seconds, #children)."""
        spans: dict[str, dict] = {}
        for sp in self.tracer.spans():
            agg = spans.setdefault(sp.name, {"count": 0, "seconds": 0.0,
                                             "self_seconds": 0.0})
            agg["count"] += 1
            agg["seconds"] += sp.seconds
            agg["self_seconds"] += sp.self_seconds
        return {
            "metrics": self.metrics.snapshot(),
            "spans": spans,
            "roots": [{"name": r.name, "cat": r.cat,
                       "seconds": r.seconds,
                       "spans": sum(1 for _ in r.walk())}
                      for r in self.tracer.finished],
        }

    def chrome_trace(self) -> dict:
        return chrome_trace(self.tracer)

    def write_trace(self, path):
        return write_chrome_trace(path, self.tracer, self.metrics)

    def prometheus_text(self, prefix: str = "mining_") -> str:
        return self.metrics.prometheus_text(prefix=prefix)

    # ------------------------------------------------------ jax profiler
    @contextmanager
    def jax_profile(self, logdir: str | None):
        """Optional ``jax.profiler`` start/stop hook around a traced
        query: ``with tel.jax_profile("/tmp/prof"): miner.count(...)``.
        ``logdir=None`` (or an unavailable profiler) degrades to a
        no-op, so callers can pass the CLI flag through unconditionally."""
        if not logdir:
            yield None
            return
        import jax
        jax.profiler.start_trace(logdir)
        try:
            yield logdir
        finally:
            jax.profiler.stop_trace()


# module-level disabled singleton: runners built without a session share
# this so bare WaveRunner construction never allocates tracer state; note
# its *registry* is still per-runner (each runner builds its own
# Telemetry unless handed one — see WaveRunner.__init__)
def null_telemetry() -> Telemetry:
    return Telemetry(enabled=False)
