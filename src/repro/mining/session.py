"""Miner session API: a graph-resident query engine.

IntersectX's core claim is that stream state — the SMT, the S-Cache, the
cached stream registers — persists *across* intersections, so repeated
queries over one graph amortise all data movement. The one-shot entry
points this repo grew up with (``WaveRunner.run``/``run_set``, the
per-app wrappers in ``mining.apps``) re-stage the graph and re-derive
every schedule per call. A ``Miner`` is the session that owns a graph for
its lifetime and serves any number of queries against it:

    m = Miner(graph)
    m.count("triangle")                 # -> int
    m.count_many(["4-clique", "diamond", "4-cycle",
                  "paw", "4-path", "4-star"])   # -> list[int], one pass
    m.embeddings("triangle")            # -> (N, 3) int32 matrix

Every query runs through an explicit three-stage pipeline, each stage
memoised for the session's lifetime:

**compile** — a query (a name from ``plan._NAMED_QUERIES``, a ``Motif``
shape, or an explicit ``Pattern``) lowers to a ``WavePlan`` via
``plan.compile_pattern``. Plans are cached per (query, emit, aggregate),
each beside its one-plan ``PlanForest`` — the program a single query
runs — so a repeated query builds nothing on the host.

**schedule** — for batches, the automatic matching-order search
(``forest.schedule_patterns``) picks each ``Motif``'s matching order to
maximise shared canonical prefixes across the batch (explicit ``Pattern``
queries are fixed points), then ``forest.build_forest`` merges the
compiled plans into a ``PlanForest``. Forests are cached on the batch's
canonical plan keys, so a repeated batch re-derives nothing.

**execute** — ``WaveRunner.run_set`` interprets the forest,
with two session-level residency guarantees: the graph's CSR buffers are
staged to device ONCE at construction (``jax.device_put``), and every
jitted executable lives in the session's ``ExecutableCache``, so repeated
queries never retrace. A ``Miner`` is single-threaded (no locking around
the cache or the runner's mutable stats): a concurrent server gives each
worker its own session — per-worker warm-up, zero retraces after it.

Executable-cache key
--------------------

This section is THE definition of the executable-cache key — every other
docstring (``MinerConfig``, ``ExecutableCache``, ``mining.shard``) points
here instead of restating it. ``ExecutableCache`` keys are::

    (mesh/shape signature) + (chunk, backend)
        + (kind, LevelOp, capacity signature, ...)

segment by segment:

* **mesh/shape signature** — ``mesh_signature(mesh)``: platform + device
  count, extended with the actual mesh axes ``((name, size), ...)`` for a
  sharded session (see the mesh contract below). Isolates executables
  compiled for different device topologies; the sharded runner
  additionally prefixes its per-executable keys with
  ``("mesh", axis, shards)`` so sharded and unsharded traces can never
  collide.
* **runner config** — the ``MinerConfig`` execution knobs that change
  compiled shapes or kernel paths: ``chunk``, ``backend``.
  ``mesh``/``mesh_axis``/``feed_partition`` enter through the mesh segment
  and the feed partitioner instead; ``telemetry`` is deliberately NOT part
  of any key (tracing must never force a retrace — gated in ci_gate
  ``--telemetry``).
* **per-executable key** — the runner's trailing segment:
  ``(kind, LevelOp, capacity signature, ...)``. LevelOps hash by value,
  so structurally equal levels of different patterns share one trace.

A cache *miss* is a retrace — ``Miner.stats`` exposes hit/miss counters,
and the session-reuse contract (tested in tests/test_session.py, gated in
benchmarks/ci_gate.py) is that a repeated query produces **zero** new
traces.

Mesh contract (sharded sessions)
--------------------------------

``Miner(g, mesh=S)`` (S > 1) mines data-parallel over a 1-D device mesh:

* **mesh** — ``distributed.sharding.make_mining_mesh(S, axis=mesh_axis)``
  over the first S visible devices; ``mesh_axis`` defaults to ``"mine"``
  and is the only axis. On CPU, fake devices come from
  ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
* **cache key** — ``mesh_signature(mesh)`` appends the axis spec
  ``((name, size), ...)`` to the platform/device-count signature, and the
  sharded runner additionally prefixes its per-executable keys with
  ``("mesh", axis, shards)``: sharded and unsharded traces can never
  collide, and a repeated sharded query is still 0 retraces.
* **partials layout** — the graph is replicated (``PartitionSpec()``);
  wave buffers are sharded on the mining axis as S back-to-back per-shard
  blocks; count leaves ``psum`` their (hi, lo) partials as four 16-bit
  limbs (exact at any mesh size, reassembled host-side); expand levels
  return per-shard ``(S, m)`` boundary meta (live totals drive lockstep
  chunking, capacities take the max over shards); emit gathers per-shard
  survivor blocks. Counts are bit-identical to the unsharded session.
* **feed** — ``shard.shard_edge_steps`` deals each degree bucket's edges
  round-robin across shards (``feed_partition="contiguous"`` keeps the
  hub-pinning foil); per-shard feed items ride
  ``stats["runner"]["shard_feed_items"]`` — backed by a *labeled* counter
  series (``metrics.counter("shard_feed_items", shard=s)``), so exporters
  see one series per shard while the legacy list shape is preserved.

Observability
-------------

Every session carries a ``repro.obs.Telemetry``: ``miner.telemetry``.

* **metrics** — ``telemetry.metrics`` is the registry backing every
  counter in ``miner.stats`` (session pipeline counters AND the runner's
  dispatch/sync counters — the legacy dicts are derived views, identical
  key order and values). ``telemetry.prometheus_text()`` renders the
  whole registry; labeled series (per-shard feed items) export one sample
  per label set.
* **tracing** — every span is a ``jax.profiler.TraceAnnotation``
  named ``ix.<name>``, always: under the profiler the spans share the
  device trace's clock. Pass ``telemetry=Telemetry(enabled=True)`` (or
  call ``miner.telemetry.enable()``) and every query also records a span
  tree: ``query`` (attribute ``seq``, this session's query number) →
  ``compile``/``schedule``/``execute`` → ``feed_bucket`` (host bucketing
  of a feed pass), per-``feed`` and per-level ``L{l}:{kind}`` spans →
  ``dispatch`` spans (op kind, items, capacities, exec-cache hit/miss)
  and ``sync`` spans (attribute ``site``: ``meta``, ``pack``, ``emit``)
  around each blocking device→host read. A ``dispatch`` span times the
  host's enqueue, not the device: tracing never synchronises, so on or
  off the same work runs. Export with ``telemetry.write_trace(path)``
  (Chrome-trace JSON — chrome://tracing / ui.perfetto.dev) or aggregate
  with ``telemetry.snapshot()`` / ``tracer.level_seconds()``.
* **jax profiler** — ``with miner.telemetry.jax_profile(logdir): ...``
  wraps a query in ``jax.profiler`` start/stop for an XLA-level trace.

Value streams (SVPU, §IV-E)
---------------------------

A session over a *weighted* graph — one built with per-edge f32 values
(``graph.build_csr(..., edge_values=...)`` or ``graph.with_edge_values``)
— additionally serves **aggregate queries**::

    m = Miner(with_edge_values(g, weights))
    m.aggregate("triangle")                  # Σ over triangles of Π edge w
    m.aggregate("4-clique", op="max")        # heaviest clique's weight
    m.aggregate_many(["triangle", "4-clique"], op="min")

The contract, stage by stage:

* **semantics** — an embedding's value is the product of its pattern-edge
  weights; ``aggregate`` reduces embedding values with ``op`` (``'sum'`` /
  ``'max'`` / ``'min'``). Zero embeddings aggregate to ``0.0`` for every
  op. Queries must resolve to fully symmetry-broken schedules (``div ==
  1``; ``Motif`` queries always are, ``triangle-nested`` is not).
* **value alignment** — edge values live in a CSR-aligned plane: the
  session stages them with the keys, once (``padded_value_rows`` gathers
  value rows under the SAME permutation as the sorted key rows, tested in
  tests/test_values.py).
* **zero extra feed passes** — the aggregate leaf rides the unweighted
  plan's dispatches: same stream structure (``LevelOp.stream_key()``
  ignores the value disposition), same membership kernels
  (``kernels.ops.xlevel_agg`` shares ``xlevel_count``'s tile schedule), so
  ``stats["runner"]["feed_chunks"]`` and ``level_kernel_dispatches`` for a
  weighted query equal its unweighted twin's (gated in ci_gate --values).
* **0 retraces on repeat** — aggregate executables are exec-cache keyed
  like every other level (the LevelOp's ``agg`` fields are part of its
  value hash), so a repeated ``aggregate`` call traces nothing new.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Callable, Sequence

import jax
import numpy as np

from repro.graph.csr import CSRGraph
from repro.obs import LegacyStatsView, Telemetry
from .engine import WaveRunner
from .forest import PlanForest, build_forest, schedule_patterns
from .plan import Motif, WavePlan, compile_pattern, resolve_query

__all__ = ["ExecutableCache", "Miner", "MinerConfig", "mesh_signature"]


def mesh_signature(mesh=None) -> tuple:
    """Device-topology component of the executable-cache key: platform +
    device count, extended with the actual mesh axes ``((name, size), ...)``
    when the session mines over a device mesh. Meshes with different axis
    names or sizes therefore never share an executable, and the unsharded
    signature (no mesh segment) can never equal a sharded one."""
    sig: tuple = (jax.default_backend(), jax.device_count())
    if mesh is not None:
        sig += tuple((str(a), int(s)) for a, s in dict(mesh.shape).items())
    return sig


class ExecutableCache:
    """Session-lifetime cache of jitted executables, with hit/miss stats.

    Lifted out of ``WaveRunner`` so executables survive the runner that
    built them: every entry is keyed by the full signature documented in
    the module docstring, making the cache safe to share across runners
    (and, later, across meshes). ``misses`` counts traces actually built —
    the session's *retrace* counter."""

    def __init__(self, prefix: tuple = (), mesh=None):
        self.prefix = prefix + (mesh_signature(mesh),)
        self._entries: dict[tuple, Callable] = {}
        self.hits = 0
        self.misses = 0

    def get_or_build(self, key: tuple, build: Callable):
        """Return (executable, freshly_built?) for ``key``."""
        key = self.prefix + key
        fn = self._entries.get(key)
        if fn is None:
            fn = self._entries[key] = build()
            self.misses += 1
            return fn, True
        self.hits += 1
        return fn, False

    def __len__(self) -> int:
        return len(self._entries)

    def snapshot(self) -> dict:
        return {"hits": self.hits, "misses": self.misses,
                "entries": len(self._entries)}


@dataclasses.dataclass(frozen=True)
class MinerConfig:
    """The ONE way to configure a session — every construction knob lives
    here (``Miner(g, **kwargs)`` is sugar that builds/extends a config).

    The execution knobs are fixed for the session's lifetime because they
    are part of every executable's cache key — see the module docstring's
    "Executable-cache key" section for the full key and which fields land
    in which segment. ``telemetry`` is observability wiring, not an
    execution knob: it is excluded from equality and never enters a cache
    key (tracing must not retrace)."""

    chunk: int | None = None          # wave chunk; None = auto-sized
    backend: str = "auto"             # kernel backend (pallas/xla/auto)
    mesh: int | None = None           # >1: shard over that many devices
    mesh_axis: str = "mine"           # mesh axis name (cache-key relevant)
    feed_partition: str = "round_robin"  # edge-feed dealing (shard.py)
    # session observability (repro.obs); None = fresh disabled Telemetry
    telemetry: Telemetry | None = dataclasses.field(
        default=None, compare=False, repr=False)

    @classmethod
    def from_args(cls, args, **overrides) -> "MinerConfig":
        """Build a config from a parsed launcher namespace
        (``launch.cli`` flag names, shared by mine.py / serve.py):
        ``--shards N`` → ``mesh`` (``N > 1``), ``--trace OUT`` → a
        tracing-enabled ``Telemetry``. Missing attributes fall back to
        the field defaults, so any ``argparse.Namespace`` that carries a
        subset of the flags works. ``overrides`` win over flags."""
        shards = int(getattr(args, "shards", 0) or 0)
        cfg = cls(
            chunk=getattr(args, "chunk", None),
            mesh=shards if shards > 1 else None,
            telemetry=Telemetry(
                enabled=bool(getattr(args, "trace", ""))),
        )
        return dataclasses.replace(cfg, **overrides) if overrides else cfg


class Miner:
    """A graph-resident mining session: compile → schedule → execute.

    Owns the graph (device-staged once), the compiled-plan and forest
    caches, and the executable cache for its whole lifetime. See the
    module docstring for the pipeline contract.
    """

    # session pipeline counters, in their historical insertion order
    _SESSION_KEYS = ("queries", "plan_hits", "plan_misses",
                     "schedule_hits", "schedule_misses")

    def __init__(self, graph: CSRGraph, config: MinerConfig | None = None,
                 telemetry: Telemetry | None = None, **overrides):
        # every knob lives in MinerConfig; bare kwargs (including the
        # historical ``telemetry=`` / ``mesh=`` arguments) are sugar that
        # builds or extends one
        if telemetry is not None:
            overrides["telemetry"] = telemetry
        if config is None:
            config = MinerConfig(**overrides)
        elif overrides:
            config = dataclasses.replace(config, **overrides)
        self.config = config
        # one Telemetry per session, shared with the runner: every counter
        # (session pipeline + runner dispatch/sync) lands in one registry
        # and every span of a traced query lands in one tracer
        self.telemetry = (config.telemetry if config.telemetry is not None
                          else Telemetry())
        if config.mesh is not None and int(config.mesh) > 1:
            from repro.distributed.sharding import make_mining_mesh
            from .shard import ShardedWaveRunner
            self.mesh = make_mining_mesh(int(config.mesh),
                                         axis=config.mesh_axis)
            self.exec_cache = ExecutableCache(mesh=self.mesh)
            self._runner = ShardedWaveRunner(
                graph, self.mesh, axis=config.mesh_axis,
                feed_partition=config.feed_partition, chunk=config.chunk,
                backend=config.backend, exec_cache=self.exec_cache,
                telemetry=self.telemetry)
            # the runner replicated the CSR buffers across the mesh
            self.graph: CSRGraph = self._runner.g
        else:
            # stage the CSR buffers to device once per session — queries
            # only ever ship scalars and per-chunk vertex ids after this
            self.mesh = None
            self.graph = jax.device_put(graph)
            self.exec_cache = ExecutableCache()
            self._runner = WaveRunner(
                self.graph, chunk=config.chunk, backend=config.backend,
                exec_cache=self.exec_cache, telemetry=self.telemetry)
        # (query, emit, aggregate) -> (plan, its one-plan forest)
        self._plans: dict[tuple, tuple[WavePlan, PlanForest]] = {}
        self._seq = itertools.count()
        self._forests: dict[tuple, PlanForest] = {}
        self.metrics = self.telemetry.metrics
        self._stats = LegacyStatsView()
        self._sct = {k: self._stats.expose_counter(k, self.metrics)
                     for k in self._SESSION_KEYS}

    # ------------------------------------------------------------ compile
    def compile(self, query, emit: bool = False,
                aggregate: str | None = None) -> WavePlan:
        """Stage 1: lower one query to a ``WavePlan`` (cached).

        ``Motif`` queries are scheduled standalone (batch-aware order
        choice happens in ``schedule``); explicit ``Pattern``s and named
        paper patterns keep their declared matching order. ``aggregate``
        compiles the weighted (SVPU value) program — see the module
        docstring's "Value streams" section."""
        return self._compiled(query, emit, aggregate)[0]

    def _compiled(self, query, emit: bool = False,
                  aggregate: str | None = None):
        """(plan, one-plan forest) of one query: ``compile``'s cache entry,
        the forest being what ``run_set`` executes for a single query."""
        tr = self.telemetry.tracer
        with tr.span("compile", query=str(query), emit=emit):
            resolved = resolve_query(query)
            key = (resolved, emit, aggregate)
            hit = self._plans.get(key)
            if hit is not None:
                self._sct["plan_hits"].inc()
                return hit
            self._sct["plan_misses"].inc()
            if isinstance(resolved, Motif):
                resolved = schedule_patterns([resolved])[0]
            plan = compile_pattern(resolved, emit=emit, aggregate=aggregate)
            hit = self._plans[key] = (plan, build_forest([plan]))
            return hit

    def _run_one(self, query, emit: bool = False,
                 aggregate: str | None = None):
        """Execute one query: its cached one-plan forest through
        ``run_set``."""
        return self._runner.run_set(
            self._compiled(query, emit, aggregate)[1])[0]

    # ----------------------------------------------------------- schedule
    def schedule(self, queries: Sequence, emit: bool = False,
                 aggregate: str | None = None) -> PlanForest:
        """Stage 2: batch matching-order search + forest merge (cached).

        Returns the ``PlanForest`` for the batch: ``Motif`` members get
        their order from the shared-prefix search (jointly, with any
        explicit ``Pattern`` members as fixed context), and the compiled
        plans merge into one prefix trie. Cached on the resolved batch, so
        repeated and permuted-config queries skip both the search and the
        merge."""
        tr = self.telemetry.tracer
        with tr.span("schedule", queries=len(queries), emit=emit):
            resolved = tuple(resolve_query(q) for q in queries)
            key = (resolved, emit, aggregate)
            forest = self._forests.get(key)
            if forest is not None:
                self._sct["schedule_hits"].inc()
                return forest
            self._sct["schedule_misses"].inc()
            # Motifs are searched jointly; Pattern members are fixed points
            # of the search but still shape its score (they sit in the
            # trial trie). The order search ignores the value disposition —
            # agg plans share the unweighted plans' stream structure.
            pats = schedule_patterns(resolved)
            plans = []
            for r, p in zip(resolved, pats):
                plan = compile_pattern(p, emit=emit, aggregate=aggregate)
                if (r, emit, aggregate) not in self._plans:
                    self._plans[(r, emit, aggregate)] = \
                        (plan, build_forest([plan]))
                plans.append(plan)
            forest = build_forest(plans)
            self._forests[key] = forest
            return forest

    # ------------------------------------------------------------ execute
    def _query_span(self, kind: str, **attrs):
        """Root span of one query; ``seq`` numbers this session's queries,
        so every span of one query shares an identifier."""
        return self.telemetry.tracer.span("query", kind=kind,
                                          seq=next(self._seq), **attrs)

    def count(self, query) -> int:
        """Count embeddings of one pattern query."""
        self._sct["queries"].inc()
        with self._query_span("count", query=str(query)):
            return self._run_one(query)

    def count_many(self, queries: Sequence) -> list[int]:
        """Count a batch of pattern queries in one fused forest pass.

        Results are positional and bit-identical to per-query ``count``
        calls on the same scheduled patterns."""
        self._sct["queries"].inc()
        with self._query_span("count_many", queries=len(queries)):
            return self._runner.run_set(self.schedule(queries))

    def _require_values(self) -> None:
        if self.graph.edge_values is None:
            raise ValueError(
                "aggregate queries need a weighted graph — build with "
                "edge_values (graph.build_csr(..., edge_values=...) or "
                "graph.with_edge_values)")

    def aggregate(self, query, op: str = "sum") -> float:
        """Reduce embedding values of one query with ``op`` ('sum' / 'max' /
        'min'); an embedding's value is the product of its pattern-edge
        weights. See the module docstring's "Value streams" section."""
        self._require_values()
        self._sct["queries"].inc()
        with self._query_span("aggregate", query=str(query), op=op):
            return self._run_one(query, aggregate=op)

    def aggregate_many(self, queries: Sequence, op: str = "sum") -> list:
        """Aggregate a batch of queries in one fused forest pass (same
        sharing as ``count_many``: aggregate leaves ride the shared
        expands, results positional)."""
        self._require_values()
        self._sct["queries"].inc()
        with self._query_span("aggregate_many", queries=len(queries), op=op):
            return self._runner.run_set(self.schedule(queries, aggregate=op))

    def embeddings(self, query) -> np.ndarray:
        """Enumerate embeddings of one query as an (N, k) int32 matrix."""
        self._sct["queries"].inc()
        with self._query_span("embeddings", query=str(query)):
            return self._run_one(query, emit=True)

    def run_plans(self, plans: Sequence[WavePlan]) -> list:
        """Execute pre-compiled plans (FSM's feed, power users) as one
        cached forest."""
        self._sct["queries"].inc()
        plans = list(plans)
        with self._query_span("run_plans", plans=len(plans)):
            key = ("plans", tuple(p.canonical_key() for p in plans))
            forest = self._forests.get(key)
            if forest is None:
                self._sct["schedule_misses"].inc()
                forest = self._forests[key] = build_forest(plans)
            else:
                self._sct["schedule_hits"].inc()
            return self._runner.run_set(forest)

    # -------------------------------------------------------------- stats
    @property
    def runner(self) -> WaveRunner:
        """The session's execute-stage interpreter (stats, level_execs)."""
        return self._runner

    @property
    def stats(self) -> dict:
        """Session counters: pipeline-stage cache hits/misses, the
        executable cache (``exec_cache.misses`` == retraces), and the
        runner's dispatch/sync counters. Every scalar here is derived
        from ``self.metrics`` (legacy view — identical keys and values to
        the dicts this property historically assembled)."""
        # mirror the executable cache into gauges at snapshot time, so a
        # registry export (prometheus/trace) carries the retrace counters
        cache = self.exec_cache.snapshot()
        for k, v in cache.items():
            self.metrics.gauge(f"exec_cache_{k}").set(v)
        return {
            **self._stats,
            "mesh": mesh_signature(self.mesh),
            "exec_cache": cache,
            "retraces": self.exec_cache.misses,
            "runner": dict(self._runner.stats),
        }
