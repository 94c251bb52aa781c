"""Wavefront pattern-enumeration engine: device-resident, host-orchestrated.

The paper's execution model is a core issuing stream instructions whose
operands live in the S-Cache. The TPU translation keeps the *dataflow* —
(prefix stream) x (neighbor list) bounded intersections — but replaces the
instruction stream with level-synchronous waves driven by a compiled
``mining.plan.WavePlan`` (the §IV-F translator, run ahead of time):

  level 1: the edge list (half edges v1 < v0 when the plan's restrictions
           break that symmetry, straight from the CSR offset register)
  level l: for each surviving work item, the plan's LevelOp masks one base
           stream by the INTER/SUB/bound/injectivity refs it declares
           (the clique special case is S_l = S_{l-1} ∩ N(v) ∩ [0, v))

Between levels the surviving (prefix, vertex) work items are compacted into
a dense worklist (the translation buffer of §IV-F), and the prefix capacity
is re-derived from the actual max survivor length — the paper's Fig. 14
observation (clique streams are short) becomes an adaptive buffer size.

``WaveRunner`` compacts the expand's match mask on-device (segmented
prefix-sum scatter, ``ops.xinter_compact`` / ``ops.xlevel_compact``) into
the next wave's (rows, verts) buffers; only the level-boundary scalars
(total, max count, max degree) ever cross to the host. Executables are
cached per (cap_a, cap_b, chunk) so degree-bucketed shapes never retrace,
and the level-1 edge feed is double-buffered (chunk N+1 uploads while
chunk N computes) — the S-Cache residency win, restated as "operands
never leave HBM". The numpy ``compact`` (``np.nonzero``) is the oracle
the kernel-level compaction tests check against; no runner uses it.

Work is chunked so device buffers stay bounded; padded tail items carry
bound=0 so they contribute nothing (branch-free masking, no special cases).
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.batch import (batch_inter, batch_inter_count,
                              compact_indices_scan)
from repro.obs import LegacyStatsView, Telemetry
from repro.core.stream import LANE, SENTINEL, round_capacity
from repro.graph.csr import CSRGraph, padded_rows, padded_value_rows
from repro.kernels.ops import (xinter_compact, xinter_count, xlevel_agg,
                               xlevel_compact, xlevel_count, xsub_compact,
                               xsub_count)
from repro.values import edge_value_lookup, prefix_scale
from .forest import build_forest
from .plan import LevelOp, WavePlan, clique_pattern, compile_pattern, pattern


def half_edges(g: CSRGraph) -> np.ndarray:
    """(E/2, 2) array of (v0, v1) with v1 < v0 — the symmetric-breaking edge
    frontier, read directly via the CSR offset register (offsets[v0] = number
    of neighbors < v0)."""
    indptr = np.asarray(g.indptr)
    offsets = np.asarray(g.offsets)
    indices = np.asarray(g.indices)
    counts = offsets.astype(np.int64)
    v0 = np.repeat(np.arange(g.num_vertices, dtype=np.int32), counts)
    # position of each kept slot within its row
    pos = np.arange(counts.sum(), dtype=np.int64) - np.repeat(
        np.concatenate([[0], np.cumsum(counts)[:-1]]), counts)
    v1 = indices[indptr[v0].astype(np.int64) + pos]
    return np.stack([v0, v1], axis=1)


def directed_edges(g: CSRGraph) -> np.ndarray:
    """(E, 2) all directed edges (v0, v1) in CSR order."""
    indptr = np.asarray(g.indptr).astype(np.int64)
    v0 = np.repeat(np.arange(g.num_vertices, dtype=np.int32), np.diff(indptr))
    v1 = np.asarray(g.indices)[: g.num_edges]
    return np.stack([v0, v1], axis=1)


def _pad_to(x: np.ndarray, n: int, fill) -> np.ndarray:
    if x.shape[0] == n:
        return x
    pad = np.full((n - x.shape[0],) + x.shape[1:], fill, dtype=x.dtype)
    return np.concatenate([x, pad], axis=0)


@dataclasses.dataclass
class Wave:
    """A compacted frontier: prefix rows + the vertex that extends each."""

    rows: np.ndarray    # (N, cap) int32 sorted sentinel-padded prefix streams
    verts: np.ndarray   # (N,) int32 extension vertex (also the bound)

    def __len__(self) -> int:
        return int(self.verts.shape[0])


def _pow2cap(n: int) -> int:
    """Smallest power-of-two LANE multiple >= n (degree bucket capacity)."""
    c = LANE
    while c < n:
        c *= 2
    return c


def _class_of_degree(deg: np.ndarray) -> np.ndarray:
    """Per-degree capacity class as log2(cap / LANE), where cap is
    ``_pow2cap(max(d, 1))``: one lookup table, indexed by degree, so a
    feed classifies every edge end without a Python call per edge."""
    top = int(deg.max()) if deg.size else 0
    base = LANE.bit_length()
    return np.array([_pow2cap(max(d, 1)).bit_length() - base
                     for d in range(top + 1)], dtype=np.uint16)


def edge_buckets(g: CSRGraph, symmetric: bool = True):
    """Host bucketing of one level-1 feed pass: [(cap, edges), ...], the
    (E_b, 2) edges whose prefix vertex v0 falls in degree bucket ``cap``,
    in ascending ``cap`` order. Within a bucket the edges are ordered by
    the capacity class of v1, ascending (stable: CSR order among equals),
    so consecutive edges share an N(v1) class and a chunk cut from the
    bucket gathers N(v1) at its own edges' class, not at the widest v1 of
    the bucket. Runs eagerly, so a caller can time it."""
    edges = half_edges(g) if symmetric else directed_edges(g)
    if edges.shape[0] == 0:
        return []
    deg = np.asarray(g.degrees)
    cls = _class_of_degree(deg)
    k0, k1 = cls[deg[edges[:, 0]]], cls[deg[edges[:, 1]]]
    # one stable sort on the (class v0, class v1) pair: a small-integer key
    order = np.argsort((k0 << 8) | k1, kind="stable")
    edges, k0 = edges[order], k0[order]
    cuts = np.flatnonzero(np.diff(k0)) + 1
    return [(LANE << int(k0[lo]), edges[lo:hi]) for lo, hi in
            zip(np.r_[0, cuts], np.r_[cuts, edges.shape[0]])]


def feed_cuts(buckets, chunk: int, shards: int = 1):
    """The steps of one level-1 feed pass over ``edge_buckets``, as
    (cap, block, nb): ``block`` is the bucket's next ``shards * nb`` edges
    (fewer at the bucket's end) and ``nb`` the width of one shard's part,
    ``min(chunk, pow2cap(ceil(E / shards)))`` for a bucket of E edges: one
    compiled shape per degree bucket. A block is a view; padding it
    (``chunk_of``) or dealing it over shards (``shard.deal``) is the
    step's own work."""
    for cap, sel in buckets:
        e = sel.shape[0]
        nb = min(chunk, _pow2cap(max(-(-e // shards), 1)))
        for lo in range(0, e, shards * nb):
            yield cap, sel[lo: lo + shards * nb], nb


def chunk_of(block: np.ndarray, nb: int):
    """(v0, v1, n): one feed chunk's int32 vertex columns, padded to ``nb``
    with vertex 0, and its live count."""
    return (_pad_to(block[:, 0].astype(np.int32), nb, 0),
            _pad_to(block[:, 1].astype(np.int32), nb, 0), block.shape[0])


def bucket_chunks(buckets, chunk: int):
    """Slice ``edge_buckets`` into (cap, v0, v1, n) chunk-padded int32
    vertex arrays *without* materialising neighbor rows — row gathers
    happen on-device so the feed can be double-buffered. With the bucket
    in v1-class order, the live v1 of a chunk share one class except in
    the few chunks that straddle a class change."""
    for cap, block, nb in feed_cuts(buckets, chunk):
        yield (cap, *chunk_of(block, nb))


def edge_chunks(g: CSRGraph, chunk: int, symmetric: bool = True):
    """Host half of the level-1 feed: ``edge_buckets`` then
    ``bucket_chunks``; yields (cap, v0, v1, n)."""
    return bucket_chunks(edge_buckets(g, symmetric), chunk)


def edge_wave(g: CSRGraph, chunk: int, symmetric: bool = True):
    """Yield level-1 waves: (v0 rows are N(v0), vert = v1), bucketed by the
    prefix vertex's degree so per-edge work is O(bucket) not O(max degree)
    (<= 2x padding waste — the paper's Fig. 14 stream-length skew exploited
    as static capacity classes; EXPERIMENTS.md §Perf mining iteration).
    Each bucket runs in v1-class order (``edge_buckets``), so the v1 of a
    wave mostly share one capacity class."""
    for cap, v0, v1, n in edge_chunks(g, chunk, symmetric):
        rows, _ = padded_rows(g, jnp.asarray(v0), cap)
        yield Wave(rows=rows, verts=v1), n


def _neighbor_cap(g: CSRGraph, verts: np.ndarray) -> int:
    deg = np.asarray(g.degrees)
    mx = int(deg[np.asarray(verts)].max()) if len(verts) else 1
    return _pow2cap(max(mx, 1))


def expand_count(g: CSRGraph, wave: Wave, bounded: bool = True) -> jnp.ndarray:
    """counts[i] = |rows_i ∩ N(verts_i) ∩ [0, verts_i)| (bound dropped when
    ``bounded`` is False). Neighbor capacity = the chunk's degree bucket."""
    capn = _neighbor_cap(g, wave.verts)
    nbr, _ = padded_rows(g, jnp.asarray(wave.verts), capn)
    bounds = jnp.asarray(wave.verts) if bounded else None
    return batch_inter_count(jnp.asarray(wave.rows), nbr, bounds)


def expand(g: CSRGraph, wave: Wave, out_cap: int | None = None):
    """Materialise S_l rows: (rows (N, out_cap), counts (N,))."""
    capn = _neighbor_cap(g, wave.verts)
    rows_a = jnp.asarray(wave.rows)
    cap = out_cap or min(rows_a.shape[1], capn)
    nbr, _ = padded_rows(g, jnp.asarray(wave.verts), capn)
    rows, counts = batch_inter(rows_a, nbr,
                               jnp.asarray(wave.verts), out_cap=cap)
    return np.asarray(rows), np.asarray(counts)


def compact(rows: np.ndarray, counts: np.ndarray, limit: int | None = None,
            return_src: bool = False):
    """Host compaction oracle: expand (rows, counts) into the next Wave.

    The device compaction (``ops.xinter_compact``, which ``WaveRunner``
    runs) is tested item for item against this np.nonzero form; it is also
    the ``return_src`` provider of ``apps.triangle_list_host``.

    Every valid key rows[i, j] (j < counts[i]) becomes a work item whose
    prefix is rows[i] and whose extension vertex/bound is that key. The
    prefix capacity shrinks to the padded max survivor length (adaptive
    stream capacity — clique streams are short, paper Fig. 14).
    ``return_src`` additionally yields the source row index of each item
    (needed when the caller must recover the enclosing prefix vertices).
    """
    counts = counts[: limit] if limit is not None else counts
    rows = rows[: counts.shape[0]]
    maxc = int(counts.max()) if counts.size else 0
    if maxc == 0:
        return (None, None) if return_src else None
    cap = round_capacity(maxc)
    col = np.arange(rows.shape[1])
    ii, jj = np.nonzero(col[None, :] < counts[:, None])
    verts = rows[ii, jj].astype(np.int32)
    wave = Wave(rows=rows[ii, :cap], verts=verts)
    return (wave, ii) if return_src else wave


def pair_chunks(g: CSRGraph, edges: np.ndarray, chunk: int):
    """Host half of the pair feed: yields (cap_a, cap_b, v0, v1, n) without
    materialising rows (device gathers, double-bufferable)."""
    if edges.shape[0] == 0:
        return
    deg = np.asarray(g.degrees)
    cls = _class_of_degree(deg).astype(np.int64)
    cap_a = LANE << cls[deg[edges[:, 0]]]
    cap_b = LANE << cls[deg[edges[:, 1]]]
    keys = cap_a << 32 | cap_b
    for key in np.unique(keys):
        ca, cb = int(key >> 32), int(key & 0xFFFFFFFF)
        sel = edges[keys == key]
        nb = min(chunk, _pow2cap(sel.shape[0]))
        for lo in range(0, sel.shape[0], nb):
            sl = sel[lo: lo + nb]
            n = sl.shape[0]
            v0 = _pad_to(sl[:, 0].astype(np.int32), nb, 0)
            v1 = _pad_to(sl[:, 1].astype(np.int32), nb, 0)
            yield ca, cb, v0, v1, n


def pair_wave(g: CSRGraph, edges: np.ndarray, chunk: int):
    """Yield degree-bucketed padded row pairs for an (N, 2) vertex-pair list:
    (rows_a, rows_b, v0, v1, n_valid). Used by apps that intersect/subtract
    two neighbor lists per edge (TT, induced TC)."""
    for ca, cb, v0, v1, n in pair_chunks(g, edges, chunk):
        rows_a, _ = padded_rows(g, jnp.asarray(v0), ca)
        rows_b, _ = padded_rows(g, jnp.asarray(v1), cb)
        yield rows_a, rows_b, v0, v1, n


def wave_chunks(wave: Wave, chunk: int):
    """Split a host wave into padded device chunks; yields (Wave, n_valid).

    Padding uses vertex 0 with bound 0 => zero contribution."""
    n = len(wave)
    for lo in range(0, max(n, 1), chunk):
        r = wave.rows[lo: lo + chunk]
        v = wave.verts[lo: lo + chunk]
        if r.shape[0] == 0:
            continue
        k = r.shape[0]
        yield Wave(rows=_pad_to(r, chunk, SENTINEL), verts=_pad_to(v, chunk, 0)), k


# ---------------------------------------------------------------------------
# count accumulator: a leaf's running count as three int32 limbs
# ---------------------------------------------------------------------------

COUNT_LIMBS = 3


def count_acc_add(acc, w0, w16, w32):
    """``acc`` (int32 limbs of weights 2^0, 2^16, 2^32) plus a partial
    given as nonnegative int32 sums at those weights, carry-propagated so
    the two low limbs stay below 2^16. Exact while every sum stays below
    2^31: each weight sum below 2^30 (a chunk's (hi, lo) pair, or a psum'd
    limb quad over fewer than 2^13 shards) and a running total below 2^63,
    whose top limb is floor(total / 2^32) < 2^31. No limb ever wraps, so
    an accumulator needs no host read before the query's end."""
    t0 = acc[0] + w0
    t1 = acc[1] + w16 + (t0 >> 16)
    t2 = acc[2] + w32 + (t1 >> 16)
    return jnp.stack([t0 & 0xFFFF, t1 & 0xFFFF, t2])


def count_acc_value(limbs) -> int:
    """The exact count an accumulator's limbs hold, as a Python int."""
    a0, a1, a2 = (int(x) for x in limbs)
    return (a2 << 32) + (a1 << 16) + a0


DEFAULT_CHUNK = 4096


def choose_chunk(cap: int, budget_bytes: int = 64 << 20) -> int:
    """Chunk size so one wave's buffers stay within ``budget_bytes``."""
    per_row = cap * 4 * 4  # rows + neighbor rows + output + slack
    c = max(LANE, budget_bytes // max(per_row, 1))
    return int(min(DEFAULT_CHUNK * 4, (c // LANE) * LANE))


# ---------------------------------------------------------------------------
# WaveRunner — the stream-program interpreter over the device wave pipeline
# ---------------------------------------------------------------------------

# count_edges back-compat surface: the four (symmetric, bounded) triangle
# stream shapes as one-level plans
_EDGE_COUNT_PATTERNS = {
    (True, True): pattern("edges-sym-bounded", 3, [(0, 1), (0, 2), (1, 2)],
                          restrictions=[(1, 0), (2, 1)]),
    (True, False): pattern("edges-sym", 3, [(0, 1), (0, 2), (1, 2)],
                           restrictions=[(1, 0)]),
    (False, True): pattern("edges-bounded", 3, [(0, 1), (0, 2), (1, 2)],
                           restrictions=[(2, 1)]),
    (False, False): pattern("edges", 3, [(0, 1), (0, 2), (1, 2)]),
}


class WaveRunner:
    """Stream-program interpreter: executes any compiled ``WavePlan`` on the
    device-resident wavefront pipeline.

    ``run_set(forest)`` is the interpreter — the §IV-F translator's
    software half; ``run(plan)`` runs one plan as a one-plan forest
    (``build_forest([plan])`` walks exactly ``plan.ops``, so it is the same
    program). Each ``LevelOp`` lowers to one cached jitted executable
    that gathers the neighbor streams it references, AND-combines their
    membership marks (INTER) / complements (SUB) over the base stream, applies
    bound and injectivity masks, and either counts, materialises + compacts
    (``ops.xinter_compact`` / ``ops.xsub_compact`` fused fast paths when the
    level is a single bounded stream op), or emits embeddings. Per-pattern
    engine methods are gone: ``clique``/``count_edges``/... below are thin
    plan wrappers kept for the benchmark/test surface.

    Mechanisms shared by every plan:

    * **executable cache** keyed by (kind, LevelOp, capacities, chunk):
      LevelOps hash by value, so recompiling a pattern — or two patterns
      sharing a level shape — reuses traces (``stats['exec_hits']``);
    * **fused expand_compact**: survivors are compacted on device; the only
      per-level host traffic is the meta sync (total, max survivor count,
      max degree per forwarded column) that sizes the next level's static
      capacities;
    * **prefix-column forwarding**: the compiler's liveness fields
      (``out_cols``/``gather_refs``) tell the interpreter which matched
      vertices deeper levels reference; columns are gathered through the
      compacted ``src`` indices on device, never round-tripping to host;
    * **double-buffered feeds**: level-1 edge chunks upload one ahead of
      compute;
    * **device count accumulators**: a count leaf reduces each chunk to
      an exact partial and adds it, in the same executable, to one int32
      limb accumulator per leaf node (``count_acc_add``; exact for any
      total below 2^63, never flushed), threaded through every dispatch
      of the query. ``run_set`` reads every accumulator, and any
      aggregate pair, in ONE ``jax.device_get`` at its end — no count
      vector and no per-chunk partial ever crosses to the host.

    A ``mining.forest.PlanForest`` holds one or several plans: one
    edge-feed pass per orientation, each shared trie node's expand +
    compaction dispatched once per wave chunk and fanned out to every child
    branch (children whose branch deferred constraints into residuals first
    get a per-branch packed worklist, so relaxation never inflates their
    downstream item count), with per-leaf accumulators — results
    bit-identical to per-plan ``run`` calls.

    General (multi-operand) levels — several INTER/SUB refs, or injectivity
    excludes — dispatch ONE fused k-operand kernel per executable call
    (``ops.xlevel_count`` / ``ops.xlevel_compact``: the refs are stacked
    into a (k, B, cap) operand, polarity INTER-first, window/excludes folded
    in-kernel); the executable cache is keyed on the per-ref capacity
    signature, so a k-operand level's trace is reused across degree buckets
    exactly like the single-op ones. Compaction everywhere is the O(B·cap)
    segmented prefix-sum scatter, never a masked sort.

    Every executable is built in two halves: an unjitted *body*
    (``_count_body`` / ``_expand_body`` / ``_emit_body`` / ``_chunk_body`` /
    ``_rpack_body``) holding the traced computation, and a ``_jit_*`` hook
    that wraps it for dispatch (plain ``jax.jit`` here). The mesh-sharded
    runner (``mining.shard.ShardedWaveRunner``) overrides only the hooks —
    wrapping each body in ``shard_map`` with a ``psum`` leaf reduction —
    plus the feed/meta plumbing, so both runners interpret plans through
    the exact same per-level semantics. The bodies are written to accept
    the live count ``n`` as either a scalar (this runner) or a shape-(1,)
    per-shard slice (broadcast against ``jnp.arange`` either way).
    """

    # data-parallel width of the wave arrays: every (items,) buffer holds
    # ``_shards`` per-shard blocks back to back; 1 here (single device),
    # the mesh size on ShardedWaveRunner (which also divides the host-side
    # batch arithmetic below by it).
    _shards: int = 1
    # where a feed step's arrays go: the default device here, split over
    # the mining axis on ShardedWaveRunner
    _feed_sharding = None
    # prepended to every executable-cache key so sharded (shard_map-wrapped)
    # traces can never collide with unsharded traces of the same LevelOp
    _exec_prefix: tuple = ()
    # where a count accumulator lives: the default device here, replicated
    # over the mesh on ShardedWaveRunner
    _acc_sharding = None

    # legacy ``stats`` keys, in their historical insertion order — each is
    # a registry counter the view derives from (see __init__)
    _STAT_KEYS = ("exec_hits", "exec_misses", "host_syncs",
                  "device_compactions", "items",
                  "level_kernel_dispatches", "count_rides")

    def __init__(self, g: CSRGraph, chunk: int | None = None,
                 backend: str = "auto", exec_cache=None,
                 telemetry: Telemetry | None = None):
        self.g = g
        # chunk <= 2^15 is the exactness envelope of the (hi, lo) int32
        # per-chunk count partials (see _plan_count_fn): a 2^15-item chunk of
        # 16-bit low words sums below 2^31. choose_chunk already stays under
        # it; explicit larger requests are clamped, never silently wrapped.
        self.chunk = min(chunk or choose_chunk(g.padded_max_degree), 1 << 15)
        self.backend = backend
        # session-lifetime executable cache (mining.session.ExecutableCache):
        # when provided, compiled executables outlive this runner — repeated
        # queries on one Miner retrace nothing. Keys are widened with the
        # runner config (chunk / backend) so runners with different shapes
        # never collide; None keeps the private per-runner dict.
        self._exec_cache = exec_cache
        self._exec: dict[tuple, Callable] = {}
        # telemetry substrate (repro.obs): the metrics registry is the
        # single source of truth for every counter, and ``self.stats`` is
        # the legacy dict DERIVED from it (bit-identical view, golden-
        # tested). Spans are profiler annotations always and a span tree
        # only when the session enables the tracer; neither synchronises.
        self.telemetry = telemetry if telemetry is not None else Telemetry()
        self.metrics = self.telemetry.metrics
        self.stats = LegacyStatsView()
        self._ct = {k: self.stats.expose_counter(k, self.metrics)
                    for k in self._STAT_KEYS}
        # registry-only extras (not part of the legacy view)
        self._ct_feed_chunks = self.metrics.counter("feed_chunks")
        # level-1 gather fill: slots the feed's row gathers move (chunk
        # width x row capacities) and the neighbor keys among them
        self._ct_row_slots = self.metrics.counter("feed_row_slots")
        self._ct_row_keys = self.metrics.counter("feed_row_keys")
        # SVPU value plane: aggregate-leaf executions (each rides an
        # existing membership dispatch — value_lane_dispatches counts leaves
        # whose dispatch carried a value lane, NOT extra kernel launches)
        self._ct_value_lanes = self.metrics.counter("value_lane_dispatches")
        self._exec_fresh = False
        # per-(kind, level) executable dispatch counts — the fusion metric:
        # a PlanForest run dispatches each shared level once where the
        # independent-plan path dispatches it once per pattern.
        self.level_execs: dict[tuple[str, int], int] = {}

    @staticmethod
    def _level_dispatches(op: LevelOp) -> int:
        """Membership-kernel dispatches one executable call issues for
        ``op``: one fused kernel covers every INTER/SUB ref of a level, and
        window-only levels need none."""
        return 1 if op.inter or op.sub else 0

    def _bump(self, op: LevelOp) -> None:
        key = (op.kind, op.level)
        self.level_execs[key] = self.level_execs.get(key, 0) + 1
        self._ct["level_kernel_dispatches"].inc(self._level_dispatches(op))

    # ------------------------------------------------------------------ cache
    def _executable(self, key: tuple, build: Callable) -> Callable:
        key = self._exec_prefix + key
        if self._exec_cache is not None:
            key = (self.chunk, self.backend) + key
            fn, fresh = self._exec_cache.get_or_build(key, build)
            self._exec_fresh = fresh
            self._ct["exec_misses" if fresh else "exec_hits"].inc()
            return fn
        fn = self._exec.get(key)
        if fn is None:
            fn = self._exec[key] = build()
            self._exec_fresh = True
            self._ct["exec_misses"].inc()
        else:
            self._exec_fresh = False
            self._ct["exec_hits"].inc()
        return fn

    # -------------------------------------------------------- traced dispatch
    def _dispatch(self, op: LevelOp, fn: Callable, args: tuple,
                  items=None, caps_sig: tuple = ()):
        """Run one level executable inside a ``dispatch`` span (op
        kind/level, wavefront items, capacity signature, exec-cache
        hit/miss). The span times the host's enqueue — trace and compile
        included when the executable is fresh — and never waits for the
        device: device time is the profiler's to report."""
        attrs = {"kind": op.kind, "level": op.level,
                 "dispatches": self._level_dispatches(op),
                 "exec_cached": not self._exec_fresh}
        if op.agg is not None:
            attrs["agg"] = op.agg
        if items is not None:
            attrs["items"] = lambda: int(np.asarray(items).sum())
        if caps_sig:
            attrs["caps"] = lambda: str(tuple(caps_sig))
        with self.telemetry.tracer.span("dispatch", cat="dispatch", **attrs):
            return fn(*args)

    def _level_span(self, op: LevelOp, n):
        """Level span for one op's processing on one wave chunk (children
        levels nest inside)."""
        return self.telemetry.tracer.span(
            f"L{op.level}:{op.kind}", cat="level", level=op.level,
            kind=op.kind, items=lambda: int(np.asarray(n).sum()))

    def _sync_span(self, site: str):
        """Span around one blocking device->host read at ``site``."""
        return self.telemetry.tracer.span("sync", cat="sync", site=site)

    # ------------------------------------------------------------------ feeds
    @staticmethod
    def _double_buffered(steps):
        """Run one step ahead of the consumer: step N+1 is built and its
        upload dispatched (async) while the consumer computes on step N."""
        pending = None
        for step in steps:
            if pending is not None:
                yield pending
            pending = step
        if pending is not None:
            yield pending

    def _edge_feed(self, symmetric: bool = True):
        """Double-buffered level-1 feed: (cap, dv0, dv1, v1_host, n). The
        bucketing runs here, eagerly, inside a ``feed_bucket`` span; each
        step's padding (or dealing), fill count and upload run inside a
        ``feed_step`` span, closed before the step is handed on, so it
        never spans a dispatch."""
        tr = self.telemetry.tracer
        with tr.span("feed_bucket", cat="host", symmetric=symmetric):
            buckets = edge_buckets(self.g, symmetric)

        def steps():
            for cap, block, nb in feed_cuts(buckets, self.chunk,
                                            self._shards):
                with tr.span("feed_step", cat="host"):
                    v0, v1, n = self._cut(block, nb)
                    self._count_feed_fill(cap, v0, n)
                    step = (cap, jax.device_put(v0, self._feed_sharding),
                            jax.device_put(v1, self._feed_sharding), v1, n)
                yield step
        return self._double_buffered(steps())

    def _cut(self, block: np.ndarray, nb: int):
        """(v0, v1, n) of one feed step: the block padded to ``nb``."""
        return chunk_of(block, nb)

    def _live(self, verts, n):
        """The live entries of a feed chunk's vertex column: ``n`` is the
        live count, or the per-shard vector when the chunk holds one block
        per shard. A chunk's N(v1) capacity is taken over these alone, as
        an expand's meta takes the next level's over live items: every
        level masks dead rows by ``n``, so their gather may truncate."""
        live = (np.arange(verts.shape[0] // self._shards)
                < np.reshape(n, (-1, 1))).reshape(-1)
        return verts[live]

    def _count_feed_fill(self, cap: int, verts, n) -> None:
        """Credit the level-1 gather of one feed chunk's rows of ``verts``
        at capacity ``cap`` — N(v0) for every chunk, N(v1) where the level
        gathers it — to ``feed_row_slots`` (chunk width x cap) and
        ``feed_row_keys`` (the neighbor keys of the live edges among those
        slots)."""
        deg = np.asarray(self.g.degrees)
        self._ct_row_slots.inc(verts.shape[0] * cap)
        self._ct_row_keys.inc(
            int(np.minimum(deg[self._live(verts, n)], cap).sum()))

    # ------------------------------------------------------------- plan parts
    @staticmethod
    def _in_cols(op: LevelOp) -> tuple[int, ...]:
        """Prefix columns whose *values* the level executable consumes."""
        cols = set(op.val_refs()) | {c for c in op.gather_refs
                                     if c < op.level}
        if op.kind == "emit":
            cols |= {c for c in op.out_cols if c < op.level}
        return tuple(sorted(cols))

    @staticmethod
    def _fused_shape(op: LevelOp) -> str | None:
        """'inter'/'sub' when one fused bounded kernel covers the level.

        Lower bounds ride the kernels' lbounds operand (whole-tile skipping,
        like the R3 upper bound); residuals and the live mask fold into the
        per-row bound (bound 0 = dead row). Per-element injectivity
        (``exclude``) and several refs take the k-operand kernel."""
        if op.exclude:
            return None
        if len(op.inter) == 1 and not op.sub:
            return "inter"
        if len(op.sub) == 1 and not op.inter:
            return "sub"
        return None

    @staticmethod
    def _stack_refs(g, get, caps: dict, refs: tuple[int, ...]):
        """Gather the k reference neighbor streams and stack them into the
        fused kernel's (k, B, cap) operand; refs gathered at smaller degree
        buckets are SENTINEL-padded to the widest (padding keeps each row
        sorted, so every ref's tile schedule stays valid)."""
        capmax = max(caps[j] for j in refs)
        rows = []
        for j in refs:
            r, _ = padded_rows(g, get[j], caps[j])
            if caps[j] < capmax:
                r = jnp.pad(r, ((0, 0), (0, capmax - caps[j])),
                            constant_values=SENTINEL)
            rows.append(r)
        return jnp.stack(rows)

    @staticmethod
    def _stack_val_refs(g, get, caps: dict, refs: tuple[int, ...]):
        """Value twin of ``_stack_refs``: (k, B, cap) f32 stack aligned with
        the key stack, 0.0 where keys are SENTINEL padding (the pad columns
        never match, so their value is irrelevant but must exist)."""
        capmax = max(caps[j] for j in refs)
        rows = []
        for j in refs:
            v = padded_value_rows(g, get[j], caps[j])
            if caps[j] < capmax:
                v = jnp.pad(v, ((0, 0), (0, capmax - caps[j])))
            rows.append(v)
        return jnp.stack(rows)

    @staticmethod
    def _excl_vals(op: LevelOp, get):
        """Per-row injectivity values for the fused kernels' excludes
        operand (None when the level declares none)."""
        if not op.exclude:
            return None
        return jnp.stack([get[e] for e in op.exclude], axis=1)

    @staticmethod
    def _min_ub(op: LevelOp, get):
        ub = get[op.ub[0]]
        for u in op.ub[1:]:
            ub = jnp.minimum(ub, get[u])
        return ub

    @staticmethod
    def _max_lb(op: LevelOp, get):
        lb = get[op.lb[0]]
        for w in op.lb[1:]:
            lb = jnp.maximum(lb, get[w])
        return lb

    def _ub_vec(self, op: LevelOp, get, n, nrows: int):
        """Per-row effective upper bound for the fused kernels: min over the
        ``ub`` columns (SENTINEL when unbounded), then zeroed for padding
        rows and residual-failing items — bound 0 kills the whole row inside
        the tile schedule, so deferred constraints cost no B-tile DMA."""
        if op.ub:
            ub = self._min_ub(op, get)
        else:
            ub = jnp.full((nrows,), SENTINEL, jnp.int32)
        ok = jnp.arange(nrows, dtype=jnp.int32) < n
        for kind, i, j in op.residual:
            ok = ok & ((get[i] < get[j]) if kind == "lt"
                       else (get[i] != get[j]))
        return jnp.where(ok, ub, 0)

    def _plan_count_fn(self, op: LevelOp, caps_sig: tuple, cap_base: int):
        """Terminal count level -> ``(acc, g, vals, carry, n) -> acc``.

        The body reduces the chunk to an exact (hi, lo) int32 pair —
        Σ(count >> 16) and Σ(count & 0xffff); ``_jit_count`` reduces that
        across the mesh on the sharded runner; the same executable then
        adds it to the leaf's accumulator (``count_acc_add``, weights from
        ``_count_weights``). With chunk <= 2^15 neither pair word can wrap
        (lo < 2^31, hi < 2^30), and the accumulator is exact for any total
        below 2^63, so the only remaining envelope is per *item*: a
        tail-folded count (survivors x degree factor) must stay below 2^31,
        which holds whenever maxc * max_degree < 2^31.
        """
        def build():
            part = self._jit_count(
                op, self._count_body(op, caps_sig, cap_base))

            def fn(acc, g, vals, carry, n):
                return count_acc_add(
                    acc, *self._count_weights(part(g, vals, carry, n)))
            return jax.jit(fn)
        return self._executable(("pcount", op, caps_sig, cap_base), build)

    @staticmethod
    def _count_weights(part):
        """(w0, w16, w32) of a chunk's (hi, lo) pair, worth hi·2^16 + lo."""
        hi, lo = part[0], part[1]
        return lo & 0xFFFF, (lo >> 16) + (hi & 0xFFFF), hi >> 16

    def _count_body(self, op: LevelOp, caps_sig: tuple, cap_base: int):
        """Unjitted count-level body (see the two-halves note in the class
        docstring); ``_jit_count`` wraps it for dispatch."""
        backend = self.backend
        in_cols = self._in_cols(op)
        caps = dict(caps_sig)
        fused = self._fused_shape(op)
        refs = op.inter + op.sub
        pol = (1,) * len(op.inter) + (0,) * len(op.sub)

        def fn(g, vals, carry, n):
            get = dict(zip(in_cols, vals))
            base = carry if op.use_carry else \
                padded_rows(g, get[op.base], caps[op.base])[0]
            ub = self._ub_vec(op, get, n, base.shape[0])
            lb = self._max_lb(op, get) if op.lb else None
            if fused:
                ref = op.inter[0] if fused == "inter" else op.sub[0]
                nbr, _ = padded_rows(g, get[ref], caps[ref])
                cfun = xinter_count if fused == "inter" else xsub_count
                counts = cfun(base, nbr, ub, backend=backend, lbounds=lb)
            else:
                bs = self._stack_refs(g, get, caps, refs) if refs \
                    else None
                counts = xlevel_count(base, bs, pol, ub, backend=backend,
                                      lbounds=lb,
                                      excludes=self._excl_vals(op, get))
            if op.tail is not None:
                col, c = op.tail
                counts = counts * (g.degrees[get[col]].astype(jnp.int32)
                                   - c)
            return jnp.stack([jnp.sum(counts >> 16, dtype=jnp.int32),
                              jnp.sum(counts & 0xFFFF, dtype=jnp.int32)])
        return fn

    def _plan_agg_fn(self, op: LevelOp, caps_sig: tuple, cap_base: int):
        """Terminal SVPU aggregate level (``op.agg``): one (value, live)
        f32 pair per chunk, riding the same dispatch budget as the count
        leaf (``xlevel_agg`` shares ``xlevel_count``'s tile schedule)."""
        def build():
            return self._jit_agg(op, self._agg_body(op, caps_sig, cap_base))
        return self._executable(("pagg", op, caps_sig, cap_base), build)

    def _agg_body(self, op: LevelOp, caps_sig: tuple, cap_base: int):
        """Unjitted aggregate-leaf body; ``_jit_agg`` wraps it for dispatch.

        Per kept slot the embedding's value is the product over ALL pattern
        edges of the edge weight, assembled from three sources: prefix-
        prefix edges fold into the per-row ``scale`` (``prefix_scale``),
        candidate-edge weights the kernel's own INTER refs observe ride the
        mask-MAC value lane (``b_vals``), and candidate edges covered at an
        ancestor level (carry reuse / the fresh base's own gather) land in
        ``a_vals`` (value-row gather + ``edge_value_lookup``). The per-chunk
        partial is [op-reduced value, live embedding count] — live gates
        the op identity out at finalize (zero embeddings -> 0.0)."""
        backend = self.backend
        in_cols = self._in_cols(op)
        caps = dict(caps_sig)
        refs = op.inter + op.sub
        pol = (1,) * len(op.inter) + (0,) * len(op.sub)

        def fn(g, vals, carry, n):
            get = dict(zip(in_cols, vals))
            if op.use_carry:
                base = carry
                a_vals = jnp.ones(base.shape, jnp.float32)
            else:
                base = padded_rows(g, get[op.base], caps[op.base])[0]
                a_vals = padded_value_rows(g, get[op.base], caps[op.base])
            for c in op.agg_cand_cols:
                a_vals = a_vals * edge_value_lookup(g, get[c], base)
            scale = prefix_scale(g, get, op.agg_scale_edges) \
                if op.agg_scale_edges \
                else jnp.ones((base.shape[0],), jnp.float32)
            ub = self._ub_vec(op, get, n, base.shape[0])
            lb = self._max_lb(op, get) if op.lb else None
            if refs:
                bs = self._stack_refs(g, get, caps, refs)
                bv = self._stack_val_refs(g, get, caps, refs)
            else:
                bs = bv = None
            counts, rvals = xlevel_agg(
                base, bs, pol, a_vals, bv, scale, op=op.agg, bounds=ub,
                backend=backend, lbounds=lb,
                excludes=self._excl_vals(op, get))
            # dead rows carry the op identity, so the plain row reduce is
            # correct; ``live`` is only read as a zero test at finalize
            if op.agg == "sum":
                value = jnp.sum(rvals, dtype=jnp.float32)
            elif op.agg == "max":
                value = jnp.max(rvals)
            else:
                value = jnp.min(rvals)
            live = jnp.sum(counts, dtype=jnp.int32).astype(jnp.float32)
            return jnp.stack([value, live])
        return fn

    # -------------------------------------------------------- jit hooks
    # Single-device dispatch is a plain jit of each body; the sharded
    # runner overrides these to wrap the same bodies in shard_map (psum
    # reductions for count partials, per-shard meta/total rows otherwise).
    def _jit_count(self, op: LevelOp, body: Callable) -> Callable:
        return jax.jit(body)

    def _jit_agg(self, op: LevelOp, body: Callable) -> Callable:
        return jax.jit(body)

    def _jit_expand(self, op: LevelOp, body: Callable,
                    want_count: bool) -> Callable:
        return jax.jit(body)

    def _jit_emit(self, op: LevelOp, body: Callable) -> Callable:
        return jax.jit(body)

    def _jit_chunk(self, op: LevelOp, body: Callable) -> Callable:
        return jax.jit(body)

    def _jit_rpack(self, body: Callable, nrefs: int) -> Callable:
        return jax.jit(body)

    def _pack_total(self, tot):
        """Host view of a residual-pack live total: (orchestration value,
        any-survivors?). The sharded runner returns the per-shard total
        vector so downstream chunking stays lockstep SPMD."""
        tot = int(tot)
        return tot, bool(tot)

    def _survivor_core(self, op: LevelOp, caps: dict, out_cap: int,
                       out_items: int):
        """Traced core shared by expand/emit: survivors -> compacted items.

        Fast paths: a single INTER/SUB level is one fused
        ``xinter_compact``/``xsub_compact`` dispatch; a general level (k
        INTER/SUB refs, injectivity excludes) is one fused k-operand
        ``xlevel_compact`` dispatch. In both, the per-row bound vector
        (``_ub_vec``) folds the declared upper bounds, the live mask and any
        forest residuals into the bound operand (bound 0 kills dead rows
        inside the kernel) and lower bounds ride ``lbounds``. The epilogue
        is the O(B·cap) prefix-sum scatter (no masked sort anywhere).
        """
        backend = self.backend
        fused = self._fused_shape(op)
        refs = op.inter + op.sub
        pol = (1,) * len(op.inter) + (0,) * len(op.sub)

        def core(g, get, base, n):
            ub = self._ub_vec(op, get, n, base.shape[0])
            lb = self._max_lb(op, get) if op.lb else None
            if fused:
                ref = op.inter[0] if fused == "inter" else op.sub[0]
                nbr, _ = padded_rows(g, get[ref], caps[ref])
                cfun = xinter_compact if fused == "inter" else xsub_compact
                return cfun(base, nbr, ub, out_cap=out_cap,
                            out_items=out_items, backend=backend, lbounds=lb)
            bs = self._stack_refs(g, get, caps, refs) if refs else None
            return xlevel_compact(
                base, bs, pol, ub, out_cap=out_cap, out_items=out_items,
                backend=backend, lbounds=lb,
                excludes=self._excl_vals(op, get))
        return core

    def _plan_expand_fn(self, op: LevelOp, caps_sig: tuple, cap_base: int,
                        out_cap: int, out_items: int,
                        want_count: bool = False):
        """Fused gather + level masks + on-device compaction + meta.

        meta = [total, max survivor count] + [max degree of column c over
        live items, for c in op.gather_refs] — the only host sync per level.
        ``want_count`` (count-rides-expand) appends the survivor-count sum
        as an exact (hi, lo) int32 pair: the partial a riding count leaf is
        credited with, at zero extra dispatches (same envelope as
        ``_plan_count_fn``: counts are already per-row exact).
        """
        def build():
            return self._jit_expand(
                op, self._expand_body(op, caps_sig, cap_base, out_cap,
                                      out_items, want_count), want_count)
        return self._executable(
            ("pexpand", op, caps_sig, cap_base, out_cap, out_items,
             want_count), build)

    def _expand_body(self, op: LevelOp, caps_sig: tuple, cap_base: int,
                     out_cap: int, out_items: int, want_count: bool):
        """Unjitted expand-level body; meta layout as in
        ``_plan_expand_fn``, the (hi, lo) ride pair (when ``want_count``)
        in the last two slots."""
        in_cols = self._in_cols(op)
        caps = dict(caps_sig)
        core = self._survivor_core(op, caps, out_cap, out_items)

        def fn(g, vals, carry, n):
            get = dict(zip(in_cols, vals))
            base = carry if op.use_carry else \
                padded_rows(g, get[op.base], caps[op.base])[0]
            rows2, counts, src, verts, total, maxc = \
                core(g, get, base, n)
            live = jnp.arange(out_items, dtype=jnp.int32) < total
            metas = [total, maxc]
            for c in op.gather_refs:
                cv = verts if c == op.level else get[c][src]
                metas.append(jnp.max(jnp.where(live, g.degrees[cv], 0)))
            if want_count:
                metas += [jnp.sum(counts >> 16, dtype=jnp.int32),
                          jnp.sum(counts & 0xFFFF, dtype=jnp.int32)]
            return rows2, src, verts, jnp.stack(metas)
        return fn

    def _plan_emit_fn(self, op: LevelOp, caps_sig: tuple, cap_base: int,
                      out_cap: int, out_items: int):
        """Terminal emit level: compacted embeddings stay device-side until
        one bulk pull per chunk (FSM's triangle feed; ROADMAP item)."""
        def build():
            return self._jit_emit(
                op, self._emit_body(op, caps_sig, cap_base, out_cap,
                                    out_items))
        return self._executable(
            ("pemit", op, caps_sig, cap_base, out_cap, out_items), build)

    def _emit_body(self, op: LevelOp, caps_sig: tuple, cap_base: int,
                   out_cap: int, out_items: int):
        """Unjitted emit-level body: (embedding matrix, live total)."""
        in_cols = self._in_cols(op)
        caps = dict(caps_sig)
        core = self._survivor_core(op, caps, out_cap, out_items)

        def fn(g, vals, carry, n):
            get = dict(zip(in_cols, vals))
            base = carry if op.use_carry else \
                padded_rows(g, get[op.base], caps[op.base])[0]
            _, _, src, verts, total, _ = core(g, get, base, n)
            live = jnp.arange(out_items, dtype=jnp.int32) < total
            cols_out = [verts if c == op.level
                        else jnp.where(live, get[c][src], 0)
                        for c in op.out_cols]
            return jnp.stack(cols_out, axis=1), total
        return fn

    def _plan_chunk_fn(self, op: LevelOp, b: int, out_cap: int, cap2: int,
                       chunk: int):
        """Slice the compacted worklist into the next level's device wave:
        forwarded prefix columns gather through ``src`` (zeroed past the live
        count so padding items carry bound-0 everywhere), the new vertex
        column comes from ``verts``, and the survivor streams become the next
        carry when the compiler proved reuse."""
        def build():
            return self._jit_chunk(op, self._chunk_body(op, cap2, chunk))
        return self._executable(("pchunk", op, b, out_cap, cap2, chunk),
                                build)

    def _chunk_body(self, op: LevelOp, cap2: int, chunk: int):
        """Unjitted worklist-slice body for ``_plan_chunk_fn``."""
        carry_out = op.carry_out

        def fn(rows2, src, verts2, colvals, lo, m):
            s = jax.lax.dynamic_slice_in_dim(src, lo, chunk)
            v = jax.lax.dynamic_slice_in_dim(verts2, lo, chunk)
            valid = jnp.arange(chunk, dtype=jnp.int32) < m
            v = jnp.where(valid, v, 0)
            outs = tuple(jnp.where(valid, cv[s], 0) for cv in colvals)
            if carry_out:
                return outs, v, rows2[s, :cap2]
            return outs, v
        return fn

    # ------------------------------------------------------- the interpreter
    def _finalize(self, plan: WavePlan, parts: list):
        """Reduce one plan's host parts to its result: emit blocks, (value,
        live) aggregate pairs, or exact counts (Python ints: each count
        leaf's accumulator total and each ride partial)."""
        if plan.ops[-1].kind == "emit":
            if not parts:
                return np.zeros((0, plan.k), dtype=np.int32)
            return np.concatenate(parts, axis=0).astype(np.int32)
        agg = plan.ops[-1].agg
        if agg is not None:
            # f32 (value, live) pairs; live > 0 gates the op identity out
            # (a weighted query over zero embeddings aggregates to 0.0)
            value, live = None, 0.0
            for p in parts:
                v = np.asarray(p, dtype=np.float64)
                live += float(v[1])
                x = float(v[0])
                if value is None:
                    value = x
                elif agg == "sum":
                    value += x
                elif agg == "max":
                    value = max(value, x)
                else:
                    value = min(value, x)
            return float(value) if (value is not None and live > 0) else 0.0
        total = sum(parts)
        if plan.div > 1:
            assert total % plan.div == 0, (plan.pattern.name, total, plan.div)
            total //= plan.div
        return total

    def run(self, plan: WavePlan):
        """Execute one compiled ``WavePlan`` as a one-plan forest.

        Counting plans return a Python int (divided by ``plan.div``); emit
        plans return the (N, k) int32 embedding matrix in matching order.
        ``Miner`` caches the forest beside the plan and calls ``run_set``.
        """
        return self.run_set(build_forest([plan]))[0]

    def run_set(self, forest):
        """Execute a ``mining.forest.PlanForest``: each feed orientation is
        materialised and iterated ONCE, every trie root consumes the same
        device-resident edge chunks, and shared interior nodes run their
        expand + compaction a single time before fanning out to all child
        branches. Each count-leaf node keeps one device accumulator
        (``leaves``, keyed by node identity) that every dispatch of the
        leaf threads through; per-plan lists collect ride partials,
        aggregate pairs and embedding blocks. At the end one
        ``jax.device_get`` reads every accumulator and every part still on
        the device, and each leaf's total is credited to all its plans.

        Returns a list of per-plan results in ``forest.plans`` order (ints
        for counting plans, (N, k) int32 matrices for emit plans) —
        bit-identical to running each plan through ``run`` independently.
        """
        acc: list[list] = [[] for _ in forest.plans]
        leaves: dict[int, list] = {}
        tr = self.telemetry.tracer
        with tr.span("execute", plans=len(forest.plans), forest=True):
            for symmetric, roots in ((True, forest.symmetric_roots),
                                     (False, forest.directed_roots)):
                if not roots:
                    continue
                need1 = any(1 in r.op.row_refs() for r in roots)
                for cap0, dv0, dv1, v1h, n in self._edge_feed(symmetric):
                    self._ct_feed_chunks.inc()
                    with tr.span("feed", cat="level", cap=cap0,
                                 items=lambda: int(np.asarray(n).sum())):
                        caps = {0: cap0}
                        if need1:
                            caps[1] = _neighbor_cap(self.g, self._live(v1h, n))
                            self._count_feed_fill(caps[1], v1h, n)
                        for root in roots:
                            self._forest_descend(root, {0: dv0, 1: dv1},
                                                 caps, None, n, acc, leaves)
            with tr.span("finalize"):
                nodes = list(leaves.values())
                limbs, acc = jax.device_get(([a for _, a in nodes], acc))
                # one read per accumulator and per part (ride partials
                # offset theirs at arrival: they came in a meta sync)
                self._ct["host_syncs"].inc(
                    len(nodes) + sum(len(a) for a in acc))
                for (plans, _), lm in zip(nodes, limbs):
                    total = count_acc_value(lm)
                    for i in plans:
                        acc[i].append(total)
                return [self._finalize(plan, parts)
                        for plan, parts in zip(forest.plans, acc)]

    def _forest_descend(self, node, cols: dict, caps: dict, carry, n: int,
                        acc: list, leaves: dict) -> None:
        """Execute one forest node on a wave chunk; fan out over children.

        A count leaf dispatches its executable on the node's accumulator
        in ``leaves`` (zeros at its first chunk of the query) and keeps
        the result there; an aggregate leaf appends its pair and an emit
        leaf its embedding block to each owning plan's list in ``acc``; an
        expand runs once and its compacted worklist's chunks feed *every*
        child branch."""
        op = node.op
        caps_sig = tuple(sorted((c, caps[c]) for c in op.row_refs()))
        cap_base = int(carry.shape[1]) if op.use_carry else caps[op.base]
        vals = tuple(cols[c] for c in self._in_cols(op))
        carry_in = carry if op.use_carry else np.int32(0)
        with self._level_span(op, n):
            if op.kind == "count":
                self._bump(op)
                if op.agg is None:
                    fn = self._plan_count_fn(op, caps_sig, cap_base)
                    leaf = leaves.get(id(node))
                    if leaf is None:
                        zero = jax.device_put(np.zeros(COUNT_LIMBS, np.int32),
                                              self._acc_sharding)
                        leaf = leaves[id(node)] = [node.plans, zero]
                    leaf[1] = self._dispatch(
                        op, fn, (leaf[1], self.g, vals, carry_in, n),
                        items=n, caps_sig=caps_sig)
                    return
                self._ct_value_lanes.inc()
                fn = self._plan_agg_fn(op, caps_sig, cap_base)
                part = self._dispatch(op, fn, (self.g, vals, carry_in, n),
                                      items=n, caps_sig=caps_sig)
                for i in node.plans:
                    acc[i].append(part)
                return
            b = (int(carry.shape[0]) if op.use_carry
                 else int(cols[op.base].shape[0])) // self._shards
            out_cap = min([cap_base] + [caps[j] for j in op.inter])
            out_items = -(-b * out_cap // self.chunk) * self.chunk
            if op.kind == "emit":
                parts = self._plan_emit(op, caps_sig, cap_base, out_cap,
                                        out_items, vals, carry_in, n)
                for i in node.plans:
                    acc[i].extend(parts)
                return
            if node.ride_plans:
                self._ct["count_rides"].inc(len(node.ride_plans))
            exp = self._expand_device(op, caps_sig, cap_base, out_cap,
                                      out_items, vals, carry_in, n,
                                      want_count=bool(node.ride_plans))
            if exp is None:
                return
            rows2, src, verts2, total, caps2, cap2, ride = exp
            if ride is not None:
                for i in node.ride_plans:
                    acc[i].append(ride)
                # ride partials arrived inside the expand's existing meta
                # sync; offset run_set's per-part tally so they aren't
                # double-counted (guarded dec: underflow raises)
                self._ct["host_syncs"].dec(len(node.ride_plans))
            # children that kept every constraint of the shared node consume
            # the compacted worklist as-is (one chunk stream for all of
            # them); children whose branch deferred constraints into
            # residuals get a per-branch packed worklist first, so relaxation
            # never inflates a branch's downstream item count past its
            # independent plan's.
            feeds: list[tuple[list, object, object, int]] = []
            shared = [ch for ch in node.children if not ch.op.residual]
            if shared:
                feeds.append((shared, src, verts2, total))
            for ch in node.children:
                if not ch.op.residual:
                    continue
                pfn, refs = self._residual_pack_fn(
                    op.level, ch.op.residual,
                    int(src.shape[0]) // self._shards)
                rvals = tuple(cols[c] for c in refs)
                src_b, verts_b, tot_b = pfn(rvals, src, verts2, total)
                with self._sync_span("pack"):
                    tot_b, has_b = self._pack_total(tot_b)
                self._ct["host_syncs"].inc()
                if has_b:
                    feeds.append(([ch], src_b, verts_b, tot_b))
            for children, s, v, t in feeds:
                for cols2, carry2, m in self._expand_chunks(
                        op, b, out_cap, cap2, rows2, s, v, cols, t):
                    for child in children:
                        self._forest_descend(child, cols2, caps2, carry2, m,
                                             acc, leaves)

    def _plan_emit(self, op, caps_sig, cap_base, out_cap, out_items, vals,
                   carry_in, n) -> list:
        self._bump(op)
        fn = self._plan_emit_fn(op, caps_sig, cap_base, out_cap, out_items)
        emb, total = self._dispatch(op, fn, (self.g, vals, carry_in, n),
                                    items=n, caps_sig=caps_sig)
        with self._sync_span("emit"):
            total = int(total)
            emb = np.asarray(emb)[:total] if total else None
        self._ct["device_compactions"].inc()
        self._ct["items"].inc(total)
        return [emb] if total else []

    def _expand_device(self, op, caps_sig, cap_base, out_cap, out_items,
                       vals, carry_in, n, want_count: bool = False):
        """Run one expand executable + meta sync. Returns ``None`` when no
        survivors, else (rows2, src, verts2, total, caps2, cap2, ride) —
        ``ride`` is the survivor count, an exact Python int reassembled
        from the meta's (hi, lo) pair, when ``want_count``
        (count-rides-expand), else None."""
        self._bump(op)
        fn = self._plan_expand_fn(op, caps_sig, cap_base, out_cap, out_items,
                                  want_count)
        rows2, src, verts2, meta = self._dispatch(
            op, fn, (self.g, vals, carry_in, n), items=n, caps_sig=caps_sig)
        with self._sync_span("meta"):
            meta = [int(x) for x in np.asarray(meta)]
        if want_count:
            meta, ride = meta[:-2], (meta[-2] << 16) + meta[-1]
        else:
            ride = None
        total, maxc, dmaxs = meta[0], meta[1], meta[2:]
        self._ct["host_syncs"].inc()
        self._ct["device_compactions"].inc()
        self._ct["items"].inc(total)
        if total == 0:
            return None
        caps2 = {c: _pow2cap(max(d, 1))
                 for c, d in zip(op.gather_refs, dmaxs)}
        cap2 = round_capacity(maxc) if op.carry_out else 0
        return rows2, src, verts2, total, caps2, cap2, ride

    def _expand_chunks(self, op, b, out_cap, cap2, rows2, src, verts2, cols,
                       total):
        """Slice a compacted (src, verts) worklist into next-level device
        chunks; yields (cols2, carry2, m)."""
        cfn = self._plan_chunk_fn(op, b, out_cap, cap2, self.chunk)
        fwdvals = tuple(cols[c] for c in op.out_cols if c < op.level)
        for lo in range(0, total, self.chunk):
            m = min(self.chunk, total - lo)
            if op.carry_out:
                outs, vch, carry2 = cfn(rows2, src, verts2, fwdvals, lo, m)
            else:
                outs, vch = cfn(rows2, src, verts2, fwdvals, lo, m)
                carry2 = None
            cols2 = dict(zip([c for c in op.out_cols if c < op.level], outs))
            if op.level in op.out_cols:
                cols2[op.level] = vch
            yield cols2, carry2, m

    def _residual_pack_fn(self, level: int, residual: tuple, out_items: int):
        """Per-branch worklist pack: drop items failing a child branch's
        residuals *before* chunking, so a branch that shared a relaxed
        ancestor processes exactly the items its independent plan would
        (order-preserving prefix-sum scatter over the item indices —
        ``compact_indices_scan``, O(items) instead of the index sort's
        O(items·log)). Returns (packing fn, value columns it consumes)."""
        refs = tuple(sorted({c for _, i, j in residual for c in (i, j)
                             if c < level}))

        def build():
            return self._jit_rpack(
                self._rpack_body(level, residual, refs, out_items),
                len(refs))
        return self._executable(("rpack", level, residual, out_items),
                                build), refs

    def _rpack_body(self, level: int, residual: tuple, refs: tuple,
                    out_items: int):
        """Unjitted residual-pack body for ``_residual_pack_fn``."""
        def fn(rvals, src, verts, total):
            get = dict(zip(refs, rvals))

            def val(c):
                return verts if c == level else get[c][src]
            idx = jnp.arange(out_items, dtype=jnp.int32)
            ok = idx < total
            for kind, i, j in residual:
                ok = ok & ((val(i) < val(j)) if kind == "lt"
                           else (val(i) != val(j)))
            order, tot = compact_indices_scan(ok)
            live = idx < tot
            return src[order], \
                jnp.where(live, verts[order], 0).astype(jnp.int32), tot
        return fn

    # ----------------------------------------------- plan wrappers (compat)
    def count_edges(self, symmetric: bool = True, bounded: bool = True) -> int:
        """Σ over edges of |N(v0) ∩ N(v1) (∩ [0, v1))| — triangle / nested
        triangle counting as a one-level plan."""
        return self.run(compile_pattern(
            _EDGE_COUNT_PATTERNS[(symmetric, bounded)]))

    def clique(self, k: int) -> int:
        """k-clique counting, k >= 3 (compiled chain-restricted plan)."""
        if k < 3:
            raise ValueError("clique needs k >= 3")
        return self.run(compile_pattern(clique_pattern(k)))

    def three_chain_induced(self) -> int:
        """Per directed edge (m, a): |{b ∈ N(m): b > a, b ∉ N(a)}|."""
        from .plan import THREE_CHAIN_INDUCED
        return self.run(compile_pattern(THREE_CHAIN_INDUCED))

    def tailed_triangle(self) -> int:
        """Fig. 2b: BoundedIntersect(N0, N1, v0) per directed edge; the tail
        level compiles away into the closed-form deg(v1) - 2 multiplier."""
        from .plan import TAILED_TRIANGLE
        return self.run(compile_pattern(TAILED_TRIANGLE))
