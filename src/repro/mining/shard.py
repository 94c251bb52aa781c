"""Mesh-sharded mining: data-parallel wavefronts over a 1-D device mesh.

The wavefront interpreter (``mining.engine.WaveRunner``) is embarrassingly
parallel over the level-1 edge feed: every edge's pattern-tree descent is
independent, and every per-level executable is already written as a pure
body over (prefix columns, carry, live count). ``ShardedWaveRunner``
exploits exactly that: it reuses the *unmodified* level bodies and wraps
each one's ``_jit_*`` dispatch hook in ``jax.shard_map`` over
a mesh axis (default ``"mine"``), so each device runs the identical wave
program on its local feed block:

  * the CSR graph is replicated (``PartitionSpec()``) — staged once per
    session, every shard intersects against its own copy;
  * wave buffers — prefix-column values, carries, compacted (src, verts)
    worklists — are sharded on the mining axis: a global ``(S * items,)``
    buffer holds ``S`` per-shard blocks back to back;
  * count leaves reduce their (hi, lo) partials with ``jax.lax.psum``
    over the mining axis. Per-shard hi words can reach 2^30, so an 8-way
    int32 psum could wrap: partials are split into four 16-bit limbs
    *before* the psum (limb sums stay far below 2^31). The same
    executable then adds the psum'd quad to the leaf's count accumulator,
    replicated (``PartitionSpec()``) on every device: three int32 limbs of
    weights 2^0, 2^16, 2^32, carried after each add, exact for any total
    below 2^63 on a mesh of fewer than 2^13 devices
    (``engine.count_acc_add``). The host reads it once, at the query's end;
  * expand levels return their level-boundary meta per shard (an (S, m)
    row block): per-shard live totals drive lockstep chunking (every
    shard walks ``ceil(max_totals / chunk)`` steps; shards past their own
    total carry bound-0 padding and contribute nothing), while next-level
    capacities take the max over shards — capacities are upper bounds, so
    the widening is lossless;
  * emit levels gather per-shard survivor blocks on the host (one bulk
    pull per chunk, then a per-shard slice to each live total).

Orchestration stays on the host and stays *identical* to the single-device
interpreter — same forest fan-out, same residual packs —
because the only per-shard state it tracks is the live-total vector
(``_pack_total``). Counts are therefore bit-identical to the single-device
session: the same integer summands, grouped differently.

The level-1 feed is dealt by ``shard_edge_steps``: per degree bucket,
edges are round-robin dealt across shards (CSR edge order is sorted by
source vertex, so a hub's edge run would land on one shard under a
contiguous split — the dealt assignment bounds the per-step imbalance at
one item). The runner deals each super-step in the base feed's
``feed_step`` span (``WaveRunner._edge_feed``; dealing, fill count and
the sharded upload). ``stats["shard_feed_items"]`` exposes the per-shard
feed item counts so the balance is measurable, and the registry counter
``shard_pad_items`` the lockstep slots no live item fills (level-1
super-steps and expand chunks); ``mode="contiguous"`` keeps the
chunk-granular contiguous assignment as the measurable foil. The leaf
reductions run in the name scope ``mesh_psum`` (``MESH_SCOPE``), so their
device ops can be told apart in a profile.

Use via the session API (``Miner(g, mesh=8)``); the mesh itself comes from
``repro.distributed.sharding.make_mining_mesh`` and its axes are part of
every executable-cache key (``session.mesh_signature``), so sharded and
unsharded executables never collide and repeated sharded queries retrace
nothing.
"""
from __future__ import annotations

from typing import Callable

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.stream import round_capacity
from repro.graph.csr import CSRGraph
from .engine import WaveRunner, _pow2cap, edge_buckets, feed_cuts

__all__ = ["ShardedWaveRunner", "shard_edge_steps"]

FEED_PARTITIONS = ("round_robin", "contiguous")
# name scope of the cross-shard reductions of count and aggregate leaves:
# their device ops carry it in their ``tf_op``
MESH_SCOPE = "mesh_psum"


def shard_edge_steps(g: CSRGraph, chunk: int, shards: int,
                     symmetric: bool = True, mode: str = "round_robin"):
    """Level-1 feed for an ``shards``-way mesh: yields lockstep super-steps
    ``(cap, v0, v1, n)`` where ``v0``/``v1`` are (shards * nb,) int32 arrays
    holding one nb-item block per shard back to back, and ``n`` is the
    (shards,) per-shard live count.

    Per degree bucket of E edges the block width is
    ``nb = min(chunk, pow2cap(ceil(E / shards)))`` — the bucket's work
    divided across the mesh, so a sharded pass takes ~``1/shards`` the
    super-steps of the single-device feed (the dispatch-scaling contract
    gated in benchmarks/ci_gate.py). Each super-step spans
    ``shards * nb`` consecutive bucket edges, in the bucket's v1-class
    order (``edge_buckets``), so a step's N(v1) capacity is that of its own
    widest live v1:

    * ``round_robin`` (default): shard s takes ``step_edges[s::shards]``.
      CSR edge order groups a vertex's edges consecutively, so dealing
      spreads every hub's run across the whole mesh; per-step imbalance
      is at most one item.
    * ``contiguous``: shard s takes the s-th contiguous nb-slice — the
      hub-pinning foil (a partial step loads low shards and leaves high
      shards empty) kept for the load-balance benchmark.

    Both modes enumerate the same edge multiset; only the edge -> shard
    assignment differs, so counts are unaffected.
    """
    if mode not in FEED_PARTITIONS:
        raise ValueError(f"feed_partition must be one of {FEED_PARTITIONS}, "
                         f"got {mode!r}")
    return shard_bucket_steps(edge_buckets(g, symmetric), chunk, shards,
                              mode)


def shard_bucket_steps(buckets, chunk: int, shards: int,
                       mode: str = "round_robin"):
    """The dealing half of ``shard_edge_steps``, over ``edge_buckets``."""
    for cap, block, nb in feed_cuts(buckets, chunk, shards):
        yield (cap, *deal(block, nb, shards, mode))


def deal(block: np.ndarray, nb: int, shards: int,
         mode: str = "round_robin"):
    """(v0, v1, n) of one lockstep super-step: the ``block``'s edges dealt
    into ``shards`` blocks of ``nb`` items laid back to back (dead slots
    vertex 0), and the (shards,) per-shard live counts."""
    v0 = np.zeros((shards, nb), np.int32)
    v1 = np.zeros((shards, nb), np.int32)
    n = np.zeros((shards,), np.int32)
    for s in range(shards):
        part = block[s::shards] if mode == "round_robin" \
            else block[s * nb: (s + 1) * nb]
        k = part.shape[0]
        n[s] = k
        v0[s, :k] = part[:, 0]
        v1[s, :k] = part[:, 1]
    return v0.reshape(-1), v1.reshape(-1), n


class ShardedWaveRunner(WaveRunner):
    """``WaveRunner`` with every executable wrapped in ``shard_map``.

    See the module docstring for the sharding contract. Only the dispatch
    hooks (``_jit_*``), the feed, and the boundary-meta plumbing differ
    from the base interpreter — the traced level bodies are shared, so the
    two runners cannot drift semantically.
    """

    def __init__(self, g: CSRGraph, mesh, *, axis: str = "mine",
                 feed_partition: str = "round_robin",
                 chunk: int | None = None, backend: str = "auto",
                 exec_cache=None, telemetry=None):
        if axis not in dict(mesh.shape):
            raise ValueError(f"axis {axis!r} not in mesh axes "
                             f"{tuple(dict(mesh.shape))}")
        if feed_partition not in FEED_PARTITIONS:
            raise ValueError(f"feed_partition must be one of "
                             f"{FEED_PARTITIONS}, got {feed_partition!r}")
        super().__init__(g, chunk=chunk, backend=backend,
                         exec_cache=exec_cache, telemetry=telemetry)
        self.mesh = mesh
        self.axis = axis
        self.feed_partition = feed_partition
        self._shards = int(dict(mesh.shape)[axis])
        self._exec_prefix = ("mesh", axis, self._shards)
        self._psh = P(axis)          # sharded on the mining axis
        self._prp = P()              # replicated
        self._rep_sharding = NamedSharding(mesh, self._prp)
        self._acc_sharding = self._rep_sharding
        self._feed_sharding = NamedSharding(mesh, self._psh)
        # replicate the CSR buffers across the mesh once per runner
        self.g = jax.device_put(g, self._rep_sharding)
        # mesh-only metrics: the psum counter joins the legacy view as a
        # plain counter; the per-shard feed tallies are a LABELED series
        # (one counter per shard) whose legacy key derives the historical
        # list-of-ints shape from the series
        self._ct["psum_reductions"] = self.stats.expose_counter(
            "psum_reductions", self.metrics)
        self._shard_feed = [self.metrics.counter("shard_feed_items", shard=s)
                            for s in range(self._shards)]
        self.stats.expose("shard_feed_items",
                          lambda: [c.value for c in self._shard_feed])
        # registry-only: the slots of lockstep blocks that no live item
        # fills (level-1 super-steps and expand chunks); beside
        # shard_feed_items it gives the mesh's feed fill
        self._ct_shard_pad = self.metrics.counter("shard_pad_items")

    # ----------------------------------------------------------- dispatch
    def _shmap(self, body: Callable, in_specs, out_specs) -> Callable:
        return jax.jit(jax.shard_map(body, mesh=self.mesh,
                                     in_specs=in_specs, out_specs=out_specs,
                                     check_vma=False))

    def _level_in_specs(self, op):
        """(g, vals, carry, n) specs shared by count/expand/emit hooks:
        replicated graph, sharded prefix-value columns, sharded carry (a
        replicated zero scalar when the level has none), per-shard n."""
        psh, prp = self._psh, self._prp
        return (prp, (psh,) * len(self._in_cols(op)),
                psh if op.use_carry else prp, psh)

    def _jit_count(self, op, body):
        axis = self.axis

        def wrapped(g, vals, carry, n):
            part = body(g, vals, carry, n)
            # 16-bit limb split BEFORE the psum: per-shard hi can reach
            # 2^30, limb sums stay < 2^19 (hi) / 2^31 (lo) at any mesh size
            with jax.named_scope(MESH_SCOPE):
                limbs = jnp.stack([part[0] >> 16, part[0] & 0xFFFF,
                                   part[1] >> 16, part[1] & 0xFFFF])
                return jax.lax.psum(limbs, axis)
        return self._shmap(wrapped, self._level_in_specs(op), self._prp)

    @staticmethod
    def _count_weights(q):
        """(w0, w16, w32) of a psum'd limb quad: hi's limbs at 2^32 and
        2^16, lo's at 2^16 and 2^0."""
        return q[3], q[1] + q[2], q[0]

    def _jit_agg(self, op, body):
        axis = self.axis
        red = {"sum": jax.lax.psum, "max": jax.lax.pmax,
               "min": jax.lax.pmin}[op.agg]

        def wrapped(g, vals, carry, n):
            part = body(g, vals, carry, n)      # (2,) f32 [value, live]
            # value reduces with the leaf's own op (a dead shard carries the
            # op identity, so pmax/pmin absorb it); live always psums —
            # finalize gates the identity out when the whole mesh is dead
            with jax.named_scope(MESH_SCOPE):
                return jnp.stack([red(part[0], axis),
                                  jax.lax.psum(part[1], axis)])
        return self._shmap(wrapped, self._level_in_specs(op), self._prp)

    def _jit_expand(self, op, body, want_count):
        def wrapped(g, vals, carry, n):
            rows2, src, verts, meta = body(g, vals, carry, n)
            # per-shard meta row: host sees the (shards, m) block
            return rows2, src, verts, meta.reshape(1, -1)
        psh = self._psh
        return self._shmap(wrapped, self._level_in_specs(op),
                           (psh, psh, psh, psh))

    def _jit_emit(self, op, body):
        def wrapped(g, vals, carry, n):
            emb, total = body(g, vals, carry, n)
            return emb, total.reshape(1)
        psh = self._psh
        return self._shmap(wrapped, self._level_in_specs(op), (psh, psh))

    def _jit_chunk(self, op, body):
        psh, prp = self._psh, self._prp
        ncv = len([c for c in op.out_cols if c < op.level])
        out = ((psh,) * ncv, psh) + ((psh,) if op.carry_out else ())
        return self._shmap(body, (psh, psh, psh, (psh,) * ncv, prp, psh),
                           out)

    def _jit_rpack(self, body, nrefs):
        def wrapped(rvals, src, verts, total):
            src2, verts2, tot = body(rvals, src, verts, total)
            return src2, verts2, tot.reshape(1)
        psh = self._psh
        return self._shmap(wrapped, ((psh,) * nrefs, psh, psh, psh),
                           (psh, psh, psh))

    def _bump(self, op) -> None:
        super()._bump(op)
        if op.kind == "count":
            self._ct["psum_reductions"].inc()

    # --------------------------------------------------------------- feed
    def _cut(self, block, nb: int):
        """Deal one super-step over the shards (``deal``), crediting each
        shard's live items to ``shard_feed_items`` and the lockstep padding
        slots to ``shard_pad_items``; the base feed uploads it split over
        the mining axis."""
        v0, v1, n = deal(block, nb, self._shards, self.feed_partition)
        for c, k in zip(self._shard_feed, n):
            c.inc(int(k))
        self._ct_shard_pad.inc(v0.shape[0] - int(n.sum()))
        return v0, v1, n

    # ------------------------------------------------- boundary-meta plumbing
    def _pack_total(self, tot):
        tot = np.asarray(tot, dtype=np.int64).reshape(-1)
        return tot, bool(tot.max() > 0)

    def _expand_device(self, op, caps_sig, cap_base, out_cap, out_items,
                       vals, carry_in, n, want_count: bool = False):
        """Sharded twin of the base meta sync: ``meta`` arrives as one
        (shards, m) row block. Per-shard live totals come back as a vector
        (they drive lockstep chunking); capacities take the max over shards
        (upper bounds — lossless); ride partials are summed exactly on the
        host into one Python int (they already crossed in the meta sync, no
        extra collective)."""
        self._bump(op)
        fn = self._plan_expand_fn(op, caps_sig, cap_base, out_cap, out_items,
                                  want_count)
        rows2, src, verts2, meta = self._dispatch(
            op, fn, (self.g, vals, carry_in, n), items=n, caps_sig=caps_sig)
        with self._sync_span("meta"):
            meta = np.asarray(meta).astype(np.int64)    # (shards, m)
        if want_count:
            hi, lo = (int(x) for x in meta[:, -2:].sum(axis=0))
            meta, ride = meta[:, :-2], (hi << 16) + lo
        else:
            ride = None
        totals = meta[:, 0]
        maxc = int(meta[:, 1].max())
        dmaxs = meta[:, 2:].max(axis=0)
        self._ct["host_syncs"].inc()
        self._ct["device_compactions"].inc()
        self._ct["items"].inc(int(totals.sum()))
        if int(totals.max()) == 0:
            return None
        caps2 = {c: _pow2cap(max(int(d), 1))
                 for c, d in zip(op.gather_refs, dmaxs)}
        cap2 = round_capacity(maxc) if op.carry_out else 0
        return rows2, src, verts2, totals, caps2, cap2, ride

    def _expand_chunks(self, op, b, out_cap, cap2, rows2, src, verts2, cols,
                       totals):
        """Lockstep worklist chunking: every shard slices the SAME [lo, lo +
        chunk) window of its local compacted worklist; the per-shard live
        width ``m`` masks shards already past their own total (their padding
        items carry bound 0 downstream). ``ceil(max_totals / chunk)`` steps
        — the shard with the most survivors sets the wavefront length."""
        cfn = self._plan_chunk_fn(op, b, out_cap, cap2, self.chunk)
        fwdvals = tuple(cols[c] for c in op.out_cols if c < op.level)
        totals = np.asarray(totals, dtype=np.int64).reshape(-1)
        for lo in range(0, int(totals.max()), self.chunk):
            m = np.clip(totals - lo, 0, self.chunk).astype(np.int32)
            self._ct_shard_pad.inc(self._shards * self.chunk - int(m.sum()))
            if op.carry_out:
                outs, vch, carry2 = cfn(rows2, src, verts2, fwdvals, lo, m)
            else:
                outs, vch = cfn(rows2, src, verts2, fwdvals, lo, m)
                carry2 = None
            cols2 = dict(zip([c for c in op.out_cols if c < op.level], outs))
            if op.level in op.out_cols:
                cols2[op.level] = vch
            yield cols2, carry2, m

    def _plan_emit(self, op, caps_sig, cap_base, out_cap, out_items, vals,
                   carry_in, n) -> list:
        """Terminal emit: one bulk embedding pull, then per-shard survivor
        blocks sliced to each shard's live total."""
        self._bump(op)
        fn = self._plan_emit_fn(op, caps_sig, cap_base, out_cap, out_items)
        emb, totals = self._dispatch(op, fn, (self.g, vals, carry_in, n),
                                     items=n, caps_sig=caps_sig)
        with self._sync_span("emit"):
            totals = np.asarray(totals, dtype=np.int64).reshape(-1)
            emb = np.asarray(emb) if int(totals.max()) else None
        self._ct["device_compactions"].inc()
        self._ct["items"].inc(int(totals.sum()))
        if emb is None:
            return []
        blocks = []
        for s, t in enumerate(totals):
            if t:
                blocks.append(emb[s * out_items: s * out_items + int(t)])
        return [np.concatenate(blocks, axis=0)]
