"""The paper's mining applications (§VI-B) + 4-motif mining, as patterns.

Every function here is now a **deprecated thin shim** over the session API
(``mining.session.Miner``): each delegates to a module-level per-graph
session (``shared_session``), so the old one-shot surface keeps its exact
behaviour for existing tests/benchmarks while gaining session semantics —
the graph is staged to device once, executables are cached across calls,
and multi-pattern batches are scheduled by the automatic matching-order
search. New code should hold a ``Miner`` directly:

    from repro.mining.session import Miner
    m = Miner(g)
    m.count("triangle"); m.count_many(["diamond", "paw"]) ...

The only hand-written paths left are genuine closed forms (non-induced
three-chain = Σ C(deg, 2)) and the host ``triangle_list_host`` oracle the
device enumeration is property-tested against.

All counts are exact and each embedding is counted once (symmetry breaking
via compiled upper/lower-bound restrictions, Fig. 2b's R3 operand), except
the explicitly paper-faithful *nested* variants which reproduce the
Fig. 4a unbounded S_NESTINTER dataflow and divide by the automorphism
count (``Pattern.div``).

Definitions (verified against brute-force oracles in tests):
  triangle           unordered vertex triples, mutually adjacent
  three-chain        non-induced: paths a—m—b (a<b);  induced: additionally
                     (a,b) ∉ E   (3-motif uses the induced count)
  tailed triangle    triangle {v0,v1,v2} + edge (v1,v3), v3 ∉ {v0,v2}; the
                     pattern automorphism (v0<->v2) is broken with v2 < v0
  k-clique           complete subgraphs of size k, counted once
  4-motif            induced counts of the six connected 4-vertex motifs
                     (4-path, 4-star, 4-cycle, paw, diamond, 4-clique)
"""
from __future__ import annotations

import warnings
import weakref
from collections import OrderedDict

import numpy as np

from repro.graph.csr import CSRGraph
from .engine import Wave, choose_chunk, compact, expand, half_edges, \
    pair_wave
from .forest import PlanForest
from .plan import FOUR_MOTIF_SHAPES, Pattern, TAILED_TRIANGLE, \
    THREE_CHAIN_INDUCED, TRIANGLE, TRIANGLE_NESTED, WavePlan, \
    clique_pattern, compile_pattern
from .session import Miner

def _deprecated(name: str) -> None:
    """One-shot shim warning: every call on the legacy surface points at
    the stable API (``repro.mining.Miner`` / ``MiningService``). Emitted
    per call, not per import, so merely importing this module (the FSM
    feed lives here) stays silent."""
    warnings.warn(
        f"repro.mining.apps.{name} is deprecated; hold a session instead: "
        "repro.mining.Miner(g).count(...) (or MiningService for "
        "concurrent traffic)", DeprecationWarning, stacklevel=3)


# ---------------------------------------------------------------------------
# the module-level session pool backing the deprecated one-shot surface
# ---------------------------------------------------------------------------

# (id(graph), chunk) -> (weakref to graph, Miner). The
# weakref guards against id() reuse after the original graph is collected;
# a small LRU bounds how many sessions (device stagings + exec caches) the
# shim surface keeps alive at once.
_SESSION_POOL: OrderedDict = OrderedDict()
_SESSION_POOL_CAP = 8


def shared_session(g: CSRGraph, chunk: int | None = None) -> Miner:
    """Get-or-create the module-level ``Miner`` for (graph, config).

    This is what makes the legacy free functions sessions in disguise:
    every call over the same graph and config lands on one ``Miner``, so
    graph staging, compiled plans, schedules and executables are all
    reused across calls."""
    key = (id(g), chunk)
    ent = _SESSION_POOL.get(key)
    if ent is not None and ent[0]() is g:
        _SESSION_POOL.move_to_end(key)
        return ent[1]
    miner = Miner(g, chunk=chunk)
    _SESSION_POOL[key] = (weakref.ref(g), miner)
    while len(_SESSION_POOL) > _SESSION_POOL_CAP:
        _SESSION_POOL.popitem(last=False)
    return miner


def pattern_count(g: CSRGraph, pat: Pattern, chunk: int | None = None) -> int:
    """Deprecated shim: ``Miner.count`` on the shared session."""
    _deprecated("pattern_count")
    return shared_session(g, chunk).count(pat)


def pattern_embeddings(g: CSRGraph, pat: Pattern,
                       chunk: int | None = None) -> np.ndarray:
    """Deprecated shim: ``Miner.embeddings`` on the shared session."""
    _deprecated("pattern_embeddings")
    return shared_session(g, chunk).embeddings(pat)


def pattern_set_run(g: CSRGraph, plans: list[WavePlan] | PlanForest,
                    chunk: int | None = None) -> list:
    """Deprecated shim: run a batch of compiled plans (or a pre-built
    ``PlanForest``) as one fused pass on the shared session. Results come
    back per plan, in order — ints for counting plans, (N, k) matrices for
    emit plans — bit-identical to independent ``Miner.count`` runs."""
    _deprecated("pattern_set_run")
    miner = shared_session(g, chunk)
    if isinstance(plans, PlanForest):
        return miner.runner.run_set(plans)
    return miner.run_plans(plans)


def pattern_set_count(g: CSRGraph, pats: list[Pattern],
                      chunk: int | None = None) -> list[int]:
    """Deprecated shim: ``Miner.count_many`` on the shared session."""
    _deprecated("pattern_set_count")
    return shared_session(g, chunk).count_many(pats)


def triangle_count(g: CSRGraph, chunk: int | None = None) -> int:
    """Symmetry-broken triangle counting: one bounded intersection per half
    edge (v0 > v1), bound v1 => each triangle v0 > v1 > v2 counted once."""
    _deprecated("triangle_count")
    return shared_session(g, chunk).count(TRIANGLE)


def triangle_count_nested(g: CSRGraph, chunk: int | None = None) -> int:
    """Paper-faithful Fig. 4a: Σ_v S_NESTINTER(N(v)) counts each triangle 6x.

    The per-vertex nested instruction flattens to one unbounded intersection
    per *directed* edge — exactly the µop stream §IV-F's translator emits —
    and ``TRIANGLE_NESTED.div`` divides the automorphisms out at retire."""
    _deprecated("triangle_count_nested")
    return shared_session(g, chunk).count(TRIANGLE_NESTED)


def three_chain_count(g: CSRGraph, induced: bool = False,
                      chunk: int | None = None) -> int:
    """Three-chain (path) counting.

    non-induced: Σ_m C(deg m, 2) — closed form (no intersection needed; the
    stream engine is exercised by the induced variant).
    induced: the compiled SUB + lower-bound plan (b ∈ N(m), b ∉ N(a), b > a).
    """
    _deprecated("three_chain_count")
    deg = np.asarray(g.degrees, dtype=np.int64)
    non_induced = int((deg * (deg - 1) // 2).sum())
    if not induced:
        return non_induced
    return shared_session(g, chunk).count(THREE_CHAIN_INDUCED)


def tailed_triangle_count(g: CSRGraph, chunk: int | None = None) -> int:
    """Fig. 2b dataflow: per directed edge (v0,v1), BoundedIntersect(N0,N1,v0)
    yields the v2 < v0 candidates; the tail level folds into the closed-form
    deg(v1) - 2 multiplier at compile time."""
    _deprecated("tailed_triangle_count")
    return shared_session(g, chunk).count(TAILED_TRIANGLE)


def three_motif(g: CSRGraph, fused: bool = True) -> dict[str, int]:
    """3-motif mining: counts of both connected 3-vertex induced motifs.

    ``fused`` routes both patterns through one session batch (a fused
    ``PlanForest``); ``fused=False`` keeps the independent per-plan path
    (the baseline the forest is benchmarked and property-tested against)."""
    _deprecated("three_motif")
    if fused:
        t, chains = shared_session(g).count_many(
            [TRIANGLE, THREE_CHAIN_INDUCED])
    else:
        t = triangle_count(g)
        chains = three_chain_count(g, induced=True)
    return {"triangle": t, "chain": chains}


def clique_count(g: CSRGraph, k: int, chunk: int | None = None) -> int:
    """k-clique counting, k >= 3: the compiled chain-restricted plan. Every
    level reuses the parent's survivor stream (the compiler's carry
    analysis), so the interpreter issues the exact executable sequence the
    old hand-coded engine did."""
    _deprecated("clique_count")
    if k < 3:
        raise ValueError("clique_count needs k >= 3")
    return shared_session(g, chunk).count(clique_pattern(k))


def four_motif(g: CSRGraph, chunk: int | None = None,
               fused: bool = True) -> dict[str, int]:
    """4-motif mining: induced counts of all six connected 4-vertex motifs.

    The motifs are adjacency-only shapes (``plan.FOUR_MOTIF_SHAPES``); the
    session's schedule stage picks each one's matching order automatically
    so the batch collapses to three shared level-2 expands over two
    edge-feed passes. ``fused=False`` runs the same auto-scheduled patterns
    independently — same counts, kept as the comparison baseline."""
    _deprecated("four_motif")
    miner = shared_session(g, chunk)
    if fused:
        counts = miner.count_many(list(FOUR_MOTIF_SHAPES))
        return dict(zip(FOUR_MOTIF_SHAPES, counts))
    from . import plan as P
    return {name: miner.count(P.FOUR_MOTIFS[name])
            for name in FOUR_MOTIF_SHAPES}


# the FSM pattern batch: every engine-fed plan FSM's support evaluation
# consumes, merged into one forest (a single feed pass). Today that is the
# triangle emit plan — wedge/star/path domains are closed forms over the
# neighbor-label count table — but additional engine-fed patterns join the
# same batch (and share its prefixes) by appending here.
FSM_FEED_PLANS: tuple = (compile_pattern(TRIANGLE, emit=True),)


def fsm_pattern_feed(g: CSRGraph, chunk: int | None = None,
                     miner: Miner | None = None) -> list:
    """Run the FSM engine-feed batch on a session; returns per-plan results
    in ``FSM_FEED_PLANS`` order (triangle embeddings first). ``miner``
    reuses a caller-held session (FSM passes its own)."""
    miner = miner or shared_session(g, chunk)
    return miner.run_plans(list(FSM_FEED_PLANS))


def triangle_list(g: CSRGraph, chunk: int | None = None) -> np.ndarray:
    """Enumerate all triangles as (T, 3) vertex triples (v0 > v1 > v2).

    Used by FSM (labelled support needs embeddings, not counts). Runs the
    triangle *emit* plan through the session: compaction happens on device
    via ``ops.xinter_compact``'s src output, and only the compacted
    embedding matrix crosses to the host."""
    _deprecated("triangle_list")
    return fsm_pattern_feed(g, chunk)[0]


def triangle_list_host(g: CSRGraph, chunk: int | None = None) -> np.ndarray:
    """Host-compaction oracle for ``triangle_list`` (np.nonzero +
    ``compact(return_src=True)``) — kept as the property-test reference for
    the device emit path."""
    chunk = chunk or choose_chunk(g.padded_max_degree)
    out = []
    for rows0, rows1, v0, v1, n in pair_wave(g, half_edges(g), chunk):
        wave = Wave(rows=np.asarray(rows0), verts=v1)
        rows2, counts2 = expand(g, wave)
        w2, ii = compact(rows2, counts2, limit=n, return_src=True)
        if w2 is None:
            continue
        out.append(np.stack([v0[ii], v1[ii], w2.verts], axis=1))
    if not out:
        return np.zeros((0, 3), dtype=np.int32)
    return np.concatenate(out, axis=0).astype(np.int32)
