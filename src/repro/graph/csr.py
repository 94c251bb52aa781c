"""Padded CSR graph representation (the paper's S_CSR register file, §III-A).

The paper loads three registers with S_CSR: CSR index (indptr), CSR edge list
(indices) and *CSR offset* — for every vertex v, the position within N(v) of
the smallest neighbor larger than v. The offset register exists purely to
serve symmetry breaking (scan only the `< v` or `> v` half of a neighbor
list); we keep it with identical semantics.

TPU adaptations:
  * ``indices`` is sentinel-padded to a LANE multiple so any window gather is
    in-bounds and masked loads are branch-free.
  * ``blocks`` / ``block_ptr`` hold the same edge lists lane-aligned: N(v)
    starts at lane 0 of block ``block_ptr[v]`` and fills ceil(deg v / LANE)
    128-lane blocks, SENTINEL elsewhere, so ``padded_rows`` reads whole
    blocks (a row gather) instead of one int32 per slot.
  * Every neighbor list is sorted ascending (required by all ISA ops).
  * ``degree_buckets`` groups vertices by padded-degree capacity so batched
    kernels waste bounded work on padding (the S_NESTINTER translation buffer
    becomes a static schedule over buckets — see core/nested.py).

Value plane (the paper's SVPU, §IV-E): ``edge_values`` is an optional f32
array aligned index-for-index with ``indices`` — entry i is the weight of
the directed edge whose destination is ``indices[i]``. ``build_csr``
threads caller weights through the exact same self-loop-drop / mirror /
dedup / lexsort permutation the keys take, so a (key, value) pair never
separates; ``padded_value_rows`` is the value twin of ``padded_rows``
(0.0 where keys are SENTINEL). Weighted graphs are staged once per
session like keys — the value plane adds no per-query uploads.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.stream import LANE, SENTINEL, Stream, round_capacity, stream_from_slice


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class CSRGraph:
    """Compressed sparse row graph; all neighbor lists sorted ascending."""

    indptr: jax.Array    # (V+1,) int32
    indices: jax.Array   # (E_pad,) int32, sentinel-padded to LANE multiple
    offsets: jax.Array   # (V,)   int32: first idx in N(v) with neighbor > v
    degrees: jax.Array   # (V,)   int32
    blocks: jax.Array    # (nblocks+1, LANE) int32: lane-aligned N(v); last all SENTINEL
    block_ptr: jax.Array  # (V,)  int32: first block of N(v)
    # optional value plane: (E_pad,) f32 aligned with ``indices`` (0.0 pad)
    edge_values: jax.Array | None = None
    num_vertices: int = dataclasses.field(metadata=dict(static=True), default=0)
    num_edges: int = dataclasses.field(metadata=dict(static=True), default=0)
    max_degree: int = dataclasses.field(metadata=dict(static=True), default=0)

    @property
    def padded_max_degree(self) -> int:
        return round_capacity(self.max_degree)

    @property
    def weighted(self) -> bool:
        return self.edge_values is not None


def build_csr(edges: np.ndarray, num_vertices: int | None = None,
              undirected: bool = True,
              edge_values: np.ndarray | None = None) -> CSRGraph:
    """Build a CSRGraph from an (M, 2) int edge array (host side).

    Self-loops and duplicate edges are removed; for ``undirected`` graphs both
    directions are materialised (the paper's datasets are undirected simple
    graphs for mining purposes).

    ``edge_values`` (optional, (M,) float) rides the exact same permutation
    the keys take — self-loop drop, mirroring (both directions inherit the
    undirected weight), dedup and the final lexsort — so value i always
    belongs to the directed edge ``edges[i]`` of the finished CSR.
    """
    edges = np.asarray(edges, dtype=np.int64).reshape(-1, 2)
    values = None
    if edge_values is not None:
        values = np.asarray(edge_values, dtype=np.float32).reshape(-1)
        if values.shape[0] != edges.shape[0]:
            raise ValueError(
                f"edge_values has {values.shape[0]} entries for "
                f"{edges.shape[0]} edges")
    if num_vertices is None:
        num_vertices = int(edges.max()) + 1 if edges.size else 0
    keep = edges[:, 0] != edges[:, 1]                          # drop self loops
    edges = edges[keep]
    if values is not None:
        values = values[keep]
    if undirected:
        edges = np.concatenate([edges, edges[:, ::-1]], axis=0)
        if values is not None:
            values = np.concatenate([values, values], axis=0)
    # dedup
    key = edges[:, 0] * np.int64(num_vertices) + edges[:, 1]
    _, uniq = np.unique(key, return_index=True)
    edges = edges[uniq]
    order = np.lexsort((edges[:, 1], edges[:, 0]))
    edges = edges[order]
    if values is not None:
        values = values[uniq][order]

    src, dst = edges[:, 0], edges[:, 1]
    degrees = np.bincount(src, minlength=num_vertices).astype(np.int32)
    indptr = np.zeros(num_vertices + 1, dtype=np.int32)
    np.cumsum(degrees, out=indptr[1:])
    num_edges = int(edges.shape[0])

    e_pad = round_capacity(num_edges + 1)  # +1: a window starting at E stays in-bounds
    indices = np.full(e_pad, SENTINEL, dtype=np.int32)
    indices[:num_edges] = dst.astype(np.int32)
    vals_pad = None
    if values is not None:
        vals_pad = np.zeros(e_pad, dtype=np.float32)
        vals_pad[:num_edges] = values

    # Lane-aligned plane: N(v) from lane 0 of block bptr[v], one scatter.
    nblk = (degrees.astype(np.int64) + LANE - 1) // LANE
    bptr = np.zeros(num_vertices, dtype=np.int64)
    np.cumsum(nblk[:-1], out=bptr[1:])
    nblocks = int(nblk.sum())
    blocks = np.full((nblocks + 1) * LANE, SENTINEL, dtype=np.int32)
    blocks[bptr[src] * LANE + (np.arange(num_edges) - indptr[src])] = dst
    blocks = blocks.reshape(nblocks + 1, LANE)

    # CSR offset register: first index in N(v) strictly greater than v.
    # With no self-loops this equals |{w in N(v): w < v}| — one bincount.
    offsets = np.bincount(src[dst < src], minlength=num_vertices).astype(np.int32)
    max_degree = int(degrees.max()) if num_vertices else 0

    return CSRGraph(
        indptr=jnp.asarray(indptr), indices=jnp.asarray(indices),
        offsets=jnp.asarray(offsets), degrees=jnp.asarray(degrees),
        blocks=jnp.asarray(blocks), block_ptr=jnp.asarray(bptr, jnp.int32),
        edge_values=None if vals_pad is None else jnp.asarray(vals_pad),
        num_vertices=int(num_vertices), num_edges=num_edges,
        max_degree=max_degree)


def with_edge_values(g: CSRGraph, values: np.ndarray) -> CSRGraph:
    """Attach a value plane to an existing graph.

    ``values`` is (num_edges,) float, aligned with ``edge_list(g)`` — i.e.
    value i belongs to the i-th directed edge in CSR order. Returns a new
    graph sharing every key array with ``g``.
    """
    values = np.asarray(values, dtype=np.float32).reshape(-1)
    if values.shape[0] != g.num_edges:
        raise ValueError(
            f"need {g.num_edges} edge values, got {values.shape[0]}")
    vals_pad = np.zeros(g.indices.shape[0], dtype=np.float32)
    vals_pad[: g.num_edges] = values
    return dataclasses.replace(g, edge_values=jnp.asarray(vals_pad))


def neighbors_stream(g: CSRGraph, v, cap: int | None = None) -> Stream:
    """N(v) as a Stream (S_READ of an edge list). ``cap`` static; defaults to
    the graph's padded max degree."""
    cap = round_capacity(cap if cap is not None else g.max_degree)
    start = g.indptr[v]
    length = g.indptr[v + 1] - start
    return stream_from_slice(g.indices, start, length, cap)


def padded_rows(g: CSRGraph, vs: jax.Array, cap: int):
    """Gather neighbor lists of a vertex batch into a (B, cap) padded matrix.

    Returns (keys, lengths): keys sentinel-padded/truncated to ``cap``.
    This is the data-movement core of S_NESTINTER (§IV-F): the nested
    translator's per-key stream loads become one vectorised gather. Its
    ops carry the name scope ``padded_rows`` (metadata only), so a device
    trace can find the gathers whatever XLA names their fusions.

    The gather reads ``cap // LANE`` whole 128-lane blocks of ``g.blocks``
    per row (slice ``(1, LANE)``), from ``g.block_ptr[v]`` on: ``build_csr``
    lays N(v) out lane-aligned there, so a row is a run of blocks. A gather
    of ``g.indices`` moves one int32 per slot: on a TPU v5e about 7.2 ns a
    slot, against 0.4 ns or less for blocks. Blocks past a row's own are
    other vertices' keys, and the ``col < len`` mask turns them to SENTINEL;
    a block index past the end is clipped to the last block, which is all
    SENTINEL.
    """
    assert cap % LANE == 0, f"padded_rows capacity {cap} is not a LANE multiple"
    with jax.named_scope("padded_rows"):
        vs = jnp.asarray(vs, jnp.int32)
        starts = g.indptr[vs]
        lens = g.indptr[vs + 1] - starts
        col = jnp.arange(cap, dtype=jnp.int32)
        b = g.block_ptr[vs][:, None] + jnp.arange(cap // LANE, dtype=jnp.int32)
        b = jnp.clip(b, 0, g.blocks.shape[0] - 1)
        rows = g.blocks[b].reshape(vs.shape[0], cap)
        rows = jnp.where(col[None, :] < lens[:, None], rows, SENTINEL)
        return rows, jnp.minimum(lens, cap).astype(jnp.int32)


def padded_value_rows(g: CSRGraph, vs: jax.Array, cap: int) -> jax.Array:
    """Value twin of ``padded_rows``: gather each vertex's edge values into
    a (B, cap) f32 matrix, 0.0 where the key row holds SENTINEL padding.
    Row i column k is the weight of edge (vs[i], padded_rows(...)[0][i, k]).
    """
    if g.edge_values is None:
        raise ValueError("graph has no edge_values (see with_edge_values)")
    vs = jnp.asarray(vs, jnp.int32)
    starts = g.indptr[vs]
    lens = g.indptr[vs + 1] - starts
    col = jnp.arange(cap, dtype=jnp.int32)
    idx = starts[:, None] + col[None, :]
    idx = jnp.clip(idx, 0, g.edge_values.shape[0] - 1)
    vals = g.edge_values[idx]
    return jnp.where(col[None, :] < lens[:, None], vals, 0.0)


def degree_buckets(g: CSRGraph, base: int = LANE) -> list[tuple[int, np.ndarray]]:
    """Host-side: group vertices into power-of-two capacity buckets.

    Returns [(cap, vertex_ids), ...] with cap ∈ {base, 2·base, 4·base, ...},
    covering every vertex with degree > 0. Padding waste per bucket ≤ 2×.
    """
    deg = np.asarray(g.degrees)
    out: list[tuple[int, np.ndarray]] = []
    cap = base
    lo = 1
    while lo <= max(int(deg.max()) if deg.size else 0, 1):
        sel = np.nonzero((deg >= lo) & (deg <= cap))[0]
        if sel.size:
            out.append((cap, sel.astype(np.int32)))
        lo = cap + 1
        cap *= 2
    return out


def edge_list(g: CSRGraph) -> np.ndarray:
    """(E, 2) directed edge array (host), in CSR order."""
    indptr = np.asarray(g.indptr)
    indices = np.asarray(g.indices)[: g.num_edges]
    src = np.repeat(np.arange(g.num_vertices, dtype=np.int32),
                    np.diff(indptr).astype(np.int64))
    return np.stack([src, indices], axis=1)
