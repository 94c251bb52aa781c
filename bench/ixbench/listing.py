"""Plain host listing of triangles, shared by the references and the
canonical work functions. It imports nothing of the program.

Each edge is oriented from the lower to the higher (degree, id) rank, which
bounds every out-degree by about sqrt(2E); a triangle is then found once,
at its lowest-ranked vertex, as a wedge u -> v, u -> w whose closing edge
v -> w exists.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from .graphs import HostGraph

WEDGE_BLOCK = 1 << 22


def degrees(hg: HostGraph) -> np.ndarray:
    return np.bincount(hg.edges.ravel(), minlength=hg.n).astype(np.int64)


@dataclasses.dataclass(frozen=True)
class Oriented:
    """The rank-oriented graph as an out-CSR over ranks: ``src[i] -> dst[i]``
    sorted by (src, dst), so ``keys = src * n + dst`` is sorted, and
    ``vertex[r]`` the id of the vertex ranked r."""

    n: int
    src: np.ndarray
    dst: np.ndarray
    keys: np.ndarray
    indptr: np.ndarray
    vertex: np.ndarray

    def has(self, a: np.ndarray, b: np.ndarray) -> np.ndarray:
        """Whether each oriented edge a -> b exists."""
        keys = self.keys
        k = a * self.n + b
        if keys.size == 0:
            return np.zeros(k.shape, dtype=bool)
        return keys[np.minimum(np.searchsorted(keys, k), keys.size - 1)] == k


def orient(hg: HostGraph) -> Oriented:
    n = hg.n
    deg = degrees(hg)
    vertex = np.lexsort((np.arange(n), deg))
    rank = np.empty(n, dtype=np.int64)
    rank[vertex] = np.arange(n)
    a, b = rank[hg.edges[:, 0]], rank[hg.edges[:, 1]]
    src, dst = np.minimum(a, b), np.maximum(a, b)
    order = np.lexsort((dst, src))
    indptr = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(np.bincount(src, minlength=n), out=indptr[1:])
    src, dst = src[order], dst[order]
    return Oriented(n=n, src=src, dst=dst, keys=src * n + dst,
                    indptr=indptr, vertex=vertex)


def _ranges(starts: np.ndarray, counts: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """(owner, value): for each i, the values starts[i] .. starts[i] +
    counts[i] - 1, tagged with i."""
    total = int(counts.sum())
    owner = np.repeat(np.arange(starts.size), counts)
    first = np.cumsum(counts) - counts
    return owner, starts[owner] + (np.arange(total) - first[owner])


def triangles(og: Oriented):
    """Yield blocks of triangles as out-CSR positions (p_uv, p_uw) of two of
    their edges: rank u < v < w, each triangle exactly once."""
    m = og.src.size
    pos = np.arange(m, dtype=np.int64)
    later = og.indptr[og.src + 1] - pos - 1
    cum = np.cumsum(later)
    lo = 0
    while lo < m:
        hi = int(np.searchsorted(cum, (cum[lo - 1] if lo else 0) + WEDGE_BLOCK,
                                 side="right"))
        hi = max(hi, lo + 1)
        owner, p_uw = _ranges(pos[lo:hi] + 1, later[lo:hi])
        p_uv = pos[lo:hi][owner]
        ok = og.has(og.dst[p_uv], og.dst[p_uw])
        yield p_uv[ok], p_uw[ok]
        lo = hi
