"""A run's graph, made on the host from its configuration and ``--seed``.

The generator is the benchmark's own copy of the repository's Holme-Kim
generator, so the data stays put whatever the program does to its own
generators (a test holds the copy to the program's at each configuration's
parameters).

What the seed changes, and what it keeps:

* The graph's structure is the configuration's: the generator at the
  configuration's ``vertices``, ``m_per_node``, ``tri_p`` and
  ``graph_seed``, whose realised size the configuration records, and every
  run mines that graph. A seed never changes a degree, a triangle or a
  clique.
* The seed relabels the vertices. ``orientation_preserving_shuffle`` swaps
  the ids of neighbouring, non-adjacent vertices at random, in several
  rounds. Such a swap keeps the order of the two ends of every edge, so
  each edge keeps its orientation (``v1 < v0``), its degree bucket and its
  place in the feed, while most ids move. Every seed therefore has the same
  feed sizes and executable shapes, in another order. A uniform shuffle
  would change the buckets, and with them the work and the compiled
  shapes, from seed to seed.
"""
from __future__ import annotations

import dataclasses

import numpy as np

SHUFFLE_ROUNDS = 8


@dataclasses.dataclass(frozen=True)
class HostGraph:
    """An undirected simple graph: each edge once as ``(lo, hi)``, lo < hi,
    sorted."""

    n: int
    edges: np.ndarray


def powerlaw_cluster(n: int, m_per_node: int, seed: int = 0,
                     tri_p: float = 0.3) -> np.ndarray:
    """Holme–Kim style preferential attachment with triangle closure
    (copied from the repository's generator)."""
    rng = np.random.default_rng(seed)
    m_per_node = max(1, m_per_node)
    repeated: list[int] = list(range(m_per_node))
    edges = []
    for v in range(m_per_node, n):
        chosen = rng.choice(len(repeated), size=m_per_node, replace=False)
        vs = {repeated[c] for c in chosen}
        for u in vs:
            edges.append((v, u))
            repeated.append(u)
            repeated.append(v)
            if rng.random() < tri_p and len(vs) > 1:
                # close a triangle through a random existing neighbor of u
                w = repeated[rng.integers(0, len(repeated))]
                if w != v and w != u:
                    edges.append((v, w))
                    repeated.append(w)
                    repeated.append(v)
    return np.asarray(edges, dtype=np.int64)


def canonical_edges(edges: np.ndarray) -> np.ndarray:
    """Each undirected edge once as (lo, hi), self-loops dropped, sorted."""
    e = np.asarray(edges, dtype=np.int64).reshape(-1, 2)
    e = e[e[:, 0] != e[:, 1]]
    e = np.sort(e, axis=1)
    return np.unique(e, axis=0)


def orientation_preserving_shuffle(edges: np.ndarray, n: int, seed: int,
                                   rounds: int = SHUFFLE_ROUNDS) -> np.ndarray:
    """``perm[old] = new``: a seeded relabelling that keeps, for every edge,
    which end has the larger id. Round r considers the id pairs (i, i + 1)
    with i = r mod 2, 2 + r mod 2, ... and swaps each with probability 1/2
    unless the two vertices are adjacent: only those two ids change places,
    so every other vertex compares to both as before."""
    rng = np.random.default_rng(seed)
    lo, hi = edges[:, 0], edges[:, 1]
    perm = np.arange(n, dtype=np.int64)
    inv = np.arange(n, dtype=np.int64)
    for r in range(rounds):
        a, b = perm[lo], perm[hi]
        near = np.abs(a - b) == 1
        adjacent = np.zeros(n, dtype=bool)
        adjacent[np.minimum(a, b)[near]] = True
        i = np.arange(r % 2, n - 1, 2)
        i = i[(rng.random(i.size) < 0.5) & ~adjacent[i]]
        inv[i], inv[i + 1] = inv[i + 1].copy(), inv[i].copy()
        perm[inv] = np.arange(n, dtype=np.int64)
    return perm


def generate(config: dict) -> np.ndarray:
    """The configuration's raw generator edges, before any relabelling."""
    if config["generator"] != "powerlaw_cluster":
        raise ValueError(f"unknown generator {config['generator']!r}")
    return powerlaw_cluster(int(config["vertices"]),
                            int(config["m_per_node"]),
                            seed=int(config["graph_seed"]),
                            tri_p=float(config["tri_p"]))


def make_graph(config: dict, seed: int) -> HostGraph:
    """The run's graph: the configuration's graph, relabelled by ``seed``."""
    n = int(config["vertices"])
    edges = canonical_edges(generate(config))
    perm = orientation_preserving_shuffle(edges, n, seed)
    return HostGraph(n=n, edges=canonical_edges(perm[edges]))
