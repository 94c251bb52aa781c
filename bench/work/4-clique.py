"""Canonical stream work of a 4-clique query, from the graph alone.

The canonical plan lists cliques a > b > c > d by vertex id:

* level 1, every edge {a > b}: S_ab = N(a) ∩ N(b) ∩ [0, b), reading
  deg a + deg b keys;
* level 2, every c in S_ab, that is every triangle a > b > c:
  S_ab ∩ N(c) ∩ [0, c), reading |S_ab| + deg c keys.

Keys are int32. Nothing here reads the program's plan, chunks, capacities
or padding.
"""
from __future__ import annotations

import numpy as np

from ixbench.listing import degrees, orient, triangles


def stream_bytes(hg) -> int:
    deg = degrees(hg)
    elems = int((deg[hg.edges[:, 0]] + deg[hg.edges[:, 1]]).sum())
    og = orient(hg)
    tri = [np.stack([og.vertex[og.src[p]], og.vertex[og.dst[p]],
                     og.vertex[og.dst[q]]], axis=1)
           for p, q in triangles(og)]
    if tri:
        t = -np.sort(-np.concatenate(tri), axis=1)       # a > b > c
        _, inv, s_ab = np.unique(t[:, 0] * hg.n + t[:, 1],
                                 return_inverse=True, return_counts=True)
        elems += int(s_ab[inv].sum() + deg[t[:, 2]].sum())
    return elems * 4
