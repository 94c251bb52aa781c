"""Plain host reference for 4-clique queries.

A 4-clique is found once, from its lowest-ranked triangle u < v < w (in the
rank order of ``ixbench.listing``), as a fourth vertex x > w joined to all
three. ``values`` gives one value, 1, per 4-clique.
"""
from __future__ import annotations

import numpy as np

from ixbench.listing import _ranges, orient, triangles


def values(hg) -> np.ndarray:
    og = orient(hg)
    n = 0
    for p_uv, p_uw in triangles(og):
        u, v, x3 = og.src[p_uv], og.dst[p_uv], og.dst[p_uw]
        owner, p_wx = _ranges(og.indptr[x3], og.indptr[x3 + 1] - og.indptr[x3])
        x = og.dst[p_wx]
        n += int((og.has(u[owner], x) & og.has(v[owner], x)).sum())
    return np.ones(n)
