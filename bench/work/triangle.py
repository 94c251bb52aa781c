"""Canonical stream work of a triangle query, from the graph alone.

The canonical plan intersects N(u) and N(v) once for every edge {u, v},
so it reads deg u + deg v int32 keys per edge. Nothing here reads the
program's plan, chunks, capacities or padding.
"""
from __future__ import annotations

from ixbench.listing import degrees


def stream_bytes(hg) -> int:
    deg = degrees(hg)
    elems = int((deg[hg.edges[:, 0]] + deg[hg.edges[:, 1]]).sum())
    return elems * 4
