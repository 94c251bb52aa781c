"""Plain host reference for triangle queries.

``values`` gives one value, 1, per triangle, each triangle once. The
harness reduces them (``ixbench.compare``). Nothing of the program is
imported or reused.
"""
from __future__ import annotations

import numpy as np

from ixbench.listing import orient, triangles


def values(hg) -> np.ndarray:
    n = sum(p_uv.size for p_uv, _ in triangles(orient(hg)))
    return np.ones(n)
