"""Pallas TPU kernels for the paper's compute hot spot: sorted-set intersection.

  intersect.py  batched bounded intersection (count / match-mark) with the
                scalar-prefetched tile-overlap schedule (the S-Cache
                prefetcher as a static schedule)
  svinter.py    S_VINTER: intersect keys then MAC the matched value pairs
  bitmap.py     beyond-paper bitmap path: AND + popcount for dense rows
  ops.py        backend dispatch (pallas on TPU, interpret on CPU, xla ref)
  ref.py        pure-jnp oracles
"""
from .ops import xinter, xinter_count, xvinter_mac, xbitmap_count

__all__ = ["xinter", "xinter_count", "xvinter_mac", "xbitmap_count"]
