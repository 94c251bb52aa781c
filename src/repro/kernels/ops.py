"""Backend dispatch for the stream-intersection kernels.

Public entry points used by the engine and the sparse layer. ``backend``:
  'xla'     pure-jnp reference path (fast on XLA:CPU, the semantic oracle)
  'pallas'  Pallas kernels — compiled on TPU, interpret-mode on CPU
  'auto'    pallas on TPU, xla elsewhere (interpret mode is a correctness
            vehicle, not a fast path)

Whether a kernel runs in interpret mode is decided here and only here
(``_interpret``): the kernel entries take ``interpret`` with no default.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.batch import (batch_compact_rows, batch_compact_scan,
                              batch_inter, batch_inter_compact,
                              batch_inter_count, batch_level_agg,
                              batch_level_compact, batch_level_count,
                              batch_member_mark, batch_sub_compact,
                              batch_sub_count, batch_vinter)
from repro.core.stream import SENTINEL
from .bitmap import bitmap_and_count_pallas, bitmap_and_count_ref, keys_to_bitmap
from .intersect import (intersect_count_pallas, intersect_expand_pallas,
                        intersect_mark_pallas, intersect_multi_agg_pallas,
                        intersect_multi_pallas)
from .svinter import vinter_pallas


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    """Pallas interpret mode: everywhere except on a TPU."""
    return not _on_tpu()


def _resolve(backend: str) -> str:
    if backend == "auto":
        return "pallas" if _on_tpu() else "xla"
    return backend


def xinter_count(a, b, bounds=None, backend: str = "auto", lbounds=None):
    """Batched bounded S_INTER.C (``lbounds`` = exclusive lower bound; both
    bounds ride the Pallas tile schedule, so out-of-range tiles never DMA)."""
    backend = _resolve(backend)
    if backend == "xla":
        return batch_inter_count(a, b, bounds, lbounds=lbounds)
    return intersect_count_pallas(a, b, bounds, interpret=_interpret(),
                                  lbounds=lbounds)


def xinter(a, b, bounds=None, out_cap: int | None = None, backend: str = "auto",
           lbounds=None):
    """Batched bounded S_INTER -> (rows, counts).

    Pallas path: the kernel produces the match mask (the O(n·m) compare hot
    spot); compaction is a fused XLA sort over the masked keys — keeping
    data movement in the compiler's hands, compute in the kernel's."""
    backend = _resolve(backend)
    if backend == "xla":
        return batch_inter(a, b, bounds, out_cap=out_cap, lbounds=lbounds)
    mark = intersect_mark_pallas(a, b, bounds, interpret=_interpret(),
                                 lbounds=lbounds)
    cap = out_cap or min(a.shape[1], b.shape[1])
    rows, counts = batch_compact_rows(a, mark > 0, cap)
    return rows, counts


@functools.partial(jax.jit, static_argnames=("out_cap", "out_items", "interpret"))
def _xinter_compact_pallas(a, b, bounds, out_cap: int, out_items: int,
                           interpret: bool, lbounds):
    mark, counts = intersect_expand_pallas(a, b, bounds, interpret=interpret,
                                           lbounds=lbounds)
    rows, _, src, verts, total, maxc = batch_compact_scan(
        a, mark > 0, out_cap, out_items)
    return rows, counts, src, verts, total, maxc


def xinter_compact(a, b, bounds=None, out_cap: int | None = None,
                   out_items: int | None = None, backend: str = "auto",
                   lbounds=None):
    """Fused bounded S_INTER + worklist compaction, fully device-resident.

    One dispatch produces everything the next wavefront level needs:

      rows   (B, out_cap)    per-source survivor streams S_{l+1}
      counts (B,)            per-source survivor counts
      src    (out_items,)    compacted item -> source row index
      verts  (out_items,)    compacted item extension vertex (0 = padding)
      total  ()              live item count   (host-synced at level bounds)
      maxc   ()              max survivor count (sizes the next capacity)

    This replaces the engine's host ``np.nonzero`` + re-upload round-trip:
    the Pallas kernel owns the compare work, XLA owns the prefix-sum
    scatter (``batch_compact_scan`` — O(B·cap), no sort), and only two
    scalars ever cross to the host.
    """
    backend = _resolve(backend)
    cap = out_cap or min(a.shape[1], b.shape[1])
    items = out_items or a.shape[0] * cap
    if backend == "xla":
        return batch_inter_compact(a, b, bounds, cap, items, lbounds=lbounds)
    return _xinter_compact_pallas(a, b, bounds, cap, items,
                                  interpret=_interpret(), lbounds=lbounds)


def xmark(a, b, backend: str = "auto"):
    """Batched membership mask: mark[i, s] = A_i[s] ∈ B_i (live slots only).

    The plan interpreter's multi-operand µop primitive: a level with several
    INTER/SUB references AND-combines one mark per reference (the §IV-F
    translation buffer issuing one stream instruction per operand pair).
    Pallas path reuses the tile-skipping mark kernel; bounds are applied by
    the caller so the same mark serves both INTER (mask) and SUB (~mask).
    """
    backend = _resolve(backend)
    if backend == "xla":
        return batch_member_mark(a, b)
    return intersect_mark_pallas(a, b, None, interpret=_interpret()) > 0


def _sub_window(a, bounds, lbounds):
    """The complement's value window (lbound, bound) as a keep mask.

    SUB bounds live OUTSIDE the mark kernel: the kernel's bound operand masks
    *matches*, which is the wrong polarity for a complement (an out-of-window
    key must be dropped whether or not it matched)."""
    ub = jnp.full((a.shape[0],), SENTINEL, jnp.int32) if bounds is None \
        else jnp.asarray(bounds, jnp.int32)
    lb = jnp.full((a.shape[0],), -1, jnp.int32) if lbounds is None \
        else jnp.asarray(lbounds, jnp.int32)
    return (a != SENTINEL) & (a < ub[:, None]) & (a > lb[:, None])


def xsub_count(a, b, bounds=None, backend: str = "auto", lbounds=None):
    """Batched bounded S_SUB.C:
    counts[i] = |{k ∈ A_i \\ B_i : lbounds[i] < k < bounds[i]}|."""
    backend = _resolve(backend)
    if backend == "xla":
        return batch_sub_count(a, b, bounds, lbounds=lbounds)
    mark = intersect_mark_pallas(a, b, None, interpret=_interpret())
    keep = (mark == 0) & _sub_window(a, bounds, lbounds)
    return jnp.sum(keep, axis=1, dtype=jnp.int32)


@functools.partial(jax.jit, static_argnames=("out_cap", "out_items", "interpret"))
def _xsub_compact_pallas(a, b, bounds, out_cap: int, out_items: int,
                         interpret: bool, lbounds):
    # the mark kernel runs UNBOUNDED here (see _sub_window on polarity)
    mark = intersect_mark_pallas(a, b, None, interpret=interpret)
    keep = (mark == 0) & _sub_window(a, bounds, lbounds)
    return batch_compact_scan(a, keep, out_cap, out_items)


def xsub_compact(a, b, bounds=None, out_cap: int | None = None,
                 out_items: int | None = None, backend: str = "auto",
                 lbounds=None):
    """Fused bounded S_SUB + worklist compaction — ``xinter_compact``'s twin
    for SUB levels (induced non-edge constraints), same output contract:
    (rows, counts, src, verts, total, maxc), fully device-resident.
    """
    backend = _resolve(backend)
    cap = out_cap or a.shape[1]
    items = out_items or a.shape[0] * cap
    if backend == "xla":
        return batch_sub_compact(a, b, bounds, cap, items, lbounds=lbounds)
    return _xsub_compact_pallas(a, b, bounds, cap, items,
                                interpret=_interpret(), lbounds=lbounds)


@functools.partial(jax.jit,
                   static_argnames=("pol", "out_cap", "out_items",
                                    "interpret"))
def _xlevel_compact_pallas(a, bs, pol, bounds, lbounds, excludes,
                           out_cap: int, out_items: int, interpret: bool):
    mark, _ = intersect_multi_pallas(a, bs, pol, bounds, interpret=interpret,
                                     lbounds=lbounds, excludes=excludes)
    return batch_compact_scan(a, mark > 0, out_cap, out_items)


def xlevel_count(a, bs, pol, bounds=None, backend: str = "auto",
                 lbounds=None, excludes=None):
    """Fused multi-operand level count — one dispatch for a whole
    INTER/SUB µop sequence.

    counts[i] = |{k ∈ A_i : k ∈ B^r_i ∀ INTER r, k ∉ B^r_i ∀ SUB r,
                  lbounds[i] < k < bounds[i], k ∉ excludes[i]}|

    ``bs`` is the (k, B, cap_b) operand stack (refs SENTINEL-padded to a
    common capacity), ``pol`` the static INTER-first polarity tuple — see
    ``kernels.intersect`` for the k-operand contract. ``pol = ()`` (no
    membership refs, pure window/injectivity level) is served by the XLA
    form on every backend: there is no stream work for a kernel to fuse.
    Replaces the per-ref ``xmark`` + combine loop: k mark dispatches (each
    re-reading the A-tiles) become one pass over one shared schedule.
    """
    backend = _resolve(backend)
    if backend == "xla" or not pol:
        return batch_level_count(a, bs, pol, bounds, lbounds, excludes)
    _, cnt = intersect_multi_pallas(a, bs, pol, bounds,
                                    interpret=_interpret(), lbounds=lbounds,
                                    excludes=excludes)
    return cnt


def xlevel_compact(a, bs, pol, bounds=None, out_cap: int | None = None,
                   out_items: int | None = None, backend: str = "auto",
                   lbounds=None, excludes=None):
    """Fused multi-operand level + worklist compaction, device-resident.

    ``xinter_compact``'s contract — (rows, counts, src, verts, total, maxc)
    — for a level with any number of INTER/SUB references: the multi-operand
    kernel produces the conjunctive keep mask + count in one pass
    (``intersect_multi_pallas``) and its epilogue is the O(B·cap)
    prefix-sum scatter (``batch_compact_scan``), replacing k mark dispatches
    + an O(B·cap·log) masked sort."""
    backend = _resolve(backend)
    cap = out_cap or a.shape[1]
    items = out_items or a.shape[0] * cap
    if backend == "xla" or not pol:
        return batch_level_compact(a, bs, pol, bounds, lbounds, excludes,
                                   cap, items)
    return _xlevel_compact_pallas(a, bs, pol, bounds, lbounds, excludes,
                                  cap, items, interpret=_interpret())


def xlevel_agg(a, bs, pol, a_vals, b_vals, scale, op: str = "sum",
               bounds=None, backend: str = "auto", lbounds=None,
               excludes=None):
    """Fused multi-operand level count + SVPU value aggregate (§IV-E) —
    (counts, vals) in ONE dispatch on the SAME tile schedule as
    ``xlevel_count``.

    Membership contract is ``xlevel_count``'s; additionally each kept slot
    carries ``a_vals * Π_{INTER r} matched_val_r * scale[row]`` and
    ``vals[i]`` reduces row i's kept slots with ``op`` ('sum'/'max'/'min';
    op identity for empty rows — callers mask with counts). ``b_vals`` is
    the (k, B, cap_b) value stack aligned with ``bs`` (0.0 where keys are
    SENTINEL; SUB refs' values ignored). ``pol = ()`` levels are served by
    the XLA form on every backend, like ``xlevel_count``.

    The point of the shared entry: the value lane rides the membership
    dispatch — a weighted query issues exactly the kernel dispatches and
    feed passes of its unweighted twin (gated in ci_gate.py --values)."""
    backend = _resolve(backend)
    if backend == "xla" or not pol:
        return batch_level_agg(a, bs, pol, a_vals, b_vals, scale, op=op,
                               bounds=bounds, lbounds=lbounds,
                               excludes=excludes)
    _, cnt, val = intersect_multi_agg_pallas(
        a, bs, pol, a_vals, b_vals, scale, op=op, bounds=bounds,
        interpret=_interpret(), lbounds=lbounds, excludes=excludes)
    return cnt, val


def xvinter(a_keys, a_vals, b_keys, b_vals, op: str = "mac",
            backend: str = "auto"):
    """Batched S_VINTER (SVPU, §IV-E): per-row reduce over value pairs of
    intersected keys — the shared value-intersect entry the sparse layer
    (``sparse.spmm`` / ``sparse.ttv``) routes through.

    ``op``: 'mac' (Σ va·vb — sparse dot), 'max'/'min' (Σ of per-pair
    max/min over matches). Backend dispatch like every other entry here:
    'xla' is ``core.batch.batch_vinter``, 'pallas' is the mask-MAC kernel
    (``kernels.svinter``), parity-tested in tests/test_sparse.py."""
    backend = _resolve(backend)
    if backend == "xla":
        return batch_vinter(a_keys, a_vals, b_keys, b_vals, op=op)
    return vinter_pallas(a_keys, a_vals, b_keys, b_vals, op=op,
                         interpret=_interpret())


def xvinter_mac(a_keys, a_vals, b_keys, b_vals, op: str = "mac",
                backend: str = "auto"):
    """Deprecated alias of ``xvinter`` (kept for source compatibility)."""
    return xvinter(a_keys, a_vals, b_keys, b_vals, op=op, backend=backend)


def xbitmap_count(a_words, b_words, backend: str = "auto"):
    """Bitmap-path intersection count (beyond-paper dense path)."""
    backend = _resolve(backend)
    if backend == "xla":
        return bitmap_and_count_ref(a_words, b_words)
    return bitmap_and_count_pallas(a_words, b_words, interpret=_interpret())


__all__ = ["xinter", "xinter_count", "xinter_compact", "xmark", "xsub_count",
           "xsub_compact", "xlevel_count", "xlevel_compact", "xlevel_agg",
           "xvinter", "xvinter_mac", "xbitmap_count", "keys_to_bitmap"]
