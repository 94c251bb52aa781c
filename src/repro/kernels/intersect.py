"""Batched bounded sorted-set intersection — the IntersectX IU as a Pallas kernel.

TPU adaptation of the paper's Intersection Unit (§IV-C):

* The paper's IU walks two streams with a branchy two-pointer merge; its
  S-Cache prefetches 64-key slots because the access pattern is known. On a
  TPU there are no scalar branches worth taking: we compare whole 128-key
  VMEM tiles against each other on the VPU — an all-pairs (TA x TB) equality
  mask — which is branch-free and saturates the vector unit.

* Sorted-ness makes most tile pairs disjoint. We precompute, per (row,
  A-tile), the first overlapping B-tile and the number of overlapping
  B-tiles (one vmapped searchsorted over tile boundary keys) and feed both
  tables through *scalar prefetch*, so the grid's index_map only ever DMAs
  B-tiles that can intersect: the S-Cache prefetcher reborn as a static
  schedule. B-tile DMA obeys the merge bound O((|A|+|B|)/T) per row.

* Early termination (the R3 bound operand, §III-B) zeroes the visit count of
  every A-tile whose minimum exceeds the bound — whole tiles are skipped,
  the same data-movement saving the paper gets by retiring the instruction
  early — and in-tile keys >= bound are masked.

Two kernels share the schedule:
  count: Σ matches (S_INTER.C / S_SUB.C via |A|-count)
  mark:  per-A-slot match bitmask (uint8) — S_INTER materialisation is then
         a cheap XLA scan-compaction over the mask (the kernel owns the
         O(n·m) compare work; XLA owns the data movement it already fuses).

Multi-operand levels (``intersect_multi_pallas``) fuse k B-stream operands
into ONE grid pass — the §IV-F translation buffer's whole µop sequence for a
level as a single dispatch, instead of one mark kernel per INTER/SUB
reference. The k-operand contract:

  * ``bs`` is (k, B, cap_b): the k reference streams, stacked; refs gathered
    at different capacities are SENTINEL-padded to a common cap_b (padding
    keeps rows sorted, so each ref's tile schedule stays valid);
  * ``pol`` is a static length-k tuple of 1 (S_INTER: keep members) / 0
    (S_SUB: keep non-members). Polarity is folded into a per-slot weighted
    hit score — +1 per INTER hit, -(k+1) per SUB hit — so ``score ==
    #INTER refs`` iff every INTER ref matched and no SUB ref did; one int32
    accumulator replaces k boolean mask combines;
  * each ref gets its own prefetched tile schedule (lo/nv are (k, B, nA)),
    so per-ref B-tile DMA still obeys the merge bound and the R3/lb
    whole-tile skipping — one *dispatch*, k tile-schedules;
  * the bound window (lbound < key < bound), the per-row bound-0 row kill
    and the per-item injectivity ``excludes`` (B, E) are applied in the
    kernel's finalize step, which emits both the keep mask and the
    survivor count in the same pass (no second kernel for S_*.C).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.stream import SENTINEL

TA = 128  # A-tile keys (paper slot = 64 keys; we use the TPU lane width)
TB = 128  # B-tile keys


def tile_schedule(a: jax.Array, b: jax.Array, bounds: jax.Array,
                  lbounds: jax.Array | None = None):
    """Per (row, A-tile) overlap table: (lo_tile, n_visits), both (B, nA).

    lo = first B-tile containing a key >= max(tile_min, lbound+1);
    n  = #B-tiles holding keys in [that, min(tile_max, bound-1)].

    ``lbounds`` is the per-row exclusive *lower* bound (the plan's
    ``LevelOp.lb``, e.g. three-chain's b > a): A-tiles entirely <= lbound
    are skipped whole, mirroring the R3 upper-bound early termination.
    """
    cap_b = b.shape[1]
    a_lo = a[:, ::TA]                                   # (B, nA) tile minima
    a_hi = a[:, TA - 1:: TA]                            # (B, nA) tile maxima
    eff_lo = a_lo if lbounds is None else \
        jnp.maximum(a_lo, lbounds[:, None] + 1)
    lo_idx = jax.vmap(jnp.searchsorted)(b, eff_lo)
    eff_hi = jnp.minimum(a_hi, bounds[:, None] - 1)
    hi_idx = jax.vmap(lambda bb, x: jnp.searchsorted(bb, x, side="right"))(b, eff_hi)
    lo_t = (lo_idx // TB).astype(jnp.int32)
    hi_t = ((hi_idx + TB - 1) // TB).astype(jnp.int32)
    nv = jnp.maximum(hi_t - lo_t, 0)
    # whole-tile early termination: A-tile entirely >= bound, entirely
    # <= lbound, or all-sentinel
    dead = (a_lo >= jnp.minimum(bounds[:, None], SENTINEL))
    if lbounds is not None:
        dead = dead | (a_hi <= lbounds[:, None])
    nv = jnp.where(dead, 0, nv).astype(jnp.int32)
    lo_t = jnp.minimum(lo_t, max(cap_b // TB - 1, 0))
    return lo_t, nv


# ---------------------------------------------------------------------------
# TPU layout (Mosaic)
# ---------------------------------------------------------------------------
# A grid step takes a block of R = 8 rows (one sublane tile): the (8, 128)
# A tile of those rows and one (8, 128) B tile column that every row of the
# block visits. Mosaic tiles the last two dims of a block in (8, 128) units,
# so these blocks — and (8, 1) blocks of per-row scalars — are legal where
# single-row (1, 128) blocks are not. In the kernel each row's B tile turns
# into a (128, 1) column through one transpose of the block, its (128, 128)
# match mask reduces to a (1, 128) hit row, and accumulators are stored as
# vectors (VMEM takes no scalar stores).
#
# The block visits the union of its rows' B-tile windows (``_block_windows``),
# each tile once. That is exact: a row's matches lie inside its own window
# (tile_schedule), and a tile outside it can only hold keys that match
# nothing in the A tile or fall outside the row's bound window, which the
# kernel masks anyway. The grid has cap_b / TB visit steps, the widest a
# union window can be; steps past a block's window repeat its last tile
# (no DMA) and accumulate nothing, but the compare itself runs on every
# step, so the compare work is rows x cap_a x cap_b whatever the windows.
#
# The scalar-prefetched (lo, n) tables live in SMEM, flattened to 1-D (a
# 2-D SMEM array pads its minor dim) and bounded per pallas_call by
# SMEM_TABLE_BYTES: a larger batch is split into row blocks, one
# pallas_call each, inside the caller's jit (``_by_rows``).

R = 8                       # rows per grid step (the TPU sublane count)
SMEM_TABLE_BYTES = 512 << 10


def rows_per_call(n_rows: int, n_schedules: int, n_a_tiles: int) -> int:
    """Rows (a multiple of R) one pallas_call may take so that its
    ``n_schedules`` (lo, n) int32 table pairs of ``n_a_tiles`` entries per
    R-row block fit SMEM_TABLE_BYTES."""
    per_block = 2 * 4 * n_schedules * n_a_tiles
    return R * max(1, min(-(-n_rows // R), SMEM_TABLE_BYTES // per_block))


def _by_rows(call, block_rows: int, args: tuple, row_axes: tuple):
    """``call(*args)`` over row blocks of at most ``block_rows`` rows (the
    row axis of each arg in ``row_axes``; a multiple of R); the outputs, a
    tuple of arrays with rows on axis 0, are concatenated back."""
    n = args[0].shape[row_axes[0]]
    if block_rows >= n:
        return call(*args)
    calls = -(-n // block_rows)
    step = R * -(-n // (R * calls))              # equal blocks, <= 2 shapes
    outs = [call(*(jax.lax.slice_in_dim(x, lo, min(lo + step, n), axis=ax)
                   for x, ax in zip(args, row_axes)))
            for lo in range(0, n, step)]
    return tuple(jnp.concatenate(parts, axis=0) for parts in zip(*outs))


def _pad_rows(x, axis: int, fill):
    """Pad the row axis of ``x`` to a multiple of R with ``fill``."""
    pad = -x.shape[axis] % R
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=fill)


def _block_windows(lo_t, nv):
    """Per-row (..., B, nA) tile windows -> per R-row block (..., B/R, nA)
    union windows (lo, n), flattened to 1-D for SMEM."""
    *lead, B, n_a = lo_t.shape
    lo = lo_t.reshape(*lead, B // R, R, n_a)
    nv = nv.reshape(*lead, B // R, R, n_a)
    live = nv > 0
    first = jnp.min(jnp.where(live, lo, jnp.iinfo(jnp.int32).max), axis=-2)
    end = jnp.max(jnp.where(live, lo + nv, 0), axis=-2)
    n = jnp.maximum(end - first, 0)
    return jnp.where(n > 0, first, 0).reshape(-1), n.reshape(-1)


def _b_col(lo, n, idx):
    """B-tile column of a visit: the window's tiles in order, then the last
    one repeated (a resident tile: no DMA) for steps past the window."""
    return lambda j: lo[idx] + jnp.minimum(j, jnp.maximum(n[idx] - 1, 0))


def _row_masks(a, b):
    """(R, TA) A tiles and (R, TB) B tiles -> per row r the (TB, TA) mask
    m_r[t, s] = (a[r, s] == b[r, t])."""
    bt = jnp.transpose(b)                                   # (TB, R)
    return [bt[:, r:r + 1] == a[r:r + 1, :] for r in range(R)]


def _stack_rows(rows, dtype=jnp.int32):
    """R (1, N) rows -> one (R, N) array (selects; no sublane concat)."""
    sub = jax.lax.broadcasted_iota(jnp.int32, (R, rows[0].shape[1]), 0)
    out = jnp.zeros((R, rows[0].shape[1]), dtype)
    for r, row in enumerate(rows):
        out = jnp.where(sub == r, row, out)
    return out


def _hits(masks):
    """Per-row match masks -> (R, TA) int32 0/1 hit per A slot."""
    return _stack_rows([jnp.max(m.astype(jnp.int32), axis=0, keepdims=True)
                        for m in masks])


def _row_sum(x):
    """(R, N) -> (R, 1) int32 row sums."""
    return jnp.sum(x.astype(jnp.int32), axis=1, keepdims=True)


def _window(a, bound_ref, lbound_ref):
    """Live A slots inside each row's (lbound, bound) window, (R, TA)."""
    return (a != SENTINEL) & (a < bound_ref[...]) & (a > lbound_ref[...])


def _pair_kernel(want_mark: bool, want_count: bool, lo_ref, nv_ref, a_ref,
                 b_ref, bound_ref, lbound_ref, *out_refs):
    """Bounded S_INTER over one (row block, A-tile, visit) grid step: the
    per-slot mark, the row counts, or both (the fused expand: one pass over
    the tile schedule feeds the compaction mask and the survivor count)."""
    bi, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    a = a_ref[...]
    hit = jnp.where(_window(a, bound_ref, lbound_ref),
                    _hits(_row_masks(a, b_ref[...])), 0)
    live = j < nv_ref[bi * pl.num_programs(1) + i]
    if want_mark:
        mark_ref = out_refs[0]

        @pl.when(j == 0)
        def _init_mark():
            mark_ref[...] = jnp.zeros_like(hit)

        @pl.when(live)
        def _acc_mark():
            mark_ref[...] = mark_ref[...] | hit
    if want_count:
        cnt_ref = out_refs[-1]

        @pl.when((i == 0) & (j == 0))
        def _init_cnt():
            cnt_ref[...] = jnp.zeros_like(cnt_ref)

        # B-rows are sorted sets and each tile is visited once: an A-slot
        # matches at most once, so summing per-visit hits never double counts
        @pl.when(live)
        def _acc_cnt():
            cnt_ref[...] += _row_sum(hit)


def _bounds_of(B, bounds, lbounds):
    ub = jnp.full((B,), SENTINEL, jnp.int32) if bounds is None \
        else jnp.asarray(bounds, jnp.int32)
    lb = jnp.full((B,), -1, jnp.int32) if lbounds is None \
        else jnp.asarray(lbounds, jnp.int32)         # ids >= 0: no-op bound
    return ub, lb


def _pair_call(a, b, bounds, lbounds, interpret, want_mark: bool,
               want_count: bool):
    """Run ``_pair_kernel`` -> (mark (B, cap_a) | None, counts (B,) | None)."""
    B, cap_a = a.shape
    cap_b = b.shape[1]
    assert cap_a % TA == 0 and cap_b % TB == 0, "streams are LANE-padded"
    bounds, lbounds = _bounds_of(B, bounds, lbounds)
    # padding rows: all-SENTINEL streams, bound 0 (dead)
    a, b = _pad_rows(a, 0, SENTINEL), _pad_rows(b, 0, SENTINEL)
    bounds, lbounds = _pad_rows(bounds, 0, 0), _pad_rows(lbounds, 0, -1)
    lo_t, nv = tile_schedule(a, b, bounds, lbounds)
    n_a = cap_a // TA
    a_spec = pl.BlockSpec((R, TA), lambda bi, i, j, lo, nv: (bi, i))
    row_spec = pl.BlockSpec((R, 1), lambda bi, i, j, lo, nv: (bi, 0))
    b_spec = pl.BlockSpec(
        (R, TB),
        lambda bi, i, j, lo, nv: (bi, _b_col(lo, nv, bi * n_a + i)(j)))
    kernel = functools.partial(_pair_kernel, want_mark, want_count)

    def call(lo_t, nv, a, b, bounds, lbounds):
        n = a.shape[0]
        out_specs, out_shape = [], []
        if want_mark:
            out_specs.append(a_spec)
            out_shape.append(jax.ShapeDtypeStruct((n, cap_a), jnp.int32))
        if want_count:
            out_specs.append(row_spec)
            out_shape.append(jax.ShapeDtypeStruct((n, 1), jnp.int32))
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(n // R, n_a, cap_b // TB),
                in_specs=[a_spec, b_spec, row_spec, row_spec],
                out_specs=tuple(out_specs),
            ),
            out_shape=tuple(out_shape),
            interpret=interpret,
        )(*_block_windows(lo_t, nv), a, b, bounds[:, None], lbounds[:, None])

    outs = _by_rows(call, rows_per_call(a.shape[0], 1, n_a),
                    (lo_t, nv, a, b, bounds, lbounds), (0,) * 6)
    mark = outs[0][:B] if want_mark else None
    counts = outs[-1][:B, 0] if want_count else None
    return mark, counts


@functools.partial(jax.jit, static_argnames=("interpret",))
def intersect_count_pallas(a, b, bounds=None, *, interpret: bool,
                           lbounds=None):
    """counts[i] = |{k ∈ A_i ∩ B_i : lbounds[i] < k < bounds[i]}|
    (paper S_INTER.C; the lower bound is the beyond-paper lb operand)."""
    return _pair_call(a, b, bounds, lbounds, interpret, False, True)[1]


@functools.partial(jax.jit, static_argnames=("interpret",))
def intersect_expand_pallas(a, b, bounds=None, *, interpret: bool,
                            lbounds=None):
    """Fused S_INTER mark + count in one schedule pass -> (mark, counts).

    The device expand_compact path consumes both outputs; fusing them halves
    the B-tile DMA traffic vs running the mark and count kernels separately.
    """
    return _pair_call(a, b, bounds, lbounds, interpret, True, True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def intersect_mark_pallas(a, b, bounds=None, *, interpret: bool,
                          lbounds=None):
    """mark[i, s] = 1 iff A_i[s] ∈ B_i and lbounds[i] < A_i[s] < bounds[i].

    S_INTER materialisation = sort-compact A over this mask (ops.xinter)."""
    return _pair_call(a, b, bounds, lbounds, interpret, True, False)[0]


# ---------------------------------------------------------------------------
# fused multi-operand level kernel (k B-streams per grid step), with an
# optional value lane (the SVPU, §IV-E)
# ---------------------------------------------------------------------------

AGG_IDS = {"sum": 0, "max": 1, "min": 2}
F32_MAX = 3.4e38      # masked-reduce identities (finite: inf trips asserts)
_AGG_IDENTITY = (0.0, -F32_MAX, F32_MAX)


def _multi_kernel(n_refs: int, n_inter: int, op_id, lo_ref, nv_ref, a_ref, b_ref, bound_ref, lbound_ref,
                  excl_ref, *refs):
    """One level's whole µop sequence in a single pass.

    Grid (B/R, nA, k, cap_b/TB): for each (row block, A-tile) the k refs
    stream their scheduled B-tiles through VMEM one after another while the
    A-tile and its score accumulator stay resident. The score is a weighted
    hit sum (+1 INTER, -(k+1) SUB; sorted sets hit at most once per ref, so
    the sum never aliases): score == n_inter  <=>  all INTER refs matched,
    no SUB ref did. The final grid step folds the bound window and the
    injectivity excludes and converts the score into the 0/1 keep mask +
    count.

    With ``op_id`` set, a value lane rides the SAME tile schedule (refs then
    carry a_vals, b_vals, scale, the vals output and two (R, TA) scratch
    lanes): per visited tile the masked column sum of each row's B values
    recovers each A-slot's matched value for the current ref (sorted sets:
    at most one match, so the sum *is* the matched value, exactly). ``vsum``
    accumulates that per ref across its visits; at each INTER ref's last
    visit it folds into the running product ``vprod``. The finalize step
    multiplies in the slot's own feed value and the per-row prefix scale,
    masks by keep, and reduces into the per-row aggregate with the op's
    identity — zero extra B-tile DMA."""
    if op_id is None:
        mark_ref, cnt_ref = refs
    else:
        (aval_ref, bval_ref, scale_ref, mark_ref, cnt_ref, val_ref,
         vsum_ref, vprod_ref) = refs
    bi, i, r, j = (pl.program_id(0), pl.program_id(1), pl.program_id(2),
                   pl.program_id(3))
    n_blocks, n_a = pl.num_programs(0), pl.num_programs(1)
    last = j == pl.num_programs(3) - 1
    a = a_ref[...]
    masks = _row_masks(a, b_ref[0])
    weight = jnp.where(r < n_inter, 1, -(n_refs + 1))
    live = j < nv_ref[(r * n_blocks + bi) * n_a + i]

    @pl.when((r == 0) & (j == 0))
    def _init_mark():
        mark_ref[...] = jnp.zeros_like(a)

    @pl.when((i == 0) & (r == 0) & (j == 0))
    def _init_cnt():
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    @pl.when(live)
    def _acc():
        mark_ref[...] += _hits(masks) * weight

    if op_id is not None:
        @pl.when((r == 0) & (j == 0))
        def _init_vprod():
            vprod_ref[...] = jnp.ones_like(vprod_ref)

        @pl.when(j == 0)
        def _init_vsum():
            vsum_ref[...] = jnp.zeros_like(vsum_ref)

        @pl.when((i == 0) & (r == 0) & (j == 0))
        def _init_val():
            val_ref[...] = jnp.full(val_ref.shape, _AGG_IDENTITY[op_id],
                                    jnp.float32)

        @pl.when(live)
        def _acc_val():
            bv = jnp.transpose(bval_ref[0])                     # (TB, R)
            vsum_ref[...] += _stack_rows(
                [jnp.sum(jnp.where(m, bv[:, s:s + 1], 0.0), axis=0,
                         keepdims=True) for s, m in enumerate(masks)],
                jnp.float32)

        @pl.when((r < n_inter) & last)
        def _fold():
            vprod_ref[...] *= vsum_ref[...]

    @pl.when((r == n_refs - 1) & last)
    def _finalize():
        valid = _window(a, bound_ref, lbound_ref)
        for e in range(excl_ref.shape[1]):
            valid = valid & (a != excl_ref[:, e:e + 1])
        keep = valid & (mark_ref[...] == n_inter)
        mark_ref[...] = keep.astype(jnp.int32)
        cnt_ref[...] += _row_sum(keep)
        if op_id is None:
            return
        contrib = aval_ref[...] * vprod_ref[...] * scale_ref[...]
        masked = jnp.where(keep, contrib, _AGG_IDENTITY[op_id])
        if op_id == 0:
            val_ref[...] += jnp.sum(masked, axis=1, keepdims=True)
        elif op_id == 1:
            val_ref[...] = jnp.maximum(val_ref[...], jnp.max(
                masked, axis=1, keepdims=True))
        else:
            val_ref[...] = jnp.minimum(val_ref[...], jnp.min(
                masked, axis=1, keepdims=True))


def _multi_call(a, bs, pol, bounds, lbounds, excludes, interpret,
                op_id=None, a_vals=None, b_vals=None, scale=None):
    """Shared body of the k-operand kernel entries -> (mark, counts[, vals])."""
    assert bs.ndim == 3 and bs.shape[0] == len(pol) >= 1, \
        "bs must be (k, B, cap_b) matching pol"
    assert all(p == 1 for p in pol[:sum(pol)]) \
        and all(p == 0 for p in pol[sum(pol):]), "pol must be INTER-first"
    B, cap_a = a.shape
    cap_b = bs.shape[2]
    assert cap_a % TA == 0 and cap_b % TB == 0, "streams are LANE-padded"
    bounds, lbounds = _bounds_of(B, bounds, lbounds)
    if excludes is None:
        excludes = jnp.full((B, 1), -1, jnp.int32)   # ids >= 0: no-op
    excludes = jnp.asarray(excludes, jnp.int32)
    # padding rows: all-SENTINEL streams, bound 0 (dead)
    args = [_pad_rows(a, 0, SENTINEL), _pad_rows(bs, 1, SENTINEL),
            _pad_rows(bounds, 0, 0), _pad_rows(lbounds, 0, -1),
            _pad_rows(excludes, 0, -1)]
    lo_t, nv = jax.vmap(tile_schedule, in_axes=(None, 0, None, None))(
        *args[:4])                                   # (k, B, nA) each
    k = len(pol)
    n_a = cap_a // TA
    n_excl = excludes.shape[1]
    kernel = functools.partial(_multi_kernel, k, int(sum(pol)), op_id)
    a_spec = pl.BlockSpec((R, TA), lambda bi, i, r, j, lo, nv: (bi, i))
    row_spec = pl.BlockSpec((R, 1), lambda bi, i, r, j, lo, nv: (bi, 0))
    excl_spec = pl.BlockSpec((R, n_excl), lambda bi, i, r, j, lo, nv: (bi, 0))

    def call(lo_t, nv, a, bs, bounds, lbounds, excludes, *vals):
        n = a.shape[0]
        b_spec = pl.BlockSpec(
            (1, R, TB),
            lambda bi, i, r, j, lo, nv: (
                r, bi, _b_col(lo, nv, (r * (n // R) + bi) * n_a + i)(j)))
        in_specs = [a_spec, b_spec, row_spec, row_spec, excl_spec]
        out_specs = [a_spec, row_spec]
        out_shape = [jax.ShapeDtypeStruct((n, cap_a), jnp.int32),
                     jax.ShapeDtypeStruct((n, 1), jnp.int32)]
        args = [a, bs, bounds[:, None], lbounds[:, None], excludes]
        scratch = []
        if op_id is not None:
            av, bv, sc = vals
            in_specs += [a_spec, b_spec, row_spec]
            out_specs.append(row_spec)
            out_shape.append(jax.ShapeDtypeStruct((n, 1), jnp.float32))
            args += [av, bv, sc[:, None]]
            scratch = [pltpu.VMEM((R, TA), jnp.float32)] * 2
        outs = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(n // R, n_a, k, cap_b // TB),
                in_specs=in_specs,
                out_specs=tuple(out_specs),
                scratch_shapes=scratch,
            ),
            out_shape=tuple(out_shape),
            interpret=interpret,
        )(*_block_windows(lo_t, nv), *args)
        return (outs[0],) + tuple(o[:, 0] for o in outs[1:])

    axes = (1, 1, 0, 1, 0, 0, 0)
    if op_id is not None:
        args += [_pad_rows(a_vals, 0, 0.0), _pad_rows(b_vals, 1, 0.0),
                 _pad_rows(jnp.asarray(scale, jnp.float32), 0, 0.0)]
        axes += (0, 1, 0)
    outs = _by_rows(call, rows_per_call(args[0].shape[0], k, n_a),
                    (lo_t, nv, *args), axes)
    return tuple(o[:B] for o in outs)


@functools.partial(jax.jit, static_argnames=("pol", "interpret"))
def intersect_multi_pallas(a, bs, pol, bounds=None, *, interpret: bool,
                           lbounds=None, excludes=None):
    """Fused k-operand level: conjunctive mark + count in ONE schedule pass.

    mark[i, s] = 1 iff   A_i[s] ∈ B^r_i   for every INTER ref r (pol[r]=1)
               and       A_i[s] ∉ B^r_i   for every SUB ref r  (pol[r]=0)
               and       lbounds[i] < A_i[s] < bounds[i]
               and       A_i[s] != excludes[i, e]  for every e;
    counts[i] = Σ_s mark[i, s].

    ``bs`` is the (k, B, cap_b) operand stack (see module docstring for the
    padding contract); ``pol`` the static INTER/SUB polarity tuple, which
    must be sorted INTER-first (the engine stacks refs that way; the kernel
    exploits it to derive the per-ref weight from the ref index alone).
    Replacing the per-ref ``xmark`` loop, every B-tile is DMA'd exactly once
    across the whole level instead of once per mark dispatch re-reading the
    A-tiles, and the count rides the same pass (S_*.C for free).
    """
    return _multi_call(a, bs, pol, bounds, lbounds, excludes, interpret)


@functools.partial(jax.jit, static_argnames=("pol", "op", "interpret"))
def intersect_multi_agg_pallas(a, bs, pol, a_vals, b_vals, scale, op="sum",
                               bounds=None, *, interpret: bool, lbounds=None,
                               excludes=None):
    """``intersect_multi_pallas`` + SVPU value lane -> (mark, counts, vals).

    Same k-operand membership contract (see ``intersect_multi_pallas``);
    additionally each kept slot s of row i carries the value

        a_vals[i, s] * Π_{INTER refs r} matched_val_r(i, s) * scale[i]

    and ``vals[i]`` reduces the kept slots' values with ``op`` (``sum`` /
    ``max`` / ``min``; empty rows yield the op identity — 0.0 / -3.4e38 /
    +3.4e38 — callers mask with ``counts``). ``b_vals`` is the (k, B,
    cap_b) value stack aligned with ``bs`` (SUB refs' values are ignored);
    ``scale`` is the per-row (B,) prefix product the caller folded outside
    the kernel. One dispatch, the same B-tile DMA schedule as the
    unweighted kernel — the value lane is pure VPU work on tiles already
    resident."""
    assert b_vals.shape == bs.shape and a_vals.shape == a.shape
    return _multi_call(a, bs, pol, bounds, lbounds, excludes, interpret,
                       AGG_IDS[op], a_vals, b_vals, scale)
