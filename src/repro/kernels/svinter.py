"""S_VINTER as a Pallas kernel: intersect keys, MAC value pairs on the MXU.

The paper's SVPU (§IV-E) collects (val0, val1) pairs through the load queue
and feeds a scalar FMA per matched key. The TPU-native form turns the whole
tile-pair into two dense ops: with the (TA x TB) match mask M (a permutation
sub-matrix, keys being strict sets),

        Σ_matched va·vb  =  vaᵀ · M · vb

i.e. one masked column sum (M·vb: M has at most one 1 per A slot, so the
sum is the matched value, exactly) and one VPU dot — the sparse MAC becomes
dense vector work with zero gather/scatter. MAX/MIN reductions use the mask
on the VPU directly.

Uses the same scalar-prefetched tile-overlap schedule as intersect.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.stream import SENTINEL
from .intersect import (R, TA, TB, _b_col, _block_windows, _by_rows,
                        _pad_rows, _row_masks, _stack_rows, rows_per_call,
                        tile_schedule)


def _vinter_kernel(op: str, lo_ref, nv_ref, ak_ref, av_ref, bk_ref, bv_ref,
                   out_ref):
    """One (R-row block, A-tile, visit) step; layout as in intersect.py."""
    bi, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    ak, av = ak_ref[...], av_ref[...]                  # (R, TA)
    bv = jnp.transpose(bv_ref[...])                    # (TB, R)
    parts = []
    for r, m in enumerate(_row_masks(ak, bk_ref[...])):
        m = m & (ak[r:r + 1, :] != SENTINEL)
        col = bv[:, r:r + 1]
        if op == "mac":
            # vaᵀ·M·vb: M·vb as a masked column sum (exact — at most one
            # match per A slot), then the VPU dot with va
            mv = jnp.sum(jnp.where(m, col, 0.0), axis=0, keepdims=True)
            parts.append(jnp.sum(av[r:r + 1, :] * mv, axis=1, keepdims=True))
        else:
            pair = (jnp.maximum if op == "max" else jnp.minimum)(
                av[r:r + 1, :], col)
            parts.append(jnp.sum(jnp.sum(jnp.where(m, pair, 0.0), axis=0,
                                         keepdims=True), axis=1,
                                 keepdims=True))
    contrib = _stack_rows(parts, jnp.float32)          # (R, 1)

    @pl.when((i == 0) & (j == 0))
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(j < nv_ref[bi * pl.num_programs(1) + i])
    def _acc():
        out_ref[...] += contrib


@functools.partial(jax.jit, static_argnames=("op", "interpret"))
def vinter_pallas(a_keys, a_vals, b_keys, b_vals, op: str = "mac", *,
                  interpret: bool):
    """out[i] = Σ_{k ∈ A_i ∩ B_i} op(valA_i[k], valB_i[k]) — batched S_VINTER."""
    B, cap_a = a_keys.shape
    cap_b = b_keys.shape[1]
    args = (_pad_rows(a_keys, 0, SENTINEL), _pad_rows(a_vals, 0, 0.0),
            _pad_rows(b_keys, 0, SENTINEL), _pad_rows(b_vals, 0, 0.0))
    bounds = jnp.full((args[0].shape[0],), SENTINEL, jnp.int32)  # unbounded
    lo_t, nv = tile_schedule(args[0], args[2], bounds)
    n_a = cap_a // TA
    a_spec = pl.BlockSpec((R, TA), lambda bi, i, j, lo, nv: (bi, i))
    b_spec = pl.BlockSpec(
        (R, TB), lambda bi, i, j, lo, nv: (bi, _b_col(lo, nv, bi * n_a + i)(j)))
    kern = functools.partial(_vinter_kernel, op)

    def call(lo_t, nv, ak, av, bk, bv):
        n = ak.shape[0]
        out = pl.pallas_call(
            kern,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(n // R, n_a, cap_b // TB),
                in_specs=[a_spec, a_spec, b_spec, b_spec],
                out_specs=pl.BlockSpec((R, 1),
                                       lambda bi, i, j, lo, nv: (bi, 0)),
            ),
            out_shape=jax.ShapeDtypeStruct((n, 1), jnp.float32),
            interpret=interpret,
        )(*_block_windows(lo_t, nv), ak, av, bk, bv)
        return (out[:, 0],)

    return _by_rows(call, rows_per_call(args[0].shape[0], 1, n_a),
                    (lo_t, nv, *args), (0,) * 6)[0][:B]
