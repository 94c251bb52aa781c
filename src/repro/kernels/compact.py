"""Segmented prefix-scatter compaction as a Pallas kernel.

The Pallas twin of ``core.batch.batch_compact_rows``: per row, an inclusive
prefix sum over the keep mask assigns each survivor its output slot, and the
scatter is realised branch-free as a one-hot gather — out[t] = Σ_j a[j] ·
[keep[j] ∧ pos[j] == t] — which maps onto the VPU/MXU (a 0/1 matrix times
the key vector) instead of a data-dependent store. O(B·cap·out_cap) compares
but O(B·cap) *data movement*, vs the masked sort's O(B·cap·log²cap) compare
network AND movement; on TPU the one-hot never leaves VMEM.

This is the compaction the fused level kernels' epilogue wants to share a
pass with (mark -> scan -> scatter without an HBM round-trip). Rows travel
as (B, 1, cap) so each whole-row block is TPU-tileable; the prefix sum is a
triangle-masked column sum (VPU, exact) rather than ``jnp.cumsum``. Both the
prefix triangle and the one-hot are (cap, cap)-sized: rows beyond ~1k keys
must be tiled (carry the running prefix across tiles) to stay inside the
~16 MB VMEM budget — a ROADMAP follow-on, as is profiling it against the
log-step shift-add formulation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.stream import SENTINEL


def _compact_rows_kernel(out_cap: int, a_ref, keep_ref, out_ref, cnt_ref):
    a = a_ref[0]                                          # (1, cap)
    keep = ((keep_ref[0] > 0) & (a != SENTINEL)).astype(jnp.int32)
    cap = a.shape[1]
    src = jax.lax.broadcasted_iota(jnp.int32, (cap, cap), 0)
    dst = jax.lax.broadcasted_iota(jnp.int32, (cap, cap), 1)
    # inclusive prefix sum -> survivor slots, (1, cap)
    pos = jnp.sum(jnp.where(src <= dst, jnp.transpose(keep), 0), axis=0,
                  keepdims=True) - 1
    total = jnp.sum(keep, axis=1, keepdims=True)          # (1, 1)
    slot = jax.lax.broadcasted_iota(jnp.int32, (1, out_cap), 1)
    onehot = (jnp.transpose(keep) > 0) & (jnp.transpose(pos) == slot)
    gathered = jnp.sum(jnp.where(onehot, jnp.transpose(a), 0), axis=0,
                       keepdims=True)                     # (1, out_cap)
    out_ref[0] = jnp.where(slot < total, gathered, SENTINEL)
    cnt_ref[0] = total


@functools.partial(jax.jit, static_argnames=("out_cap", "interpret"))
def compact_rows_pallas(a, keep, out_cap: int, *, interpret: bool):
    """Front-pack each row's kept keys -> (rows (B, out_cap), counts (B,)).

    Bit-identical to ``core.batch.batch_compact_rows`` (tested) under the
    same monotonicity precondition: ``a`` rows sorted, ``keep`` selects.
    """
    B, cap = a.shape
    kernel = functools.partial(_compact_rows_kernel, out_cap)
    rows, cnt = pl.pallas_call(
        kernel,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, 1, cap), lambda bi: (bi, 0, 0)),
            pl.BlockSpec((1, 1, cap), lambda bi: (bi, 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, 1, out_cap), lambda bi: (bi, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda bi: (bi, 0, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((B, 1, out_cap), jnp.int32),
            jax.ShapeDtypeStruct((B, 1, 1), jnp.int32),
        ),
        interpret=interpret,
    )(a[:, None, :], keep.astype(jnp.int32)[:, None, :])
    return rows[:, 0, :], cnt[:, 0, 0]
