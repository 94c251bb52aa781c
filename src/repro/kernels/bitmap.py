"""Bitmap intersection path — a beyond-paper, TPU-only optimization.

The paper's IU merges sorted key lists; its hardware cannot exploit dense
neighborhoods. The VPU can: encode a high-degree vertex's neighbor list as
an adjacency bitmap (32 keys per int32 word), then |A ∩ B| is AND +
popcount at 32 keys/lane/op — asymptotically worse (O(V/32) regardless of
list length) but with a constant so small it wins whenever both lists are
dense in the key space. ``benchmarks/bench_kernels.py`` sweeps the
merge-vs-bitmap crossover density.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.stream import SENTINEL

TW = 256  # words per tile (256 * 4B = 1KB per row-tile; lane-aligned)


def keys_to_bitmap(keys: jax.Array, num_bits: int) -> jax.Array:
    """(B, cap) sentinel-padded sorted keys -> (B, W) int32 bitmap words.

    Keys are unique per row, so every (word, bit) pair is unique and the
    scatter-ADD of disjoint single-bit values is exactly bitwise OR.
    """
    words = -(-num_bits // 32)
    w_pad = -(-words // TW) * TW
    valid = keys != SENTINEL
    word_idx = jnp.where(valid, keys // 32, 0).astype(jnp.int32)
    bit = jnp.where(valid,
                    jnp.left_shift(jnp.int32(1), (keys % 32).astype(jnp.int32)),
                    0).astype(jnp.int32)
    out = jnp.zeros(keys.shape[:-1] + (w_pad,), jnp.int32)
    row = jnp.arange(keys.shape[0])[:, None]
    return out.at[row, word_idx].add(bit)


def _and_count_kernel(a_ref, b_ref, out_ref):
    anded = a_ref[0] & b_ref[0]                         # (1, TW)
    cnt = jnp.sum(jax.lax.population_count(anded), axis=1, keepdims=True)

    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[0] = jnp.zeros((1, 1), jnp.int32)

    out_ref[0] += cnt


@functools.partial(jax.jit, static_argnames=("interpret",))
def bitmap_and_count_pallas(a_words: jax.Array, b_words: jax.Array, *,
                            interpret: bool) -> jax.Array:
    """counts[i] = popcount(A_i & B_i) over int32 word rows (rows travel as
    (B, 1, W) so each (1, 1, TW) block is TPU-tileable)."""
    B, W = a_words.shape
    assert b_words.shape == (B, W) and W % TW == 0
    spec = pl.BlockSpec((1, 1, TW), lambda i, j: (i, 0, j))
    out = pl.pallas_call(
        _and_count_kernel,
        grid=(B, W // TW),
        in_specs=[spec, spec],
        out_specs=pl.BlockSpec((1, 1, 1), lambda i, j: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, 1, 1), jnp.int32),
        interpret=interpret,
    )(a_words[:, None, :], b_words[:, None, :])
    return out[:, 0, 0]


def bitmap_and_count_ref(a_words: jax.Array, b_words: jax.Array) -> jax.Array:
    """Pure-jnp oracle."""
    return jnp.sum(jax.lax.population_count(a_words & b_words), axis=1)
