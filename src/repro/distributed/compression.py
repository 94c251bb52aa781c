"""int8-compressed cross-pod gradient all-reduce with error feedback.

Data-parallel gradient synchronisation dominates the multi-pod collective
budget (the 'pod' axis rides the slow inter-pod links). We compress that
hop: per-tensor int8 quantisation inside a shard_map over the pod axis,
all-reduce in int32, dequantise, and keep the quantisation residual in an
error-feedback buffer added to the next step's gradient (so compression
error does not bias the optimizer, only delays information).

The intra-pod ('data' axis) reduction stays full precision — ICI is fast
and the paper-of-record tricks (1-bit Adam etc.) all compress only the slow
hop. EXPERIMENTS.md §Perf quantifies the collective-bytes saving from the
dry-run HLO (4x on the pod axis for f32 grads).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def _quant(x):
    scale = jnp.max(jnp.abs(x)) / 127.0 + 1e-12
    return jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8), scale


def compressed_mean(x: jax.Array, axis_name: str, err: jax.Array | None = None):
    """Mean over ``axis_name`` of x (+err), int8 on the wire.

    Returns (mean, new_err). Must run inside shard_map/pmap context where
    ``axis_name`` is bound."""
    n = jax.lax.psum(1, axis_name)
    xf = x.astype(jnp.float32)
    if err is not None:
        xf = xf + err
    q, scale = _quant(xf)
    total = jax.lax.psum(q.astype(jnp.int32), axis_name)
    scale_max = jax.lax.pmax(scale, axis_name)   # shared dequant scale
    mean = (total.astype(jnp.float32) * scale_max) / n
    new_err = xf - q.astype(jnp.float32) * scale  # local residual
    return mean.astype(x.dtype), new_err


def tree_compressed_mean(grads, mesh, axis_name: str, err_tree=None):
    """Compressed-mean every leaf over ``axis_name`` via one shard_map.

    Gradients entering here must be *partial* over the pod axis (i.e. the
    loss was averaged per pod); the call completes the DP reduction.
    """
    specs = jax.tree.map(lambda _: P(), grads)   # replicated within region

    def body(g_tree):
        return jax.tree.map(lambda g: compressed_mean(g, axis_name)[0], g_tree)

    fn = jax.shard_map(body, mesh=mesh, in_specs=(specs,), out_specs=specs,
                       check_vma=False)
    return fn(grads)
