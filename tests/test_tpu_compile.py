"""The Pallas kernels compile for a TPU v5e at the shapes the engine sends.

Nothing runs: each test compiles a kernel entry (``interpret=False``) for a
v5e chip that is described, not attached, and checks that the executable
holds the expected number of Mosaic kernels (``tpu_custom_call``) — one per
SMEM-bounded row block. Interpret-mode tests cannot see what this catches:
untileable block shapes, scalar stores to VMEM, unsupported shape casts and
scalar-prefetch tables that overflow SMEM.

Shapes: rows are the engine's wave chunk (``choose_chunk``), caps its
degree buckets — ``mico`` (padded max degree 2048: 2048 rows, caps
128..2048), the low-degree chunk (16384 rows at cap 256) and the
``youtube`` twin's hub bucket (max degree 18517: cap 32768, 128 rows).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.kernels import intersect as K
from repro.kernels.bitmap import bitmap_and_count_pallas
from repro.kernels.compact import compact_rows_pallas
from repro.kernels.svinter import vinter_pallas
from repro.mining.engine import choose_chunk

MICO = [(2048, cap) for cap in (128, 512, 2048)]
WIDE = [(choose_chunk(256), 256), (choose_chunk(32768), 32768)]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            t = topologies.get_topology_desc(platform="tpu",
                                             topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means no TPU compiler
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a described chip's executables cannot be read back from the
        # persistent cache: keep these compiles out of it
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        yield t
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    """Compile ``fn(*args)`` for the described chip -> HLO text."""
    return jax.jit(fn).lower(*args).compile().as_text()


def _kernels(hlo: str) -> int:
    return hlo.count('custom_call_target="tpu_custom_call"')


def _blocks(rows: int, k: int, cap: int) -> int:
    """Row blocks (= pallas_calls) the SMEM bound splits a batch into."""
    return -(-rows // K.rows_per_call(rows, k, cap // K.TA))


def test_engine_chunks_are_the_tested_shapes():
    assert WIDE == [(16384, 256), (128, 32768)]
    assert choose_chunk(2048) == 2048


@pytest.mark.parametrize("rows,cap", MICO + WIDE)
def test_expand_compiles(one_chip, rows, cap):
    def S(shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    fn = functools.partial(K.intersect_expand_pallas, interpret=False)
    hlo = _compile(fn, S((rows, cap)), S((rows, cap)), S((rows,)))
    assert _kernels(hlo) == _blocks(rows, 1, cap)


@pytest.mark.parametrize("k", [2, 3])
@pytest.mark.parametrize("rows,cap", MICO[-1:] + WIDE)
def test_multi_compiles(one_chip, rows, cap, k):
    def S(shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    pol = (1,) * (k - 1) + (0,)

    def fn(a, bs, ub, lb, ex):
        return K.intersect_multi_pallas(a, bs, pol, ub, interpret=False,
                                        lbounds=lb, excludes=ex)
    hlo = _compile(fn, S((rows, cap)), S((k, rows, cap)), S((rows,)),
                   S((rows,)), S((rows, 2)))
    assert _kernels(hlo) == _blocks(rows, k, cap)


def test_smem_bound_splits_an_explicit_large_chunk(one_chip):
    """A user chunk of 2048 rows on the hub bucket overflows SMEM in one
    call; the kernel entry splits it into row blocks that each fit."""
    rows, cap, k = 2048, 32768, 3

    def S(shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    def fn(a, bs, ub):
        return K.intersect_multi_pallas(a, bs, (1, 1, 0), ub,
                                        interpret=False)
    hlo = _compile(fn, S((rows, cap)), S((k, rows, cap)), S((rows,)))
    assert _kernels(hlo) == _blocks(rows, k, cap) > 1


@pytest.mark.parametrize("op", ["sum", "max"])
@pytest.mark.parametrize("rows,cap", MICO[-1:] + WIDE)
def test_multi_agg_compiles(one_chip, rows, cap, op):
    def S(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def fn(a, bs, av, bv, sc, ub):
        return K.intersect_multi_agg_pallas(a, bs, (1, 1), av, bv, sc, op,
                                            ub, interpret=False)
    hlo = _compile(fn, S((rows, cap)), S((2, rows, cap)),
                   S((rows, cap), jnp.float32),
                   S((2, rows, cap), jnp.float32), S((rows,), jnp.float32),
                   S((rows,)))
    assert _kernels(hlo) == _blocks(rows, 2, cap)


def test_other_kernels_compile(one_chip):
    def S(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    rows, cap = 2048, 512
    for fn, args in [
        (functools.partial(K.intersect_count_pallas, interpret=False),
         (S((rows, cap)), S((rows, cap)), S((rows,)))),
        (functools.partial(K.intersect_mark_pallas, interpret=False),
         (S((rows, cap)), S((rows, cap)), S((rows,)))),
        (functools.partial(vinter_pallas, op="mac", interpret=False),
         (S((rows, cap)), S((rows, cap), jnp.float32), S((rows, cap)),
          S((rows, cap), jnp.float32))),
        (functools.partial(vinter_pallas, op="min", interpret=False),
         (S((rows, cap)), S((rows, cap), jnp.float32), S((rows, cap)),
          S((rows, cap), jnp.float32))),
        (functools.partial(bitmap_and_count_pallas, interpret=False),
         (S((rows, 4096)), S((rows, 4096)))),
        (functools.partial(compact_rows_pallas, out_cap=256, interpret=False),
         (S((rows, cap)), S((rows, cap), jnp.bool_))),
    ]:
        assert _kernels(_compile(fn, *args)) == 1, fn


def test_kernel_inside_shard_map_compiles_for_four_chips(topo):
    """The mesh runner's form: the fused expand inside ``jax.shard_map``
    over a 4-chip mining axis, rows split across the chips."""
    mesh = Mesh(np.array(topo.devices), ("mine",),
                axis_types=(AxisType.Auto,))
    rows, cap = 4 * 2048, 512
    feed = NamedSharding(mesh, P("mine"))

    def S(shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=feed)

    def body(a, b, ub):
        mark, cnt = K.intersect_expand_pallas(a, b, ub, interpret=False)
        return mark, jax.lax.psum(jnp.sum(cnt), "mine")
    fn = jax.shard_map(body, mesh=mesh, in_specs=(P("mine"),) * 3,
                       out_specs=(P("mine"), P()), check_vma=False)
    hlo = _compile(fn, S((rows, cap)), S((rows, cap)), S((rows,)))
    assert _kernels(hlo) == 1 and "all-reduce" in hlo
