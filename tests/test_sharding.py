"""Sharding rules: divisibility-aware resolution, ZeRO axes, batch specs."""
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.distributed.sharding import (Axes, DEFAULT_RULES, FSDP_RULES,
                                        logical_to_physical,
                                        constrain)
from repro.train.optimizer import zero_axes


def mk_mesh(shape, names):
    # abstract mesh: resolution logic only needs axis sizes, no devices
    return AbstractMesh(tuple(shape), tuple(names))


def test_divisibility_drop():
    mesh = mk_mesh((16, 16), ("data", "model"))
    # kv_heads=4 does not divide 16 -> replicated
    spec = logical_to_physical(Axes("batch", "seq", "kv_heads", "head_dim"),
                               mesh, DEFAULT_RULES, (256, 128, 4, 64))
    assert spec == P("data", None, None, None)
    # kv_heads=16 divides -> sharded
    spec = logical_to_physical(Axes("batch", "seq", "kv_heads", "head_dim"),
                               mesh, DEFAULT_RULES, (256, 128, 16, 64))
    assert spec == P("data", None, "model", None)


def test_axis_used_once():
    mesh = mk_mesh((16, 16), ("data", "model"))
    spec = logical_to_physical(Axes("vocab", "d_ff"), mesh, DEFAULT_RULES,
                               (160, 160))
    # both want 'model'; only the first gets it
    assert spec == P("model", None)


def test_multi_pod_batch():
    mesh = mk_mesh((2, 16, 16), ("pod", "data", "model"))
    spec = logical_to_physical(Axes("batch", "seq", "embed"), mesh,
                               DEFAULT_RULES, (256, 4096, 1024))
    assert spec == P(("pod", "data"), None, None)
    spec_f = logical_to_physical(Axes("embed", "d_ff"), mesh, FSDP_RULES,
                                 (1024, 4096))
    assert spec_f == P("data", "model")


def test_zero_axes_picks_replicated_dim():
    mesh = mk_mesh((16, 16), ("data", "model"))
    za = zero_axes(Axes("embed", "d_ff"), (1024, 4096), mesh, DEFAULT_RULES)
    # d_ff takes model; embed (replicated, divisible) gets the opt axes
    assert za == ("opt", "d_ff")
    spec = logical_to_physical(za, mesh, DEFAULT_RULES, (1024, 4096))
    assert spec == P("data", "model")


def test_constrain_noop_without_mesh():
    import jax.numpy as jnp
    x = jnp.ones((4, 4))
    assert constrain(x, "batch", "embed") is x
