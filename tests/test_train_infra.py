"""Optimizer, checkpoint round-trips, fault-tolerance control plane, data
pipeline determinism, compression numerics."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.distributed.fault_tolerance import (HeartbeatMonitor, StepGuard,
                                               balanced_vertex_partition,
                                               elastic_remesh)
from repro.train.checkpoint import CheckpointManager
from repro.train.data import SyntheticLMData
from repro.train.optimizer import (OptConfig, _dequantize, _quantize,
                                   adamw_init, adamw_update)


def test_adamw_decreases_quadratic():
    cfg = OptConfig(lr=0.1, weight_decay=0.0)
    params = {"w": jnp.asarray([5.0, -3.0])}
    state = adamw_init(params, cfg)
    for _ in range(200):
        grads = {"w": 2 * params["w"]}
        params, state, _ = adamw_update(grads, state, params, cfg.lr, cfg)
    assert float(jnp.abs(params["w"]).max()) < 0.3


def test_quantize_roundtrip_error_bounded():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(37, 300)).astype(np.float32))
    q, s = _quantize(x)
    back = _dequantize(q, s, x.shape)
    err = np.abs(np.asarray(back) - np.asarray(x))
    blockmax = np.abs(np.asarray(x)).max()
    assert err.max() <= blockmax / 127.0 + 1e-6


def test_adamw8bit_tracks_fp32():
    cfgs = [OptConfig(lr=0.05, weight_decay=0.0, state_bits=b)
            for b in (32, 8)]
    p0 = {"w": jnp.asarray(np.random.default_rng(1)
                           .normal(size=(64,)).astype(np.float32))}
    outs = []
    for cfg in cfgs:
        p = dict(p0)
        st = adamw_init(p, cfg)
        for _ in range(50):
            g = {"w": 2 * p["w"]}
            p, st, _ = adamw_update(g, st, p, cfg.lr, cfg)
        outs.append(np.asarray(p["w"]))
    assert np.abs(outs[0] - outs[1]).max() < 0.05


def test_checkpoint_roundtrip(tmp_path):
    cm = CheckpointManager(str(tmp_path), keep_last=2)
    params = {"a": jnp.arange(6.0).reshape(2, 3),
              "b": {"c": jnp.ones((4,), jnp.int32)}}
    cm.save(3, params, data_state={"step": 3, "seed": 0})
    cm.save(7, params, data_state={"step": 7, "seed": 0})
    cm.save(11, params, data_state={"step": 11, "seed": 0})
    assert cm.steps() == [7, 11]          # pruned to keep_last
    like = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                        params)
    got, _, manifest = cm.restore(None, like)
    assert manifest["step"] == 11
    np.testing.assert_array_equal(np.asarray(got["a"]),
                                  np.asarray(params["a"]))
    np.testing.assert_array_equal(np.asarray(got["b"]["c"]),
                                  np.asarray(params["b"]["c"]))


def test_step_guard():
    g = StepGuard(max_consecutive=2)
    assert g.ok({"loss": 1.0, "gnorm": 1.0})
    assert not g.ok({"loss": float("nan"), "gnorm": 1.0})
    assert not g.ok({"loss": 1.0, "gnorm": float("inf")})
    assert g.should_restore
    assert g.ok({"loss": 1.0, "gnorm": 1.0})
    assert not g.should_restore


def test_heartbeat_and_stragglers():
    hb = HeartbeatMonitor(num_workers=4, timeout=10.0)
    now = 1000.0
    for w in range(4):
        hb.beat(w, step_time=1.0 if w != 2 else 5.0, now=now)
    assert hb.dead(now=now + 5) == []
    hb.beat(0, now=now + 20)
    dead = hb.dead(now=now + 20)
    assert set(dead) == {1, 2, 3}
    assert hb.stragglers() == [2]


def test_elastic_remesh():
    shape, names, dropped = elastic_remesh(32, 16, model_parallel=16)
    assert shape == (32, 16) and dropped == 0
    shape, names, dropped = elastic_remesh(23, 16, model_parallel=16)
    assert shape == (16, 16) and dropped == (23 * 16 - 256)
    with pytest.raises(RuntimeError):
        elastic_remesh(0, 8)


def test_balanced_partition():
    deg = np.random.default_rng(3).integers(1, 100, size=500)
    assign = balanced_vertex_partition(deg, 8)
    cost = deg.astype(float) ** 2
    loads = np.bincount(assign, weights=cost, minlength=8)
    assert loads.max() / loads.mean() < 1.15


def test_data_pipeline_deterministic():
    d1 = SyntheticLMData(vocab_size=97, seq_len=16, global_batch=4, seed=5)
    d2 = SyntheticLMData(vocab_size=97, seq_len=16, global_batch=4, seed=5)
    b1, b2 = d1.batch_at(42), d2.batch_at(42)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    # restore path
    d2.restore({"step": 9, "seed": 5})
    assert d2.step == 9
    # bigram structure is learnable: targets mostly follow the affine map
    t, y = b1["tokens"], b1["targets"]
    match = ((t * 31 + 17) % 97 == y).mean()
    assert match > 0.8


def test_compressed_mean_single_device():
    """Wire-format exactness: int8 psum on a 1-device mesh == quantised id."""
    from repro.distributed.compression import compressed_mean
    from jax.sharding import AxisType, PartitionSpec as P
    mesh = jax.make_mesh((1,), ("pod",), axis_types=(AxisType.Auto,))
    x = jnp.asarray(np.random.default_rng(0).normal(size=(64,))
                    .astype(np.float32))

    def body(x):
        return compressed_mean(x, "pod")[0]

    got = jax.shard_map(body, mesh=mesh, in_specs=(P(),), out_specs=P(),
                        check_vma=False)(x)
    err = np.abs(np.asarray(got) - np.asarray(x))
    assert err.max() <= np.abs(np.asarray(x)).max() / 127 + 1e-6
