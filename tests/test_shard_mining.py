"""Mesh-sharded mining: ``ShardedWaveRunner`` parity, feed, cache contracts.

The sharding contract (``mining.shard`` docstring) promises:

  * **parity** — counts from an S-way mesh session are bit-identical to the
    single-device session (same integer summands, psum'd as 16-bit limbs),
    and embeddings enumerate the same multiset;
  * **feed** — ``shard_edge_steps`` enumerates exactly the single-device
    edge multiset, round-robin dealing bounds per-step imbalance at one
    item, and ``stats["shard_feed_items"]`` accounts for every edge;
  * **reuse** — sharded executables live under a mesh-prefixed cache key
    (never colliding with unsharded traces) and repeated sharded queries
    retrace nothing;
  * **degeneracy** — ``mesh=1`` (or ``mesh=None``) is the plain unsharded
    runner, and unsupported runner modes are rejected loudly.

The partitioner and ``_finalize`` tests are host-only and run everywhere;
mesh tests skip unless enough devices are visible (on CPU:
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` — the CI mesh8 leg).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.graph import build_csr
from repro.graph.generators import clique_planted, erdos_renyi, \
    powerlaw_cluster
from repro.mining import plan as P
from repro.mining import reference
from repro.mining.engine import COUNT_LIMBS, WaveRunner, count_acc_add, \
    count_acc_value, half_edges
from repro.mining.session import ExecutableCache, Miner, mesh_signature
from repro.mining.shard import FEED_PARTITIONS, ShardedWaveRunner, \
    shard_edge_steps

needs8 = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs 8 devices "
           "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")
needs2 = pytest.mark.skipif(
    jax.device_count() < 2,
    reason="needs 2 devices "
           "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")


def wheel(n: int) -> np.ndarray:
    """Hub 0 joined to every rim vertex 1..n-1, rim a cycle: one extreme
    hub (degree n-1) plus n-1 triangles — the feed-skew stress shape."""
    hub = np.stack([np.zeros(n - 1, np.int64), np.arange(1, n)], axis=1)
    rim = np.stack([np.arange(1, n), np.arange(2, n + 1)], axis=1)
    rim[-1, 1] = 1
    return np.concatenate([hub, rim], axis=0)


GRAPHS = {
    "er": build_csr(erdos_renyi(60, 240, seed=3), 60),
    "plc": build_csr(powerlaw_cluster(50, 4, seed=5), 50),
    "cliq": build_csr(clique_planted(45, 120, (6, 5), seed=1), 45),
    "wheel": build_csr(wheel(40), 40),
}
TINY = build_csr(erdos_renyi(6, 5, seed=2), 6)       # fewer edges than shards

MOTIF_NAMES = list(P.FOUR_MOTIF_SHAPES)


def mesh_for(shards: int):
    from repro.distributed.sharding import make_mining_mesh
    return make_mining_mesh(shards)


# ---------------------------------------------------------------------------
# feed partitioner (host-only: runs on any device count)
# ---------------------------------------------------------------------------


def feed_edges(g, shards, mode):
    """Flatten a sharded feed back to its (src, dst) edge list."""
    out = []
    for _cap, v0, v1, n in shard_edge_steps(g, 64, shards, mode=mode):
        nb = v0.shape[0] // shards
        for s in range(shards):
            k = int(n[s])
            out.append(np.stack([v0[s * nb: s * nb + k],
                                 v1[s * nb: s * nb + k]], axis=1))
    return np.concatenate(out, axis=0) if out else np.zeros((0, 2), np.int64)


@pytest.mark.parametrize("mode", FEED_PARTITIONS)
@pytest.mark.parametrize("name", ["er", "wheel"])
def test_feed_preserves_the_edge_multiset(name, mode):
    """Both dealing modes enumerate exactly the single-device half-edge
    multiset — only the edge -> shard assignment differs."""
    g = GRAPHS[name]
    want = half_edges(g)
    got = feed_edges(g, 8, mode)
    assert got.shape == want.shape
    order = np.lexsort((got[:, 1], got[:, 0]))
    worder = np.lexsort((want[:, 1], want[:, 0]))
    np.testing.assert_array_equal(got[order], np.asarray(want)[worder])


def test_round_robin_per_step_imbalance_is_at_most_one():
    for name in ("plc", "wheel"):
        for _cap, _v0, _v1, n in shard_edge_steps(GRAPHS[name], 64, 8):
            assert int(n.max()) - int(n.min()) <= 1


def test_contiguous_partial_steps_pin_low_shards():
    """The foil mode fills shards front to back, so a partial super-step
    leaves the high shards empty — per-shard counts are non-increasing."""
    for _cap, _v0, _v1, n in shard_edge_steps(GRAPHS["wheel"], 64, 8,
                                              mode="contiguous"):
        assert all(int(n[s]) >= int(n[s + 1]) for s in range(7))


def test_round_robin_beats_contiguous_on_a_hub_graph():
    """Total feed balance (max/min items per shard): dealing spreads the
    hub's edge run across the mesh, the contiguous split pins it."""
    g = build_csr(powerlaw_cluster(200, 8, seed=0), 200)

    def ratio(mode):
        items = np.zeros(8, np.int64)
        for _cap, _v0, _v1, n in shard_edge_steps(g, 512, 8, mode=mode):
            items += n
        return items.max() / max(items.min(), 1)

    rr, contig = ratio("round_robin"), ratio("contiguous")
    assert rr <= 1.5 < contig
    assert rr < contig


def test_feed_rejects_unknown_partition_mode():
    with pytest.raises(ValueError):
        list(shard_edge_steps(GRAPHS["er"], 64, 8, mode="hashed"))


def test_finalize_reassembles_limb_quads_exactly():
    """A psum'd 4-limb partial and the plain (hi, lo) pair for the same
    count add the same amount to a count accumulator — including hi words
    past 2^16 — and ``_finalize`` returns the exact total read from it."""
    r = WaveRunner(TINY)
    plan = P.compile_pattern(P.TRIANGLE)
    hi, lo = (1 << 17) + 9, (1 << 20) + 123
    pair = jnp.array([hi, lo], jnp.int32)
    quad = jnp.array([hi >> 16, hi & 0xFFFF, lo >> 16, lo & 0xFFFF],
                     jnp.int32)
    zero = jnp.zeros(COUNT_LIMBS, jnp.int32)
    a = count_acc_add(zero, *WaveRunner._count_weights(pair))
    b = count_acc_add(zero, *ShardedWaveRunner._count_weights(quad))
    assert np.array_equal(np.asarray(a), np.asarray(b))
    assert r._finalize(plan, [count_acc_value(a)]) == \
        r._finalize(plan, [count_acc_value(b)]) == (hi << 16) + lo


# ---------------------------------------------------------------------------
# session degeneracy + cache keying
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mesh", [None, 1])
def test_mesh_one_is_the_plain_unsharded_runner(mesh):
    m = Miner(GRAPHS["er"], mesh=mesh)
    assert type(m.runner) is WaveRunner
    assert m.mesh is None
    assert m.count("triangle") == reference.triangle_count(GRAPHS["er"])


@needs2
def test_mesh_signature_isolates_sharded_executables():
    mesh = mesh_for(2)
    assert mesh_signature(mesh) != mesh_signature(None)
    assert ("mine", 2) in mesh_signature(mesh)
    assert ExecutableCache(mesh=mesh).prefix != ExecutableCache().prefix


@needs2
def test_sharded_runner_rejects_unsupported_modes():
    g, mesh = GRAPHS["er"], mesh_for(2)
    with pytest.raises(ValueError):
        ShardedWaveRunner(g, mesh, axis="model")
    with pytest.raises(ValueError):
        ShardedWaveRunner(g, mesh, feed_partition="hashed")


@needs2
def test_two_way_mesh_triangle_parity():
    g = GRAPHS["plc"]
    assert Miner(g, mesh=2).count("triangle") == reference.triangle_count(g)


# ---------------------------------------------------------------------------
# 8-way parity (the CI mesh8 leg)
# ---------------------------------------------------------------------------


@needs8
@pytest.mark.parametrize("name", list(GRAPHS))
def test_sharded_counts_bit_identical(name):
    """The full app mix on an 8-way mesh: every count equals the
    single-device session bit for bit (and the reference oracles)."""
    g = GRAPHS[name]
    m1, m8 = Miner(g), Miner(g, mesh=8)
    for q in ("triangle", "tailed-triangle", "4-clique"):
        assert m8.count(q) == m1.count(q), q
    assert m8.count_many(MOTIF_NAMES) == m1.count_many(MOTIF_NAMES)
    assert m8.count("triangle") == reference.triangle_count(g)
    assert dict(zip(MOTIF_NAMES, m8.count_many(MOTIF_NAMES))) == \
        reference.four_motif_counts(g)


@needs8
@pytest.mark.parametrize("query", ["triangle", "4-clique"])
def test_sharded_many_chunk_counts_read_each_leaf_once(query):
    """Tiny chunks: many count dispatches add to one replicated device
    accumulator per leaf; the count equals the single-device one and each
    runner reads the leaf once a query, beside its expand metas."""
    g = GRAPHS["cliq"]
    m1, m8 = Miner(g, chunk=128), Miner(g, mesh=8, chunk=128)
    assert m8.count(query) == m1.count(query) > 0
    for m in (m1, m8):
        metas = m.runner.level_execs.get(("expand", 2), 0)
        assert m.stats["runner"]["host_syncs"] == 1 + metas


@needs8
def test_sharded_embeddings_enumerate_the_same_multiset():
    g = GRAPHS["plc"]
    t1 = Miner(g).embeddings("triangle")
    t8 = Miner(g, mesh=8).embeddings("triangle")
    assert t8.shape == t1.shape

    def key(t):
        return t[np.lexsort(t.T[::-1])]
    np.testing.assert_array_equal(key(t8), key(t1))


@needs8
def test_sharded_repeats_retrace_nothing():
    m = Miner(GRAPHS["er"], mesh=8)
    first = m.count("triangle")
    batch = m.count_many(MOTIF_NAMES)
    traced = m.stats["retraces"]
    assert traced > 0
    psums = m.stats["runner"]["psum_reductions"]
    assert psums > 0
    assert m.count("triangle") == first
    assert m.count_many(MOTIF_NAMES) == batch
    assert m.stats["retraces"] == traced
    assert m.stats["runner"]["psum_reductions"] > psums


@needs8
def test_sharded_feed_accounts_for_every_edge():
    g = GRAPHS["wheel"]
    m = Miner(g, mesh=8)
    m.count("triangle")                      # one symmetric feed pass
    items = m.stats["runner"]["shard_feed_items"]
    assert sum(items) == half_edges(g).shape[0]
    assert min(items) > 0                    # the hub's run was dealt out


@needs8
def test_sharded_handles_more_shards_than_edges():
    """TINY has fewer half-edges than shards: some shards mine nothing but
    carry bound-0 padding — counts still exact."""
    assert Miner(TINY, mesh=8).count("triangle") == \
        reference.triangle_count(TINY)
    assert Miner(TINY, mesh=8).count_many(MOTIF_NAMES) == \
        Miner(TINY).count_many(MOTIF_NAMES)


@needs8
def test_contiguous_feed_partition_is_exact_too():
    """The foil dealing mode changes only the edge -> shard assignment,
    never the counted multiset."""
    g = GRAPHS["wheel"]
    m = Miner(g, mesh=8, feed_partition="contiguous")
    assert m.count("triangle") == reference.triangle_count(g)
