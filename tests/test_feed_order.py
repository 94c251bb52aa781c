"""The level-1 feed in v1-class order (``engine.edge_buckets``).

Inside each v0 degree bucket the feed orders its edges by the capacity
class of v1, so a chunk's N(v1) gather (``_neighbor_cap`` of its v1
column) is as wide as its own widest live v1 and not the bucket's. The
contract checked here, on a hub-heavy Holme–Kim graph:

  * within each bucket, v1 classes never decrease, and every v0 is in the
    bucket's class;
  * the feed enumerates exactly the ``half_edges`` / ``directed_edges``
    multiset, on one device and dealt over a mesh;
  * every chunk's N(v1) capacity holds each live v1 and is the smallest
    class that does (dead slots, vertex 0, do not count);
  * ``feed_row_slots`` matches a brute-force count, below the old
    chunk-max count;
  * counts equal ``mining.reference`` on the one-device runner and on the
    8-device CPU mesh in both feed partitions (a subprocess, so the mesh
    check runs whatever devices this process sees); N(v1) rows here span
    one to four 128-lane blocks of the CSR's block plane.
"""
import json
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

import jax

from repro.graph import build_csr
from repro.graph.generators import powerlaw_cluster
from repro.mining import engine, reference
from repro.mining.engine import (bucket_chunks, directed_edges,
                                 edge_buckets, half_edges)
from repro.mining.session import Miner
from repro.mining.shard import FEED_PARTITIONS, shard_edge_steps

from test_obs import _feed_fill_brute

needs8 = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs 8 devices "
           "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")

ROOT = pathlib.Path(__file__).resolve().parents[1]
N = 800
CHUNK = 128


def _hub_graph():
    """Holme–Kim (low ids are its hubs) plus one super-hub, vertex 3,
    joined to every odd id: v1 classes 128, 256 and 512 in the feed."""
    hub = np.stack([np.full(N // 2, 3), np.arange(1, N, 2)], axis=1)
    return build_csr(np.concatenate([powerlaw_cluster(N, 10, seed=0), hub]),
                     N)


HUB = _hub_graph()
DEG = np.asarray(HUB.degrees)


def _pow2(d: int) -> int:
    c = 128
    while c < max(d, 1):
        c *= 2
    return c


def _live_blocks(symmetric: bool, shards: int, mode: str):
    """(cap, v0, v1, live mask) per chunk (one device) or super-step."""
    if shards == 1:
        for cap, v0, v1, n in bucket_chunks(edge_buckets(HUB, symmetric),
                                            CHUNK):
            yield cap, v0, v1, np.arange(v0.shape[0]) < n
        return
    for cap, v0, v1, n in shard_edge_steps(HUB, CHUNK, shards, symmetric,
                                           mode):
        nb = v0.shape[0] // shards
        live = (np.arange(nb) < np.reshape(n, (-1, 1))).reshape(-1)
        yield cap, v0, v1, live


FEEDS = [(1, "round_robin")] + [(8, mode) for mode in FEED_PARTITIONS]


def test_hub_graph_spans_three_v1_classes():
    classes = {_pow2(int(d)) for d in DEG[half_edges(HUB)[:, 1]]}
    assert classes == {128, 256, 512}


@pytest.mark.parametrize("symmetric", [True, False])
def test_buckets_run_in_v1_class_order(symmetric):
    buckets = edge_buckets(HUB, symmetric)
    assert [cap for cap, _ in buckets] == sorted({cap for cap, _ in buckets})
    for cap, edges in buckets:
        assert {_pow2(int(d)) for d in DEG[edges[:, 0]]} == {cap}
        k1 = [_pow2(int(d)) for d in DEG[edges[:, 1]]]
        assert all(a <= b for a, b in zip(k1, k1[1:]))


@pytest.mark.parametrize("shards,mode", FEEDS)
@pytest.mark.parametrize("symmetric", [True, False])
def test_feed_keeps_the_edge_multiset(symmetric, shards, mode):
    want = half_edges(HUB) if symmetric else directed_edges(HUB)
    got = np.concatenate([np.stack([v0[live], v1[live]], axis=1)
                          for _, v0, v1, live in
                          _live_blocks(symmetric, shards, mode)])
    assert got.shape == want.shape
    np.testing.assert_array_equal(got[np.lexsort(got.T[::-1])],
                                  want[np.lexsort(want.T[::-1])])


def _mesh(shards: int, mode: str) -> dict:
    return {} if shards == 1 else {"mesh": shards, "feed_partition": mode}


RUNNERS = [pytest.param(s, m, marks=needs8 if s > 1 else ())
           for s, m in FEEDS]


@pytest.mark.parametrize("shards,mode", RUNNERS)
def test_chunk_v1_capacity_is_the_smallest_that_holds_it(shards, mode,
                                                         monkeypatch):
    """The runner sizes each chunk's N(v1) gather from its live v1 alone:
    the vertices it sizes from are exactly the feed's v1 multiset (no
    padding), and each capacity is the smallest class that holds them."""
    seen = []
    real = engine._neighbor_cap

    def spy(g, verts):
        cap = real(g, verts)
        seen.append((np.asarray(verts), cap))
        return cap
    monkeypatch.setattr(engine, "_neighbor_cap", spy)
    m = Miner(HUB, chunk=CHUNK, **_mesh(shards, mode))
    assert m.count("triangle") == reference.triangle_count(HUB)
    for verts, cap in seen:
        assert cap >= DEG[verts].max()
        assert cap == _pow2(int(DEG[verts].max()))
    got = np.sort(np.concatenate([v for v, _ in seen]))
    np.testing.assert_array_equal(got, np.sort(half_edges(HUB)[:, 1]))
    assert len({cap for _, cap in seen}) == 3


def test_feed_row_slots_brute_force_below_chunk_max():
    m = Miner(HUB, chunk=CHUNK)
    m.count("triangle")
    slots, keys = _feed_fill_brute(HUB, CHUNK)
    assert m.metrics.value("feed_row_slots") == slots
    assert m.metrics.value("feed_row_keys") == keys
    old_slots, old_keys = _feed_fill_brute(HUB, CHUNK, ordered=False)
    assert old_keys == keys
    assert slots < old_slots


QUERIES = {
    "triangle": reference.triangle_count,
    "4-clique": lambda g: reference.clique_count(g, 4),
    "tailed-triangle": reference.tailed_triangle_count,
    "three-chain": lambda g: reference.three_chain_count(g, induced=True),
}


@pytest.fixture(scope="module")
def want():
    return {q: f(HUB) for q, f in QUERIES.items()}


@pytest.mark.parametrize("query", list(QUERIES))
def test_counts_match_the_reference_on_one_device(query, want):
    assert Miner(HUB, chunk=CHUNK).count(query) == want[query]


MESH8_SCRIPT = r"""
import json
import jax
from test_feed_order import CHUNK, HUB, QUERIES
from repro.mining.session import Miner
from repro.mining.shard import FEED_PARTITIONS

assert jax.device_count() == 8
print(json.dumps({mode: {q: Miner(HUB, chunk=CHUNK, mesh=8,
                                  feed_partition=mode).count(q)
                         for q in QUERIES} for mode in FEED_PARTITIONS}))
"""


@pytest.fixture(scope="module")
def mesh8_counts():
    """The queries on an 8-device CPU mesh, in a subprocess that sees
    eight devices (this process may see one)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"),
                                           str(ROOT / "tests")]))
    p = subprocess.run([sys.executable, "-c", MESH8_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("mode", FEED_PARTITIONS)
def test_counts_match_the_reference_on_the_mesh(mode, want, mesh8_counts):
    assert mesh8_counts[mode] == want
