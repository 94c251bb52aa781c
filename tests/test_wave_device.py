"""Device-resident wavefront pipeline vs the host oracles.

The device compaction (``ops.xinter_compact``, ``batch_compact_items``) must
reproduce the host ``compact`` oracle item-for-item: same work-item order
(np.nonzero row-major), same extension vertices, same prefix rows — across
random rows, sentinel-padded tails and bound=0 padding items. Through
``WaveRunner`` the counts and the items each expand level keeps must equal
``mining/reference.py``.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.batch import batch_compact_items, batch_inter
from repro.core.stream import SENTINEL, round_capacity
from repro.graph import build_csr
from repro.graph.generators import clique_planted, erdos_renyi, powerlaw_cluster
from repro.kernels.ops import xinter_compact
from repro.mining import reference
from repro.mining.engine import WaveRunner, compact

RNG = np.random.default_rng(11)

GRAPHS = {
    "er": build_csr(erdos_renyi(140, 900, seed=13), 140),
    "plc": build_csr(powerlaw_cluster(110, 5, seed=7), 110),
    "cliq": build_csr(clique_planted(80, 240, (7, 6, 5), seed=9), 80),
}


def _random_rows(batch, cap, hi=3000, rng=RNG):
    """Sorted sentinel-padded rows + survivor counts, incl. empty rows."""
    rows = np.full((batch, cap), SENTINEL, np.int32)
    counts = np.zeros((batch,), np.int32)
    for i in range(batch):
        if rng.random() < 0.15:
            continue                      # bound=0 / dead padding item
        n = int(rng.integers(1, cap + 1))
        rows[i, :n] = np.sort(rng.choice(hi, size=n, replace=False))
        counts[i] = n
    return rows, counts


@pytest.mark.parametrize("batch,cap", [(8, 128), (33, 256), (128, 128)])
def test_batch_compact_items_matches_host_oracle(batch, cap):
    rows, counts = _random_rows(batch, cap)
    src, verts, total, maxc = batch_compact_items(
        jnp.asarray(rows), jnp.asarray(counts), batch * cap)
    total = int(total)
    col = np.arange(cap)
    ii, jj = np.nonzero(col[None, :] < counts[:, None])
    assert total == len(ii)
    assert int(maxc) == int(counts.max())
    np.testing.assert_array_equal(np.asarray(src)[:total], ii)
    np.testing.assert_array_equal(np.asarray(verts)[:total], rows[ii, jj])
    # padding items are bound-0: they must contribute nothing downstream
    assert np.all(np.asarray(verts)[total:] == 0)
    assert np.all(np.asarray(src)[total:] == 0)


def test_batch_compact_items_chunk_rounded_buffer():
    rows, counts = _random_rows(16, 128)
    out_items = 16 * 128 + 512            # buffer larger than B*cap
    src, verts, total, _ = batch_compact_items(
        jnp.asarray(rows), jnp.asarray(counts), out_items)
    assert src.shape == (out_items,) and verts.shape == (out_items,)
    assert np.all(np.asarray(verts)[int(total):] == 0)


def test_batch_compact_items_all_dead():
    rows = np.full((12, 128), SENTINEL, np.int32)
    counts = np.zeros((12,), np.int32)
    src, verts, total, maxc = batch_compact_items(
        jnp.asarray(rows), jnp.asarray(counts), 12 * 128)
    assert int(total) == 0 and int(maxc) == 0
    assert np.all(np.asarray(verts) == 0)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_xinter_compact_matches_inter_plus_host_compact(backend):
    a_rows, _ = _random_rows(24, 256, hi=600)
    b_rows, _ = _random_rows(24, 384, hi=600)
    bounds = RNG.integers(0, 600, 24).astype(np.int32)
    a, b = jnp.asarray(a_rows), jnp.asarray(b_rows)
    rows, counts, src, verts, total, maxc = xinter_compact(
        a, b, jnp.asarray(bounds), backend=backend)
    o_rows, o_counts = batch_inter(a, b, jnp.asarray(bounds))
    np.testing.assert_array_equal(np.asarray(rows),
                                  np.asarray(o_rows)[:, : rows.shape[1]])
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(o_counts))
    wave = compact(np.asarray(o_rows), np.asarray(o_counts))
    total = int(total)
    if wave is None:
        assert total == 0
        return
    np.testing.assert_array_equal(np.asarray(verts)[:total], wave.verts)
    cap2 = round_capacity(int(maxc))
    got_rows = np.asarray(rows)[np.asarray(src)[:total], :cap2]
    np.testing.assert_array_equal(got_rows, wave.rows)


def _assert_clique_waves(g, k, chunk=None):
    """k-clique count and the items its expand levels keep: level j's
    survivors are the j-cliques (v0 > v1 > ... > v(j-1)), so a k-clique
    run keeps Σ clique_count(g, j) over 3 <= j < k items."""
    runner = WaveRunner(g, chunk=chunk)
    assert runner.clique(k) == reference.clique_count(g, k)
    assert runner.stats["device_compactions"] > 0
    assert runner.stats["items"] == sum(reference.clique_count(g, j)
                                        for j in range(3, k))


@pytest.mark.parametrize("name", list(GRAPHS))
@pytest.mark.parametrize("k", [4, 5])
def test_clique_waves_bit_identical_device_vs_host(name, k):
    for chunk in (None, 128):
        _assert_clique_waves(GRAPHS[name], k, chunk)


@pytest.mark.parametrize("name", list(GRAPHS))
def test_clique_waves_identical_with_tiny_chunks(name):
    """Small chunks force multi-chunk waves + chunk-rounded item buffers."""
    for chunk in (None, 128):
        _assert_clique_waves(GRAPHS[name], 4, chunk)


def test_all_seven_apps_agree_with_reference():
    """The seven mining apps on the device-resident runner vs reference."""
    from repro.mining.apps import shared_session
    from repro.mining.plan import clique_pattern
    g = GRAPHS["er"]
    m = shared_session(g)
    assert m.count("triangle") == reference.triangle_count(g)
    assert m.count("triangle-nested") == reference.triangle_count(g)
    deg = np.asarray(g.degrees, dtype=np.int64)
    assert int((deg * (deg - 1) // 2).sum()) == reference.three_chain_count(g)
    assert (m.count("three-chain")
            == reference.three_chain_count(g, induced=True))
    assert m.count("tailed-triangle") == reference.tailed_triangle_count(g)
    t, chain = m.count_many(["triangle", "three-chain"])
    assert {"triangle": t, "chain": chain} == reference.motif3(g)
    for k in (4, 5):
        assert m.count(clique_pattern(k)) == reference.clique_count(g, k)


def test_executable_cache_reuses_across_levels_and_graphs():
    g = GRAPHS["cliq"]
    runner = WaveRunner(g)
    runner.clique(5)
    first = dict(runner.stats)
    assert first["exec_misses"] > 0
    runner2 = WaveRunner(g)
    runner2._exec = runner._exec          # shared cache, same shapes
    runner2.stats["exec_misses"] = 0
    runner2.clique(5)
    assert runner2.stats["exec_misses"] == 0
    assert runner2.stats["exec_hits"] > 0


def test_exec_misses_equal_unique_shapes():
    """One trace per (kind, shape) key — degree buckets never re-trace."""
    g = GRAPHS["plc"]
    runner = WaveRunner(g)
    runner.clique(5)
    runner.count_edges()
    assert runner.stats["exec_misses"] == len(runner._exec)
