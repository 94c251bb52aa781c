"""SVPU value plane (paper §IV-E): weighted CSR, value-carrying kernels,
and aggregate queries vs the weighted permutation oracle.

Contracts under test:
  * **alignment** — edge values ride the exact permutation the keys take
    through ``build_csr`` (mirror / dedup / lexsort) and stay aligned in
    every padded row view and binary-search lookup;
  * **parity** — the pallas value kernel and the XLA fallback produce
    bit-identical (count, value) pairs on the dyadic weight corpus;
  * **exactness** — ``Miner.aggregate`` (sum / max / min) equals the host
    float64 ``reference.weighted_pattern_oracle`` EXACTLY on random
    weighted graphs, device and host compaction, tiny chunks;
  * **zero-overhead** — a weighted query costs the same feed passes and
    level-kernel dispatches as its unweighted twin, fuses into the same
    forest prefix, and repeats with 0 retraces.
"""
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.stream import SENTINEL
from repro.graph import build_csr, edge_weights, padded_rows, \
    padded_value_rows, with_edge_values
from repro.graph.csr import edge_list
from repro.graph.generators import erdos_renyi, powerlaw_cluster
from repro.kernels import ops
from repro.mining import plan as P
from repro.mining import reference
from repro.mining.engine import WaveRunner
from repro.mining.forest import build_forest
from repro.mining.session import Miner
from repro.values import edge_value_lookup, prefix_scale


def _weighted(edges, n=None, seed=0):
    g = build_csr(edges, n)
    return with_edge_values(g, edge_weights(edge_list(g), seed=seed))


TINY_EDGES = erdos_renyi(20, 70, seed=7)
TINY = _weighted(TINY_EDGES, 20, seed=11)
SMALL = _weighted(erdos_renyi(60, 240, seed=3), 60, seed=5)

AGG_PATTERNS = {
    "triangle": P.TRIANGLE,
    "three-chain-induced": P.THREE_CHAIN_INDUCED,
    "4-clique": P.clique_pattern(4),
}


def _weight_of(u, v, seed):
    return float(edge_weights(np.array([[u, v]]), seed=seed)[0])


# ---------------------------------------------------------------------------
# weighted CSR plumbing: alignment survives every permutation
# ---------------------------------------------------------------------------


def test_edge_weights_direction_and_duplicate_invariant():
    e = np.array([[3, 9], [9, 3], [0, 7], [7, 0]])
    w = edge_weights(e, seed=4)
    assert w[0] == w[1] and w[2] == w[3]
    assert set(np.unique(edge_weights(erdos_renyi(40, 150, seed=1), seed=2))
               ) <= {0.25, 0.5, 0.75, 1.0}


def test_build_csr_values_ride_the_key_permutation():
    """Shuffled, mirrored, duplicated input edges: every directed edge of
    the finished CSR still carries the weight of its own endpoint pair."""
    rng = np.random.default_rng(0)
    base = erdos_renyi(30, 90, seed=2)
    messy = np.concatenate([base, base[::-1, ::-1], base[:20]])
    messy = messy[rng.permutation(len(messy))]
    g = build_csr(messy, 30, edge_values=edge_weights(messy, seed=9))
    vals = np.asarray(g.edge_values)
    for i, (u, v) in enumerate(edge_list(g)):
        assert vals[i] == _weight_of(u, v, 9), (u, v)
    assert not np.any(vals[g.num_edges:])          # padding stays zero


def test_with_edge_values_roundtrip_and_validation():
    g = build_csr(TINY_EDGES, 20)
    assert not g.weighted
    e = edge_list(g)
    gw = with_edge_values(g, edge_weights(e, seed=11))
    assert gw.weighted and gw.num_edges == g.num_edges
    # key arrays are shared, values aligned with edge_list order
    assert gw.indices is g.indices
    vals = np.asarray(gw.edge_values)
    for i, (u, v) in enumerate(e):
        assert vals[i] == _weight_of(u, v, 11)
    with pytest.raises(ValueError):
        with_edge_values(g, np.ones(g.num_edges + 3, np.float32))


def test_padded_value_rows_align_with_padded_keys():
    vs = np.arange(TINY.num_vertices, dtype=np.int32)
    cap = int(TINY.padded_max_degree)
    keys, _ = padded_rows(TINY, vs, cap)
    vals = padded_value_rows(TINY, vs, cap)
    keys, vals = np.asarray(keys), np.asarray(vals)
    assert vals.shape == keys.shape
    for r, u in enumerate(vs):
        for c in range(cap):
            if keys[r, c] == SENTINEL:
                assert vals[r, c] == 0.0
            else:
                assert vals[r, c] == _weight_of(u, keys[r, c], 11)


def test_edge_value_lookup_matches_host_oracle():
    e = edge_list(TINY)
    w = {(int(u), int(v)): _weight_of(u, v, 11) for u, v in e}
    rng = np.random.default_rng(3)
    us = rng.integers(0, TINY.num_vertices, size=40).astype(np.int32)
    keys = rng.integers(0, TINY.num_vertices, size=(40, 6)).astype(np.int32)
    keys[rng.random(keys.shape) < 0.2] = SENTINEL   # padding slots miss
    got = np.asarray(edge_value_lookup(TINY, us, keys))
    for i in range(40):
        for j in range(6):
            assert got[i, j] == w.get((int(us[i]), int(keys[i, j])), 0.0)
    # 1-d form and prefix_scale compose the same lookups
    got1 = np.asarray(edge_value_lookup(TINY, us, keys[:, 0]))
    np.testing.assert_array_equal(got1, got[:, 0])
    sc = np.asarray(prefix_scale(TINY, {0: us, 1: keys[:, 0]}, ((0, 1),)))
    np.testing.assert_array_equal(sc, got[:, 0])


def test_edge_value_lookup_requires_weights():
    g = build_csr(TINY_EDGES, 20)
    with pytest.raises(ValueError):
        edge_value_lookup(g, np.zeros(4, np.int32), np.zeros(4, np.int32))


# ---------------------------------------------------------------------------
# value kernel: pallas vs XLA parity (dyadic corpus => bit-identical)
# ---------------------------------------------------------------------------


def _dyadic_streams(rng, b, cap, k):
    """(a, a_vals, bs, b_vals): sorted unique keys, SENTINEL-padded rows,
    dyadic values zeroed on padding slots."""
    def rows(n_rows, width):
        keys = np.full((n_rows, width), SENTINEL, np.int32)
        vals = np.zeros((n_rows, width), np.float32)
        for r in range(n_rows):
            m = int(rng.integers(0, min(width, 24) + 1))
            keys[r, :m] = np.sort(rng.choice(60, size=m, replace=False))
            vals[r, :m] = rng.choice([0.25, 0.5, 0.75, 1.0], size=m)
        return keys, vals
    a, av = rows(b, cap)
    bs, bv = zip(*(rows(b, cap) for _ in range(k)))
    return a, av, np.stack(bs), np.stack(bv)


@settings(max_examples=6, deadline=None)
@given(st.integers(0, 10_000), st.sampled_from(["sum", "max", "min"]),
       st.integers(1, 3))
def test_xlevel_agg_pallas_xla_parity(seed, op, k):
    rng = np.random.default_rng(seed)
    a, av, bs, bv = _dyadic_streams(rng, b=12, cap=128, k=k)
    pol = (1,) * k
    scale = rng.choice([0.25, 0.5, 1.0], size=12).astype(np.float32)
    outs = {}
    for backend in ("pallas", "xla"):
        c, v = ops.xlevel_agg(a, bs, pol, av, bv, scale, op=op,
                              backend=backend)
        outs[backend] = (np.asarray(c), np.asarray(v))
    np.testing.assert_array_equal(outs["pallas"][0], outs["xla"][0])
    np.testing.assert_array_equal(outs["pallas"][1], outs["xla"][1])


def test_xlevel_agg_sub_refs_parity():
    rng = np.random.default_rng(77)
    a, av, bs, bv = _dyadic_streams(rng, b=10, cap=128, k=2)
    pol = (1, 0)                       # one INTER, one SUB ref
    scale = np.ones(10, np.float32)
    cp, vp = ops.xlevel_agg(a, bs, pol, av, bv, scale, backend="pallas")
    cx, vx = ops.xlevel_agg(a, bs, pol, av, bv, scale, backend="xla")
    np.testing.assert_array_equal(np.asarray(cp), np.asarray(cx))
    np.testing.assert_array_equal(np.asarray(vp), np.asarray(vx))


# ---------------------------------------------------------------------------
# engine == weighted oracle, exactly
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("op", ["sum", "max", "min"])
@pytest.mark.parametrize("name", list(AGG_PATTERNS))
def test_aggregate_matches_weighted_oracle(name, op):
    pat = AGG_PATTERNS[name]
    want = reference.weighted_pattern_oracle(TINY, pat, op)
    assert Miner(TINY).aggregate(pat, op=op) == want, (name, op)


@settings(max_examples=6, deadline=None)
@given(st.integers(0, 10_000), st.integers(0, 10_000),
       st.sampled_from(["sum", "max", "min"]))
def test_aggregate_oracle_property(gseed, wseed, op):
    g = _weighted(erdos_renyi(16, 44, seed=gseed), 16, seed=wseed)
    m = Miner(g, chunk=128)
    for pat in (P.TRIANGLE, P.THREE_CHAIN_INDUCED, P.clique_pattern(4)):
        assert m.aggregate(pat, op=op) == \
            reference.weighted_pattern_oracle(g, pat, op), (pat.name, op)


def test_aggregate_many_matches_singles():
    m = Miner(SMALL)
    names = list(AGG_PATTERNS)
    batch = m.aggregate_many(names, op="sum")
    assert batch == [m.aggregate(n, op="sum") for n in names]


# ---------------------------------------------------------------------------
# zero-overhead contracts: dispatches, fusion, retraces, guards
# ---------------------------------------------------------------------------


def test_value_lanes_add_no_dispatches_or_feed_passes():
    """A weighted query costs exactly the unweighted query's kernel
    dispatches and feed chunks — value lanes ride, they never add."""
    for query in ("triangle", "4-clique"):
        pat = AGG_PATTERNS[query]
        count_r = WaveRunner(SMALL)
        count_r.run(P.compile_pattern(pat))
        agg_r = WaveRunner(SMALL)
        agg_r.run(P.compile_pattern(pat, aggregate="sum"))
        assert agg_r.stats["level_kernel_dispatches"] == \
            count_r.stats["level_kernel_dispatches"], query
        assert agg_r.metrics.value("feed_chunks") == \
            count_r.metrics.value("feed_chunks"), query
        assert agg_r.metrics.value("value_lane_dispatches") > 0


def test_count_and_aggregate_share_forest_feed():
    """stream_key ignores the value disposition: a count leaf and an
    aggregate leaf over the same stream fuse into one feed pass, and the
    merged run still produces both exact results."""
    plans = [P.compile_pattern(P.TRIANGLE),
             P.compile_pattern(P.TRIANGLE, aggregate="sum"),
             P.compile_pattern(P.TRIANGLE, aggregate="max")]
    forest = build_forest(plans)
    assert forest.sharing_stats()["feed_passes"]["fused"] == 1
    got = WaveRunner(TINY).run_set(forest)
    assert got[0] == reference.pattern_count_oracle(TINY, P.TRIANGLE)
    assert got[1] == reference.weighted_pattern_oracle(TINY, P.TRIANGLE, "sum")
    assert got[2] == reference.weighted_pattern_oracle(TINY, P.TRIANGLE, "max")


def test_repeated_aggregate_zero_retraces():
    m = Miner(SMALL)
    first = m.aggregate("triangle", op="sum")
    traced = m.stats["retraces"]
    assert traced > 0
    assert m.aggregate("triangle", op="sum") == first
    assert m.stats["retraces"] == traced
    batch = m.aggregate_many(list(AGG_PATTERNS), op="max")
    traced = m.stats["retraces"]
    assert m.aggregate_many(list(AGG_PATTERNS), op="max") == batch
    assert m.stats["retraces"] == traced


def test_aggregate_guards():
    with pytest.raises(ValueError):                # weights required
        Miner(build_csr(TINY_EDGES, 20)).aggregate("triangle")
    with pytest.raises(ValueError):                # unknown op
        P.compile_pattern(P.TRIANGLE, aggregate="avg")
    with pytest.raises(ValueError):                # emit and aggregate clash
        P.compile_pattern(P.TRIANGLE, emit=True, aggregate="sum")
    sym = P.pattern("sym-tri", 3, ((0, 1), (0, 2), (1, 2)), div=6)
    with pytest.raises(ValueError):                # div != 1 rejected
        P.compile_pattern(sym, aggregate="sum")
    with pytest.raises(ValueError):                # oracle mirrors the guard
        reference.weighted_pattern_oracle(build_csr(TINY_EDGES, 20),
                                          P.TRIANGLE, "sum")


def test_powerlaw_weighted_smoke():
    g = _weighted(powerlaw_cluster(40, 4, seed=6), 40, seed=1)
    m = Miner(g)
    want = reference.weighted_pattern_oracle(g, P.TRIANGLE, "sum")
    assert m.aggregate("triangle", op="sum") == want
