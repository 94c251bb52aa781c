"""The benchmark's copied generators, and what a run's seed changes."""
import functools
import json

import numpy as np
import pytest

from ixbench_testkit import ROOT, TINY

from ixbench import graphs  # noqa: E402


def _config(name):
    return json.loads((ROOT / "bench/configs" / f"{name}.json").read_text())


def _edge_set(edges):
    e = np.sort(np.asarray(edges, dtype=np.int64), axis=1)
    e = e[e[:, 0] != e[:, 1]]
    return np.unique(e, axis=0)


CONFIGS = ("mico", "wiki-vote")


@functools.lru_cache(maxsize=None)
def _generated(name):
    return graphs.canonical_edges(graphs.generate(_config(name)))


@pytest.mark.parametrize("name", CONFIGS)
def test_copied_generator_matches_program(name):
    from repro.graph.generators import powerlaw_cluster
    cfg = _config(name)
    theirs = powerlaw_cluster(cfg["vertices"], cfg["m_per_node"],
                              seed=cfg["graph_seed"], tri_p=cfg["tri_p"])
    np.testing.assert_array_equal(_generated(name), _edge_set(theirs))


@pytest.mark.parametrize("name", CONFIGS)
def test_config_realises_table_iv(name):
    """The graph has Table IV's vertices and, within 1 %, its edges; the
    configuration records what it realises."""
    cfg = _config(name)
    edges = _generated(name)
    deg = np.bincount(edges.ravel(), minlength=cfg["vertices"])
    table, real = cfg["assumed"]["table_iv"], cfg["assumed"]["realised"]
    assert real == {"vertices": cfg["vertices"], "edges": len(edges),
                    "max_degree": int(deg.max())}
    assert table["vertices"] == cfg["vertices"]
    assert abs(len(edges) / table["edges"] - 1) < 0.01
    assert cfg["reduced"] == []


def test_shuffle_keeps_orientation_and_feed_shapes():
    from repro.graph.csr import build_csr
    from repro.mining.engine import _neighbor_cap, choose_chunk, edge_chunks

    def feed(hg):
        g = build_csr(hg.edges, num_vertices=hg.n)
        chunk = choose_chunk(g.padded_max_degree)
        return sorted((cap, len(v0), n, _neighbor_cap(g, v1))
                      for cap, v0, v1, n in edge_chunks(g, chunk, True))

    cfg = dict(TINY, vertices=2000, m_per_node=10)
    n = cfg["vertices"]
    base = graphs.canonical_edges(graphs.generate(cfg))
    shapes = feed(graphs.HostGraph(n, base))
    for seed in (1, 2**31 + 11):
        perm = graphs.orientation_preserving_shuffle(base, n, seed)
        assert sorted(perm) == list(range(n))
        assert np.mean(perm != np.arange(n)) > 0.5
        np.testing.assert_array_equal(perm[base[:, 0]] < perm[base[:, 1]],
                                      base[:, 0] < base[:, 1])
        hg = graphs.make_graph(cfg, seed)
        assert feed(hg) == shapes
    a, b = graphs.make_graph(cfg, 1), graphs.make_graph(cfg, 2)
    assert not np.array_equal(a.edges, b.edges)
    np.testing.assert_array_equal(graphs.make_graph(cfg, 1).edges, a.edges)
