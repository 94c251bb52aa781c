"""The plain references against the program's XLA path, and the canonical
work functions against brute force, on small seeded graphs."""
import itertools

import pytest

from ixbench_testkit import ROOT, TINY

from ixbench import compare, graphs  # noqa: E402
from ixbench.harness import load_module  # noqa: E402

QUERIES = ("triangle", "4-clique")


def _ref(q):
    return load_module(ROOT / "bench/reference" / f"{q}.py")


def _work(q):
    return load_module(ROOT / "bench/work" / f"{q}.py")


def _graph(seed, **kw):
    return graphs.make_graph(dict(TINY, **kw), seed)


def _adj(hg):
    adj = {v: set() for v in range(hg.n)}
    for u, v in hg.edges:
        adj[int(u)].add(int(v))
        adj[int(v)].add(int(u))
    return adj


@pytest.mark.parametrize("seed", [1, 2**31 + 3])
@pytest.mark.parametrize("query", QUERIES)
def test_reference_matches_program(query, seed):
    from repro.graph.csr import build_csr
    from repro.mining import Miner
    hg = _graph(seed)
    m = Miner(build_csr(hg.edges, num_vertices=hg.n), backend="xla")
    count = compare.exact_answer(_ref(query).values(hg))
    assert count == m.count(query) and count > 0


def _brute_count(hg, k):
    adj = _adj(hg)
    return sum(all(b in adj[a] for a, b in itertools.combinations(vs, 2))
               for vs in itertools.combinations(range(hg.n), k))


@pytest.mark.parametrize("query,k", [("triangle", 3), ("4-clique", 4)])
def test_reference_values_match_brute_force(query, k):
    hg = _graph(5, vertices=40, m_per_node=7)
    got = _ref(query).values(hg)
    assert set(got.tolist()) == {1.0}
    assert got.size == _brute_count(hg, k)


def test_triangle_work_matches_brute_force():
    hg = _graph(4, vertices=60, m_per_node=8)
    adj = _adj(hg)
    elems = sum(len(adj[u]) + len(adj[v]) for u, v in hg.edges)
    assert _work("triangle").stream_bytes(hg) == 4 * elems


def test_4clique_work_matches_brute_force():
    hg = _graph(4, vertices=60, m_per_node=8)
    adj = _adj(hg)
    elems = 0
    for a in range(hg.n):
        for b in (x for x in adj[a] if x < a):
            s_ab = {c for c in adj[a] & adj[b] if c < b}
            elems += len(adj[a]) + len(adj[b])
            elems += sum(len(s_ab) + len(adj[c]) for c in s_ab)
    assert _work("4-clique").stream_bytes(hg) == 4 * elems
