"""PlanForest scheduler: trie structure, relaxation/residual correctness,
and the bit-identity contract (fused == independent per-plan execution).

Layers:
  * builder structure — the 4-motif batch must collapse to the documented
    trie (level-2: 6 plan ops -> 3 shared nodes; feed passes 6 -> 2), with
    relaxed constraints reappearing as residuals on the right branches;
  * count identity — ``run_set`` output equals per-plan ``run`` output,
    equals the independent brute-force oracles (census + ESU), and under
    tiny chunks (multi-chunk fan-out);
  * emit plans through the forest (FSM's triangle feed) and mixed
    emit+count batches;
  * a hypothesis property over random pattern *sets* (plus its seeded
    hypothesis-free twin): any batch of random valid patterns fused into a
    forest counts exactly what the plans count independently.
"""

import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.graph import build_csr
from repro.graph.generators import clique_planted, erdos_renyi, powerlaw_cluster
from repro.mining import exhaustive, reference
from repro.mining.engine import WaveRunner
from repro.mining.forest import build_forest
from repro.mining import plan as P

from test_plan import _draw_pattern, _seeded_pattern

GRAPHS = {
    "er": build_csr(erdos_renyi(60, 240, seed=3), 60),
    "plc": build_csr(powerlaw_cluster(50, 4, seed=5), 50),
    "cliq": build_csr(clique_planted(45, 120, (6, 5), seed=1), 45),
}
TINY = build_csr(erdos_renyi(18, 48, seed=7), 18)

FOUR_MOTIF_PLANS = [P.compile_pattern(p) for p in P.FOUR_MOTIFS.values()]


# ---------------------------------------------------------------------------
# builder structure
# ---------------------------------------------------------------------------


def _nodes_at(forest, level, kind):
    out = []

    def walk(n):
        if n.op.level == level and n.op.kind == kind:
            out.append(n)
        for ch in n.children:
            walk(ch)
    for r in forest.all_roots():
        walk(r)
    return out


def test_four_motif_forest_shares_level2():
    forest = build_forest(FOUR_MOTIF_PLANS)
    st_ = forest.sharing_stats()
    assert st_["plan_ops"][("expand", 2)] == 6
    assert st_["forest_ops"][("expand", 2)] == 3
    assert st_["forest_ops"][("count", 3)] == 6
    assert st_["feed_passes"] == {"independent": 6, "fused": 2}
    # five plans ride the half-edge feed, the 4-star alone is directed
    assert len(forest.symmetric_roots) == 2
    assert len(forest.directed_roots) == 1


def test_relaxed_node_pushes_surplus_to_residuals():
    forest = build_forest(FOUR_MOTIF_PLANS)
    wings = [n for n in _nodes_at(forest, 2, "expand")
             if n.op.inter == (1,) and not n.op.sub]
    assert len(wings) == 1                      # clique+diamond+paw share it
    node = wings[0]
    assert node.op.ub == () and node.op.residual == ()   # fully relaxed
    assert len(node.children) == 3
    # the 4-clique branch deferred its v2 < v1 bound: residual on its leaf,
    # re-added to the carried element bound (the leaf consumes the carry)
    clique_leaf = [ch for ch in node.children if ch.op.residual]
    assert len(clique_leaf) == 1
    op = clique_leaf[0].op
    assert op.use_carry and ("lt", 2, 1) in op.residual and 1 in op.ub


def test_forest_liveness_is_union_of_branches():
    forest = build_forest(FOUR_MOTIF_PLANS)
    wings = [n for n in _nodes_at(forest, 2, "expand")
             if n.op.inter == (1,) and not n.op.sub][0]
    # paw's level-3 gathers rows of columns 0 and 1; clique/diamond carry:
    # the shared node must forward the union and produce the carry
    assert set(wings.op.gather_refs) >= {0, 1, 2}
    assert wings.op.carry_out
    assert set(wings.op.out_cols) == {0, 1, 2}


def test_duplicate_plans_share_one_leaf():
    g = TINY
    forest = build_forest([P.compile_pattern(P.TRIANGLE)] * 2)
    runner = WaveRunner(g)
    got = runner.run_set(forest)
    assert got[0] == got[1] == reference.triangle_count(g)
    assert runner.level_execs == {("count", 2): 1}    # counted once


def test_canonical_plan_key_distinguishes_and_matches():
    t1 = P.compile_pattern(P.TRIANGLE)
    t2 = P.compile_pattern(P.TRIANGLE)
    assert t1.canonical_key() == t2.canonical_key()
    assert t1.canonical_key() != P.compile_pattern(P.TRIANGLE_NESTED).canonical_key()


# every distinct pattern of the session's named-query catalog
CATALOG: dict = {}
for _name, _query in P._NAMED_QUERIES.items():
    if _query not in CATALOG.values():
        CATALOG[_name] = _query


@pytest.mark.parametrize("emit", [False, True], ids=["count", "emit"])
@pytest.mark.parametrize("name", list(CATALOG))
def test_one_plan_forest_is_the_plan(name, emit, monkeypatch):
    """A single plan runs as a one-plan forest: the trie is one chain on the
    plan's feed orientation that walks exactly ``plan.ops``, and ``Miner``
    builds it once, beside the compiled plan — a repeat query builds none."""
    from repro.mining import session
    built = []

    def counting(plans):
        built.append(len(plans))
        return build_forest(plans)
    monkeypatch.setattr(session, "build_forest", counting)
    m = session.Miner(TINY)
    run = m.embeddings if emit else m.count
    first = run(name)
    assert built == [1]
    plan = m.compile(name, emit=emit)
    forest = build_forest([plan])
    roots, other = ((forest.symmetric_roots, forest.directed_roots)
                    if plan.symmetric
                    else (forest.directed_roots, forest.symmetric_roots))
    assert other == ()
    ops, nodes = [], roots
    while nodes:
        assert len(nodes) == 1 and nodes[0].ride_plans == ()
        ops.append(nodes[0].op)
        leaf, nodes = nodes[0], nodes[0].children
    assert tuple(ops) == plan.ops and leaf.plans == (0,)
    # the repeat reuses the cached forest and the compiled executables
    misses = m.exec_cache.misses
    second = run(name)
    assert built == [1] and m.exec_cache.misses == misses
    if emit:
        np.testing.assert_array_equal(first, second)
        assert first.shape[1] == plan.k
    else:
        assert first == second == reference.pattern_count_oracle(
            TINY, plan.pattern)


# ---------------------------------------------------------------------------
# count identity: fused == independent == oracles
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", list(GRAPHS))
def test_fused_four_motif_matches_independent_and_census(name):
    g = GRAPHS[name]
    fused = WaveRunner(g).run_set(build_forest(FOUR_MOTIF_PLANS))
    indep = [WaveRunner(g).run(pl) for pl in FOUR_MOTIF_PLANS]
    assert fused == indep
    assert dict(zip(P.FOUR_MOTIFS, fused)) == reference.four_motif_counts(g)


def test_fused_level2_dispatches_halve():
    g = GRAPHS["plc"]
    rf = WaveRunner(g)
    rf.run_set(build_forest(FOUR_MOTIF_PLANS))
    ri = WaveRunner(g)
    for pl in FOUR_MOTIF_PLANS:
        ri.run(pl)
    fused2 = rf.level_execs[("expand", 2)]
    indep2 = ri.level_execs[("expand", 2)]
    assert fused2 * 2 == indep2                  # 6 -> 3 per chunk sweep
    # terminal work is NOT duplicated by the fan-out
    assert rf.level_execs[("count", 3)] == ri.level_execs[("count", 3)]


def test_fused_four_motif_matches_exhaustive_esu():
    g = GRAPHS["plc"]
    got = dict(zip(P.FOUR_MOTIFS,
                   WaveRunner(g).run_set(build_forest(FOUR_MOTIF_PLANS))))
    for pat in ("diamond", "4-cycle", "4-path", "4-star"):
        assert got[pat] == exhaustive.exhaustive_count(g, pat)
    assert got["paw"] == exhaustive.exhaustive_count(g, "tailed-triangle")


@pytest.mark.parametrize("name", ["er", "cliq"])
def test_forest_device_host_compaction_agree(name):
    g = GRAPHS[name]
    got = WaveRunner(g).run_set(build_forest(FOUR_MOTIF_PLANS))
    assert got == [reference.four_motif_counts(g)[n] for n in P.FOUR_MOTIFS]


def test_run_set_records_waves():
    """A forest run visits every level of its trie: each interior node's
    expand and each leaf's count shows in ``level_execs``."""
    g = TINY
    forest = build_forest(FOUR_MOTIF_PLANS)
    runner = WaveRunner(g)
    runner.run_set(forest)
    assert runner.metrics.value("feed_chunks") > 0
    want = set(forest.sharing_stats()["forest_ops"])
    assert set(runner.level_execs) == want
    assert {lv for _, lv in runner.level_execs} == {2, 3}
    assert all(n > 0 for n in runner.level_execs.values())


def test_forest_tiny_chunks_agree():
    """Tiny chunks force multi-chunk fan-out at every shared node."""
    g = TINY
    forest = build_forest(FOUR_MOTIF_PLANS)
    assert WaveRunner(g, chunk=128).run_set(forest) == \
        WaveRunner(g).run_set(forest)


def test_session_batches_route_through_forest():
    from repro.mining.apps import shared_session
    g = GRAPHS["er"]
    m = shared_session(g)
    motifs = list(P.FOUR_MOTIF_SHAPES)
    # fused batch == the same queries run independently
    assert m.count_many(motifs) == [m.count(q) for q in motifs]
    t, chain = m.count_many(["triangle", "three-chain"])
    assert [t, chain] == [m.count("triangle"), m.count("three-chain")]
    assert {"triangle": t, "chain": chain} == reference.motif3(g)
    counts = m.count_many([P.TRIANGLE, P.clique_pattern(4)])
    assert counts == [reference.triangle_count(g), reference.clique_count(g, 4)]


# ---------------------------------------------------------------------------
# emit through the forest (FSM feed) + mixed batches
# ---------------------------------------------------------------------------


def test_triangle_emit_through_forest_matches_host_oracle():
    from repro.mining.apps import fsm_pattern_feed, triangle_list_host
    g = GRAPHS["plc"]
    tris = fsm_pattern_feed(g)[0]                # forest-scheduled emit plan
    host = triangle_list_host(g)
    assert tris.shape == host.shape == (reference.triangle_count(g), 3)

    def key(t):
        return t[np.lexsort(t.T[::-1])]
    np.testing.assert_array_equal(key(tris), key(host))


def test_mixed_emit_and_count_batch():
    g = GRAPHS["er"]
    forest = build_forest([P.compile_pattern(P.TRIANGLE, emit=True),
                           P.compile_pattern(P.TRIANGLE),
                           P.compile_pattern(P.THREE_CHAIN_INDUCED)])
    tris, tcount, chains = WaveRunner(g).run_set(forest)
    assert tcount == reference.triangle_count(g)
    assert chains == reference.three_chain_count(g, induced=True)
    assert tris.shape == (tcount, 3)


# ---------------------------------------------------------------------------
# property: random pattern sets fuse without changing any count
# ---------------------------------------------------------------------------


def _assert_forest_matches_independent(pats):
    g = TINY
    plans = [P.compile_pattern(p) for p in pats]
    fused = WaveRunner(g).run_set(build_forest(plans))
    indep = [WaveRunner(g).run(pl) for pl in plans]
    oracle = [reference.pattern_count_oracle(g, p) for p in pats]
    assert fused == indep == oracle, (pats, fused, indep, oracle)


@settings(max_examples=10, deadline=None)
@given(st.data())
def test_random_pattern_sets_fuse_bit_identically(data):
    nplans = data.draw(st.integers(2, 3), label="nplans")
    pats = [_draw_pattern(data) for _ in range(nplans)]
    _assert_forest_matches_independent(pats)


@pytest.mark.parametrize("seed", range(6))
def test_seeded_random_pattern_sets_fuse_bit_identically(seed):
    """Hypothesis-free twin of the property test (fixed corpus): pairs of
    pseudo-random patterns must fuse without changing any count."""
    pats = [_seeded_pattern(2 * seed), _seeded_pattern(2 * seed + 1)]
    _assert_forest_matches_independent(pats)
