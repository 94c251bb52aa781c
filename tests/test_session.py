"""Miner session API: compile → schedule → execute pipeline contracts.

Four contracts from the session redesign:
  * **reuse** — a second identical query on one session performs ZERO new
    traces (the lifted ``ExecutableCache``'s miss counter is the retrace
    counter), and executables survive the runner that built them;
  * **isolation** — two ``Miner``s on different graphs share nothing:
    counts stay correct and each session's caches are its own;
  * **auto-scheduling** — the matching-order search over the adjacency-only
    ``FOUR_MOTIF_SHAPES`` reproduces at least the hand-tuned sharing
    (level-2 shared nodes <= 3, feed passes <= 2) with counts bit-identical
    to the independent per-plan runs and the brute-force census, and no
    pattern definition carries a hand-written order or restriction;
  * **count-rides-expand** — a terminal count leaf whose stream and
    constraints match a sibling expand dispatches no kernel and still
    counts exactly (device and host compaction).
"""
import numpy as np
import pytest

from repro.graph import build_csr
from repro.graph.generators import clique_planted, erdos_renyi, \
    powerlaw_cluster
from repro.mining import apps, reference
from repro.mining import plan as P
from repro.mining.engine import WaveRunner
from repro.mining.session import ExecutableCache, Miner

GRAPHS = {
    "er": build_csr(erdos_renyi(60, 240, seed=3), 60),
    "plc": build_csr(powerlaw_cluster(50, 4, seed=5), 50),
    "cliq": build_csr(clique_planted(45, 120, (6, 5), seed=1), 45),
}
TINY = build_csr(erdos_renyi(18, 48, seed=7), 18)

MOTIF_NAMES = list(P.FOUR_MOTIF_SHAPES)


# ---------------------------------------------------------------------------
# session reuse: repeated queries never retrace
# ---------------------------------------------------------------------------


def test_repeated_count_zero_retraces():
    m = Miner(GRAPHS["er"])
    first = m.count("triangle")
    traced = m.stats["retraces"]
    assert traced > 0                       # the first query did compile
    assert m.count("triangle") == first
    st = m.stats
    assert st["retraces"] == traced         # second query: 0 new traces
    assert st["exec_cache"]["hits"] > 0
    assert st["plan_hits"] == 1


def test_repeated_batch_zero_retraces_and_bit_identical():
    m = Miner(GRAPHS["plc"])
    first = m.count_many(MOTIF_NAMES)
    traced = m.stats["retraces"]
    again = m.count_many(MOTIF_NAMES)
    st = m.stats
    assert again == first
    assert st["retraces"] == traced
    assert st["schedule_hits"] == 1 and st["schedule_misses"] == 1


def test_executables_outlive_the_runner():
    """The lifted cache is session state, not runner state: a second runner
    built on the same session cache starts fully warm."""
    g = GRAPHS["er"]
    cache = ExecutableCache()
    plan = P.compile_pattern(P.TRIANGLE)
    r1 = WaveRunner(g, exec_cache=cache)
    want = r1.run(plan)
    assert r1.stats["exec_misses"] == cache.misses > 0
    r2 = WaveRunner(g, exec_cache=cache)
    assert r2.run(plan) == want
    assert r2.stats["exec_misses"] == 0     # every executable reused
    assert r2.stats["exec_hits"] > 0


def test_query_forms_share_traces():
    """The same pattern asked by name and as an explicit ``Pattern`` lands
    on the same compiled plan and executables — 0 new traces (LevelOps and
    plans hash by value, not by query spelling)."""
    m = Miner(GRAPHS["er"])
    want = m.count("triangle")
    traced = m.stats["retraces"]
    assert m.count(P.TRIANGLE) == want
    assert m.stats["retraces"] == traced


# ---------------------------------------------------------------------------
# session isolation
# ---------------------------------------------------------------------------


def test_two_sessions_do_not_cross_contaminate():
    ga, gb = GRAPHS["er"], GRAPHS["cliq"]
    ma, mb = Miner(ga), Miner(gb)
    ta = ma.count("triangle")
    tb = mb.count("triangle")
    assert ta == reference.triangle_count(ga)
    assert tb == reference.triangle_count(gb)
    assert ta != tb                          # the graphs genuinely differ
    # caches are per-session: B compiled its own traces, A's were untouched
    assert ma.exec_cache is not mb.exec_cache
    assert mb.stats["retraces"] > 0
    # interleaved repeats stay warm per session
    ra, rb = ma.stats["retraces"], mb.stats["retraces"]
    assert ma.count("triangle") == ta and mb.count("triangle") == tb
    assert (ma.stats["retraces"], mb.stats["retraces"]) == (ra, rb)


def test_shared_session_pool_reuses_and_isolates():
    ga, gb = GRAPHS["er"], GRAPHS["plc"]
    ma = apps.shared_session(ga)
    assert apps.shared_session(ga) is ma
    assert apps.shared_session(gb) is not ma
    assert apps.shared_session(ga, chunk=128) is not ma   # config keyed


# ---------------------------------------------------------------------------
# automatic matching-order search
# ---------------------------------------------------------------------------


def test_shapes_carry_no_hand_ordering():
    """The 4-motif definitions are adjacency-only: no restrictions, no
    chosen matching order anywhere — ordering is derived."""
    for shape in P.FOUR_MOTIF_SHAPES.values():
        assert isinstance(shape, P.Motif)
        assert not hasattr(shape, "restrictions")
    for name, pat in P.FOUR_MOTIFS.items():
        # every scheduled pattern's restrictions are exactly the
        # automorphism-derived set for its chosen order — nothing bespoke
        assert pat.restrictions == P.auto_restrictions(pat.adj), name
        assert pat.div == 1


def test_auto_schedule_matches_hand_tuned_sharing():
    m = Miner(GRAPHS["er"])
    st = m.schedule(MOTIF_NAMES).sharing_stats()
    assert st["plan_ops"][("expand", 2)] == 6
    assert st["forest_ops"][("expand", 2)] <= 3    # hand-tuned bound
    assert st["feed_passes"]["fused"] <= 2


@pytest.mark.parametrize("name", list(GRAPHS))
def test_auto_scheduled_counts_bit_identical_and_exact(name):
    g = GRAPHS[name]
    m = Miner(g)
    fused = m.count_many(MOTIF_NAMES)
    indep = [m.count(P.FOUR_MOTIFS[n]) for n in MOTIF_NAMES]
    assert fused == indep
    assert dict(zip(MOTIF_NAMES, fused)) == reference.four_motif_counts(g)


def test_auto_schedule_device_host_agree():
    g = GRAPHS["cliq"]
    got = Miner(g).count_many(MOTIF_NAMES)
    census = reference.four_motif_counts(g)
    assert got == [census[n] for n in MOTIF_NAMES]


@pytest.mark.parametrize("seed", range(6))
def test_auto_restrictions_count_exactly_once(seed):
    """Random connected motifs: the automorphism-derived restrictions must
    count every embedding exactly once (vs the permutation oracle)."""
    import itertools
    import random
    rng = random.Random(seed)
    k = rng.choice([3, 4])
    edges = {(0, 1)} | {(rng.randint(0, lvl - 1), lvl)
                        for lvl in range(2, k)}
    for i, j in itertools.combinations(range(k), 2):
        if (i, j) not in edges and rng.random() < 0.5:
            edges.add((i, j))
    shape = P.motif("rand", k, sorted(edges), induced=bool(seed % 2))
    m = Miner(TINY)
    got = m.count(shape)
    pat = m.compile(shape).pattern
    assert got == reference.pattern_count_oracle(TINY, pat), (shape, pat)


# ---------------------------------------------------------------------------
# count-rides-expand fusion
# ---------------------------------------------------------------------------


def test_clique_count_rides_sibling_expand():
    """[4C, 5C]: the 4-clique's terminal count matches the 5-clique's
    level-3 expand exactly, so it reads that expand's counts vector —
    no ('count', 3) dispatch at all — and stays exact."""
    g = GRAPHS["cliq"]
    m = Miner(g)
    got = m.count_many([P.clique_pattern(4), P.clique_pattern(5)])
    assert got == [reference.clique_count(g, 4), reference.clique_count(g, 5)]
    assert ("count", 3) not in m.runner.level_execs
    assert m.runner.stats["count_rides"] > 0
    st = m.schedule([P.clique_pattern(4), P.clique_pattern(5)]) \
        .sharing_stats()
    assert st["count_rides"] == 1
    assert ("count", 3) not in st["forest_ops"]


def test_triangle_rides_wing_expand():
    """[T, 4C]: the triangle count leaf (ub = v1) equals the 4-clique's
    level-2 wing expand — one stream feeds both results."""
    g = GRAPHS["plc"]
    m = Miner(g)
    got = m.count_many([P.TRIANGLE, P.clique_pattern(4)])
    assert got == [reference.triangle_count(g),
                   reference.clique_count(g, 4)]
    assert ("count", 2) not in m.runner.level_execs
    assert m.runner.stats["count_rides"] > 0


def test_ride_does_not_fire_when_bounds_differ():
    """The 4-clique leaf must NOT ride the relaxed 4-motif wing expand
    (relaxation dropped the bound the leaf needs) — rides require exact
    constraint equality."""
    m = Miner(TINY)
    st = m.schedule(MOTIF_NAMES).sharing_stats()
    assert st["count_rides"] == 0
    assert st["forest_ops"][("count", 3)] == 6


def test_ride_tiny_chunks_agree():
    g = GRAPHS["cliq"]
    queries = [P.clique_pattern(4), P.clique_pattern(5)]
    assert Miner(g, chunk=128).count_many(queries) == \
        Miner(g).count_many(queries)


# ---------------------------------------------------------------------------
# embeddings + pipeline surface
# ---------------------------------------------------------------------------


def test_session_embeddings_match_host_oracle():
    g = GRAPHS["plc"]
    m = Miner(g)
    tris = m.embeddings("triangle")
    host = apps.triangle_list_host(g)
    assert tris.shape == host.shape == (reference.triangle_count(g), 3)

    def key(t):
        return t[np.lexsort(t.T[::-1])]
    np.testing.assert_array_equal(key(tris), key(host))
    before = m.stats["retraces"]
    m.embeddings("triangle")                 # warm repeat
    assert m.stats["retraces"] == before


def test_unknown_query_rejected():
    m = Miner(TINY)
    with pytest.raises(ValueError):
        m.count("no-such-pattern")


def test_compile_schedule_stages_cache():
    m = Miner(TINY)
    pl1 = m.compile("triangle")
    pl2 = m.compile("triangle")
    assert pl1 is pl2
    f1 = m.schedule(MOTIF_NAMES)
    f2 = m.schedule(MOTIF_NAMES)
    assert f1 is f2
    assert m.stats["schedule_misses"] == 1
