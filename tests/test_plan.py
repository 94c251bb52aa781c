"""Pattern-plan compiler + interpreter: structure, 4-motif oracles, and
oracle agreement for arbitrary compiled plans.

Three layers of checking:
  * compiler unit tests — carry reuse, tail folding, feed selection and
    validation errors on the canned patterns;
  * 4-motif counts vs two independent oracles (brute-force degree-signature
    census in ``reference``, ESU connected-set enumeration in
    ``exhaustive``) on random + generator graphs;
  * a hypothesis property: any randomly generated valid ``Pattern`` compiles
    to a ``WavePlan`` whose execution agrees with the
    permutation-enumeration oracle.
"""
import itertools

import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.graph import build_csr
from repro.graph.generators import clique_planted, erdos_renyi, powerlaw_cluster
from repro.mining import exhaustive, reference
from repro.mining.apps import fsm_pattern_feed, shared_session, \
    triangle_list_host
from repro.mining.engine import WaveRunner
from repro.mining import plan as P

GRAPHS = {
    "er": build_csr(erdos_renyi(60, 240, seed=3), 60),
    "plc": build_csr(powerlaw_cluster(50, 4, seed=5), 50),
    "cliq": build_csr(clique_planted(45, 120, (6, 5), seed=1), 45),
}
TINY = build_csr(erdos_renyi(18, 48, seed=7), 18)


def _four_motif(g):
    names = list(P.FOUR_MOTIF_SHAPES)
    return dict(zip(names, shared_session(g).count_many(names)))


# ---------------------------------------------------------------------------
# compiler structure
# ---------------------------------------------------------------------------


def test_clique_plan_reuses_carry_every_level():
    pl = P.compile_pattern(P.clique_pattern(5))
    assert pl.symmetric
    assert [op.kind for op in pl.ops] == ["expand", "expand", "count"]
    assert not pl.ops[0].use_carry
    assert all(op.use_carry for op in pl.ops[1:])
    for op in pl.ops:
        assert op.inter in ((1,), (2,), (3,))   # one new INTER ref per level
        assert op.ub == op.inter                # bound = newest vertex


def test_tailed_triangle_folds_to_degree_tail():
    pl = P.compile_pattern(P.TAILED_TRIANGLE)
    assert not pl.symmetric                     # no (1,0) restriction
    assert len(pl.ops) == 1
    op = pl.ops[0]
    assert op.kind == "count" and op.tail == (1, 2)
    assert op.inter == (1,) and op.ub == (0,)


def test_three_chain_compiles_sub_and_lower_bound():
    op = P.compile_pattern(P.THREE_CHAIN_INDUCED).ops[0]
    assert op.sub == (1,) and op.lb == (1,) and not op.ub


def test_cycle4_cannot_reuse_carry():
    pl = P.compile_pattern(P.CYCLE4)
    assert [op.use_carry for op in pl.ops] == [False, False]
    assert pl.ops[0].out_cols == (0, 1, 2)      # level 3 references them all


def test_star4_reuses_carry_for_sub_level():
    op = P.compile_pattern(P.STAR4).ops[1]
    assert op.use_carry and op.sub == (2,) and op.ub == (2,)


def test_emit_plan_forwards_all_columns():
    pl = P.compile_pattern(P.TRIANGLE, emit=True)
    assert pl.ops[-1].kind == "emit"
    assert pl.ops[-1].out_cols == (0, 1, 2)


def test_pattern_validation_errors():
    with pytest.raises(ValueError):             # disconnected matching order
        P.pattern("bad", 4, [(0, 1), (0, 2)])
    with pytest.raises(ValueError):             # v0-v1 not an edge
        P.pattern("bad", 3, [(0, 2), (1, 2)])
    with pytest.raises(ValueError):             # wrong feed orientation
        P.compile_pattern(P.pattern("bad", 3, [(0, 1), (0, 2), (1, 2)],
                                    restrictions=[(0, 1)]))
    with pytest.raises(ValueError):             # restriction cycle
        P.compile_pattern(P.pattern("bad", 3, [(0, 1), (0, 2), (1, 2)],
                                    restrictions=[(1, 2), (2, 1)]))


# ---------------------------------------------------------------------------
# 4-motif mining vs independent oracles
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", list(GRAPHS))
def test_four_motif_matches_bruteforce_census(name):
    g = GRAPHS[name]
    assert _four_motif(g) == reference.four_motif_counts(g)


def test_four_motif_matches_exhaustive_esu():
    g = GRAPHS["plc"]
    got = _four_motif(g)
    for pat in ("diamond", "4-cycle", "4-path", "4-star"):
        assert got[pat] == exhaustive.exhaustive_count(g, pat)
    assert got["paw"] == exhaustive.exhaustive_count(g, "tailed-triangle")


@pytest.mark.parametrize("name", ["er", "cliq"])
def test_four_motif_device_host_compaction_agree(name):
    g = GRAPHS[name]
    census = reference.four_motif_counts(g)
    for motif, pat in P.FOUR_MOTIFS.items():
        assert shared_session(g).count(pat) == census[motif], pat.name


def test_tail_count_sum_exact_past_int32():
    """The degree-tail multiplier must stay exact when one chunk's product
    sum crosses 2^31 (the pre-refactor host path multiplied in int64; the
    device path returns per-chunk (hi, lo) int32 partials). On K_n the last
    16384-edge chunk sums ~16384·n·(n-3) ≈ 3e9 > 2^31, and the total has a
    closed form: TT(K_n) = (n-3)(n-2)·n(n-1)/2."""
    n = 450
    g = build_csr(np.array(list(itertools.combinations(range(n), 2))), n)
    want = (n - 3) * (n - 2) * n * (n - 1) // 2
    assert shared_session(g, chunk=16384).count("tailed-triangle") == want


def test_pattern_oracle_consistent_with_references():
    g = TINY
    assert reference.pattern_count_oracle(g, P.TRIANGLE) \
        == reference.triangle_count(g)
    assert reference.pattern_count_oracle(g, P.clique_pattern(4)) \
        == reference.clique_count(g, 4)
    assert reference.pattern_count_oracle(g, P.TAILED_TRIANGLE) \
        == reference.tailed_triangle_count(g)


# ---------------------------------------------------------------------------
# device-resident triangle enumeration (FSM feed)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", list(GRAPHS))
def test_triangle_list_device_matches_host_oracle(name):
    g = GRAPHS[name]
    dev = fsm_pattern_feed(g)[0]
    host = triangle_list_host(g)
    assert dev.shape == host.shape == (reference.triangle_count(g), 3)
    # same triangles (chunk orders differ): compare as sorted row sets
    def key(t):
        return t[np.lexsort(t.T[::-1])]
    np.testing.assert_array_equal(key(dev), key(host))


def test_triangle_list_uses_device_compaction():
    g = GRAPHS["er"]
    runner = WaveRunner(g)
    tris = runner.run(P.compile_pattern(P.TRIANGLE, emit=True))
    assert runner.stats["device_compactions"] > 0
    assert tris.shape[0] == reference.triangle_count(g)


# ---------------------------------------------------------------------------
# property: any compiled plan agrees with the oracle
# ---------------------------------------------------------------------------


def _draw_pattern(data) -> P.Pattern:
    k = data.draw(st.integers(3, 4), label="k")
    edges = {(0, 1)}
    for lvl in range(2, k):                    # keep matching order connected
        edges.add((data.draw(st.integers(0, lvl - 1), label=f"anchor{lvl}"),
                   lvl))
    for i, j in itertools.combinations(range(k), 2):
        if (i, j) not in edges and data.draw(st.booleans(), label=f"e{i}{j}"):
            edges.add((i, j))
    # restrictions: subset of pairs oriented by a random total order => acyclic
    perm = data.draw(st.permutations(list(range(k))), label="order")
    rank = {v: i for i, v in enumerate(perm)}
    restr = []
    for i, j in itertools.combinations(range(k), 2):
        if data.draw(st.booleans(), label=f"r{i}{j}"):
            lo, hi = (i, j) if rank[i] > rank[j] else (j, i)
            if (lo, hi) == (0, 1):
                continue                       # feed orientation must be (1,0)
            restr.append((lo, hi))
    induced = data.draw(st.booleans(), label="induced")
    return P.pattern("random", k, sorted(edges), restrictions=restr,
                     induced=induced)


@settings(max_examples=12, deadline=None)
@given(st.data())
def test_random_plans_agree_with_oracle_both_modes(data):
    pat = _draw_pattern(data)
    g = TINY
    want = reference.pattern_count_oracle(g, pat)
    got = shared_session(g).count(pat)
    assert got == want, (pat, got, want)


@settings(max_examples=8, deadline=None)
@given(st.data())
def test_random_plans_tiny_chunks_agree(data):
    """Tiny chunks force multi-chunk waves + chunk-rounded item buffers."""
    pat = _draw_pattern(data)
    g = TINY
    want = reference.pattern_count_oracle(g, pat)
    assert shared_session(g, chunk=128).count(pat) == want, pat


def _seeded_pattern(seed: int) -> P.Pattern:
    """Deterministic stand-in for the hypothesis draw (runs without the
    package installed; same property, fixed corpus)."""
    import random
    rng = random.Random(seed)

    class _Draw:
        def draw(self, strat, label=None):
            return strat(rng)
    def int_st(lo, hi):
        return lambda r: r.randint(lo, hi)

    def bool_st(r):
        return r.random() < 0.5

    def perm_st(xs):
        return lambda r: r.sample(xs, len(xs))

    class _St:
        integers = staticmethod(int_st)
        booleans = staticmethod(lambda: bool_st)
        permutations = staticmethod(perm_st)
    global st
    real_st, st = st, _St()
    try:
        return _draw_pattern(_Draw())
    finally:
        st = real_st


@pytest.mark.parametrize("seed", range(10))
def test_seeded_random_plans_agree_with_oracle(seed):
    """Hypothesis-free twin of the property test: 10 pseudo-random patterns
    (k ∈ {3,4}, random adjacency/restrictions/inducedness) must agree with
    the permutation-enumeration oracle."""
    pat = _seeded_pattern(seed)
    g = TINY
    want = reference.pattern_count_oracle(g, pat)
    got = shared_session(g).count(pat)
    assert got == want, (pat, got, want)
