"""Fused multi-operand level path vs the host oracles.

``WaveRunner`` dispatches ONE k-operand kernel per general level
(``ops.xlevel_count``/``xlevel_compact``): every mining app's counts equal
the oracles of ``mining/reference.py``, and a general level with k >= 2
references costs one kernel dispatch per executable call.
"""
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.graph import build_csr
from repro.graph.generators import clique_planted, erdos_renyi, powerlaw_cluster
from repro.mining import reference
from repro.mining.apps import triangle_list_host
from repro.mining.engine import WaveRunner
from repro.mining.forest import build_forest
from repro.mining import plan as P

from test_plan import _draw_pattern, _seeded_pattern

GRAPHS = {
    "er": build_csr(erdos_renyi(90, 540, seed=23), 90),
    "plc": build_csr(powerlaw_cluster(70, 5, seed=2), 70),
    "cliq": build_csr(clique_planted(60, 180, (6, 5), seed=4), 60),
}
TINY = build_csr(erdos_renyi(18, 48, seed=7), 18)

# every paper app + the 4-motif family as compiled plans (FSM's feed is the
# triangle emit plan, covered by the emit test below)
APP_PLANS = {
    "T": P.compile_pattern(P.TRIANGLE),
    "TS": P.compile_pattern(P.TRIANGLE_NESTED),
    "TC": P.compile_pattern(P.THREE_CHAIN_INDUCED),
    "TT": P.compile_pattern(P.TAILED_TRIANGLE),
    "4C": P.compile_pattern(P.clique_pattern(4)),
    "5C": P.compile_pattern(P.clique_pattern(5)),
    **{name: P.compile_pattern(p) for name, p in P.FOUR_MOTIFS.items()},
}


# fast oracles per app (the permutation oracle is reserved for TINY — it is
# O(n^k · k!) and the census/closed forms already cover these patterns)
_ORACLE = {
    "T": reference.triangle_count,
    "TS": reference.triangle_count,
    "TC": lambda g: reference.three_chain_count(g, induced=True),
    "TT": reference.tailed_triangle_count,
    "4C": lambda g: reference.clique_count(g, 4),
    "5C": lambda g: reference.clique_count(g, 5),
    **{name: (lambda g, _n=name: reference.four_motif_counts(g)[_n])
       for name in P.FOUR_MOTIFS},
}


@pytest.mark.parametrize("name", list(APP_PLANS))
def test_apps_bit_identical_fused_on_off(name):
    g = GRAPHS["er"]
    got = WaveRunner(g).run(APP_PLANS[name])
    assert got == _ORACLE[name](g), (name, got)


@pytest.mark.parametrize("gname", list(GRAPHS))
def test_four_motif_forest_fused_on_off(gname):
    """The F4M batch (shared expands + residual-packed branches) through
    run_set vs independent plans and the brute-force census."""
    g = GRAPHS[gname]
    plans = [P.compile_pattern(p) for p in P.FOUR_MOTIFS.values()]
    fused = WaveRunner(g).run_set(build_forest(plans))
    indep = [WaveRunner(g).run(pl) for pl in plans]
    census = reference.four_motif_counts(g)
    assert fused == indep == [census[n] for n in P.FOUR_MOTIFS]


def test_three_motif_and_fsm_feed_fused_on_off():
    g = GRAPHS["plc"]
    t3 = [P.compile_pattern(P.TRIANGLE),
          P.compile_pattern(P.THREE_CHAIN_INDUCED)]
    t, chain = WaveRunner(g).run_set(build_forest(t3))
    assert {"triangle": t, "chain": chain} == reference.motif3(g)
    # FSM's engine feed: the triangle emit plan — embeddings, not counts
    emit = WaveRunner(g).run(P.compile_pattern(P.TRIANGLE, emit=True))
    np.testing.assert_array_equal(emit, triangle_list_host(g))


def test_tiny_chunks_fused_on_off():
    """Tiny chunks force multi-chunk waves + chunk-rounded item buffers
    through the scan compaction."""
    g = GRAPHS["cliq"]
    census = reference.four_motif_counts(g)
    for name in ("4-cycle", "paw"):
        plan = APP_PLANS[name]
        assert WaveRunner(g, chunk=128).run(plan) == census[name]


def test_dispatch_count_drops_from_k_to_one():
    """4-cycle's general level (inter + sub refs, k=2) must cost exactly one
    kernel dispatch per executable call, not one per reference."""
    g = GRAPHS["er"]
    plan = APP_PLANS["4-cycle"]
    runner = WaveRunner(g)
    assert runner.run(plan) == _ORACLE["4-cycle"](g)
    k3 = len(plan.ops[-1].inter) + len(plan.ops[-1].sub)
    assert k3 == 2                                  # inter (2,), sub (1,)
    # the other levels each hold one ref, so one dispatch a call
    assert all(len(op.inter) + len(op.sub) == 1 for op in plan.ops[:-1])
    n3 = runner.level_execs[("count", 3)]
    assert n3 > 0
    # every level costs one dispatch a call: the k=2 level no more
    assert runner.stats["level_kernel_dispatches"] \
        == sum(runner.level_execs.values())


def test_pallas_backend_fused_level_agrees():
    """The interpret-mode Pallas kernels through the engine's fused path
    (the TPU configuration, minus the hardware). One multi-operand pattern
    on a micro graph with a small chunk: interpret mode executes the grid
    as a Python loop, so every extra padded row costs wall clock — the
    k-operand kernel's full parity sweep lives in test_kernels.py."""
    g = build_csr(erdos_renyi(12, 30, seed=5), 12)
    plan = APP_PLANS["4-cycle"]
    got = WaveRunner(g, chunk=128, backend="pallas").run(plan)
    assert got == reference.pattern_count_oracle(g, plan.pattern)


def _assert_fused_level_invariant(pat):
    g = TINY
    plan = P.compile_pattern(pat)
    got = WaveRunner(g).run(plan)
    want = reference.pattern_count_oracle(g, pat)
    assert got == want, (pat, got, want)


@settings(max_examples=12, deadline=None)
@given(st.data())
def test_random_patterns_fused_level_bit_identical(data):
    _assert_fused_level_invariant(_draw_pattern(data))


@pytest.mark.parametrize("seed", range(8))
def test_seeded_random_patterns_fused_level_bit_identical(seed):
    """Hypothesis-free twin (fixed corpus) of the property above."""
    _assert_fused_level_invariant(_seeded_pattern(seed))
