"""Count leaves sum their chunk partials on the device.

A count leaf's executable adds each chunk's exact partial to one int32
limb accumulator per leaf node (``engine.count_acc_add``: weights 2^0,
2^16, 2^32, carried after every add), and ``run_set`` reads every
accumulator once, at the query's end. Checked here:

  * the add is exact at its wrap edges: partials at their maxima, over
    more adds than a plain int32 sum survives, and a running total that
    ends just below 2^63 — against Python ints;
  * a (hi, lo) pair and the psum'd limb quad of the same partial add the
    same amount;
  * counts split into many chunks equal the reference and the one-chunk
    run, while ``host_syncs`` stays one read per count leaf a query;
  * a forest whose leaf is shared by two plans credits both from one
    accumulator, bit-identical to per-plan counts.

The accumulator never needs a host read before the query's end (no limb
can wrap below a 2^63 total), so there is no flush read to force.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.graph import build_csr
from repro.graph.generators import powerlaw_cluster
from repro.mining import Miner, reference
from repro.mining import plan as P
from repro.mining.engine import (COUNT_LIMBS, WaveRunner, count_acc_add,
                                 count_acc_value)
from repro.mining.shard import ShardedWaveRunner
from repro.obs import Telemetry

HI_MAX = (1 << 30) - 1          # Σ(count >> 16) over a 2^15-item chunk
LO_MAX = (1 << 31) - 1          # Σ(count & 0xffff), the int32 maximum


def _limbs(total: int) -> jnp.ndarray:
    return jnp.array([total & 0xFFFF, (total >> 16) & 0xFFFF, total >> 32],
                     jnp.int32)


@jax.jit
def _add_pairs(acc, pairs):
    def step(a, part):
        return count_acc_add(a, *WaveRunner._count_weights(part)), None
    return jax.lax.scan(step, acc, pairs)[0]


@jax.jit
def _add_quads(acc, quads):
    def step(a, q):
        return count_acc_add(a, *ShardedWaveRunner._count_weights(q)), None
    return jax.lax.scan(step, acc, quads)[0]


def _quad(hi: int, lo: int) -> list[int]:
    return [hi >> 16, hi & 0xFFFF, lo >> 16, lo & 0xFFFF]


@pytest.mark.parametrize("adds", [1, 2, 3, 4096])
def test_accumulator_is_exact_over_maximal_partials(adds):
    pairs = jnp.array([[HI_MAX, LO_MAX]] * adds, jnp.int32)
    acc = _add_pairs(jnp.zeros(COUNT_LIMBS, jnp.int32), pairs)
    want = adds * ((HI_MAX << 16) + LO_MAX)
    assert count_acc_value(acc) == want
    assert 0 <= int(acc[0]) < 1 << 16 and 0 <= int(acc[1]) < 1 << 16
    if adds > 1:
        # what one int32 word per pair word would have read
        naive = (int(np.full(adds, HI_MAX, np.int32).sum(dtype=np.int32))
                 << 16) + int(np.full(adds, LO_MAX, np.int32).sum(
                     dtype=np.int32))
        assert naive != want


def test_accumulator_is_exact_up_to_a_total_just_below_2_63():
    adds = 64
    step = (HI_MAX << 16) + LO_MAX
    start = (1 << 63) - 1 - adds * step
    pairs = jnp.array([[HI_MAX, LO_MAX]] * adds, jnp.int32)
    acc = _add_pairs(_limbs(start), pairs)
    assert count_acc_value(acc) == (1 << 63) - 1
    assert int(acc[2]) == (1 << 31) - 1          # the top limb's ceiling
    assert [int(x) for x in acc] == [0xFFFF, 0xFFFF, (1 << 31) - 1]


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_pairs_and_limb_quads_add_the_same_amount(seed):
    rng = np.random.default_rng(seed)
    his = rng.integers(0, HI_MAX + 1, 300)
    los = rng.integers(0, LO_MAX + 1, 300)
    his[:5], los[:5] = HI_MAX, LO_MAX
    zero = jnp.zeros(COUNT_LIMBS, jnp.int32)
    a = _add_pairs(zero, jnp.array(np.stack([his, los], 1), jnp.int32))
    b = _add_quads(zero, jnp.array([_quad(int(h), int(lo))
                                    for h, lo in zip(his, los)], jnp.int32))
    want = sum((int(h) << 16) + int(lo) for h, lo in zip(his, los))
    assert count_acc_value(a) == count_acc_value(b) == want
    assert np.array_equal(np.asarray(a), np.asarray(b))


def test_quads_summed_over_a_mesh_stay_exact():
    """A psum over eight shards of maximal pairs: each limb sum is eight
    times a 16-bit limb, still far inside int32."""
    shards = 8
    q = [shards * x for x in _quad(HI_MAX, LO_MAX)]
    acc = _add_quads(jnp.zeros(COUNT_LIMBS, jnp.int32),
                     jnp.array([q] * 1000, jnp.int32))
    assert count_acc_value(acc) == 1000 * shards * ((HI_MAX << 16) + LO_MAX)


G = build_csr(powerlaw_cluster(300, 6, seed=4), 300)


@pytest.mark.parametrize("query, metas", [("triangle", False),
                                          ("4-clique", True)])
def test_many_chunks_count_the_same_and_read_each_leaf_once(query, metas):
    want = reference.triangle_count(G) if query == "triangle" \
        else reference.clique_count(G, 4)
    syncs, dispatches = {}, {}
    for chunk in (None, 128):
        m = Miner(G, chunk=chunk)
        st = m.runner.stats
        assert m.count(query) == want > 0
        s0, d0 = st["host_syncs"], st["level_kernel_dispatches"]
        assert m.count(query) == want
        syncs[chunk] = st["host_syncs"] - s0
        dispatches[chunk] = st["level_kernel_dispatches"] - d0
        metas_run = m.runner.level_execs.get(("expand", 2), 0) // 2
        # one read of the count leaf; every other sync is an expand's meta
        assert syncs[chunk] == 1 + metas_run
    assert dispatches[128] > dispatches[None]
    if not metas:
        assert syncs[128] == syncs[None] == 1


def test_shared_leaf_credits_every_plan_from_one_accumulator():
    shapes = list(P.FOUR_MOTIF_SHAPES)
    shapes.append(shapes[0])
    m = Miner(G, chunk=128)
    forest = m.schedule(shapes)
    shared = [n for n in _leaves(forest) if len(n.plans) > 1]
    assert shared, "the repeated shape must share its count leaf"
    got = list(m.count_many(shapes))
    solo = [Miner(G, chunk=128).count(s) for s in shapes]
    assert got == solo
    assert got[-1] == got[0]
    # one read per count leaf node, not per plan and not per chunk, beside
    # the expand metas and residual packs (each in a ``sync`` span)
    tel = Telemetry(enabled=True)
    r = WaveRunner(G, chunk=128, telemetry=tel)
    r.run_set(forest)
    leaves = len(_leaves(forest))
    assert r.level_execs[("count", 3)] > leaves
    assert r.stats["host_syncs"] == len(tel.tracer.spans("sync")) + leaves


def _leaves(forest):
    out = []

    def walk(n):
        if n.op.kind == "count":
            out.append(n)
        for ch in n.children:
            walk(ch)
    for r in forest.all_roots():
        walk(r)
    return out
