"""Pallas kernel sweeps vs the pure-jnp oracles (interpret mode on CPU).

Shape/dtype sweeps per kernel + property tests; the kernels must agree
bit-for-bit on integer outputs and to float32 tolerance on reductions.
"""
import numpy as np
import jax.numpy as jnp
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.stream import SENTINEL
from repro.kernels import ops, ref
from repro.kernels.bitmap import keys_to_bitmap

RNG = np.random.default_rng(7)


def make_rows(batch, cap, hi=4000, rng=RNG, empty_prob=0.1):
    out = np.full((batch, cap), SENTINEL, np.int32)
    for i in range(batch):
        if rng.random() < empty_prob:
            continue
        n = int(rng.integers(1, cap))
        out[i, :n] = np.sort(rng.choice(hi, size=n, replace=False))
    return out


@pytest.mark.parametrize("cap_a,cap_b", [(128, 128), (128, 384), (256, 128),
                                         (384, 640)])
def test_intersect_count_sweep(cap_a, cap_b):
    a = jnp.asarray(make_rows(6, cap_a))
    b = jnp.asarray(make_rows(6, cap_b))
    bounds = jnp.asarray(RNG.choice([SENTINEL, 100, 2000, 3999], size=6)
                         .astype(np.int32))
    got = ops.xinter_count(a, b, bounds, backend="pallas")
    want = ref.intersect_count_ref(a, b, bounds)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("cap_a,cap_b", [(128, 256), (256, 256)])
def test_intersect_rows_sweep(cap_a, cap_b):
    a = jnp.asarray(make_rows(5, cap_a))
    b = jnp.asarray(make_rows(5, cap_b))
    bounds = jnp.asarray(RNG.choice([SENTINEL, 1500], size=5).astype(np.int32))
    rows_p, n_p = ops.xinter(a, b, bounds, backend="pallas")
    rows_x, n_x = ops.xinter(a, b, bounds, backend="xla")
    np.testing.assert_array_equal(rows_p, rows_x)
    np.testing.assert_array_equal(n_p, n_x)


def test_intersect_identical_and_disjoint():
    a = jnp.asarray(make_rows(3, 128, empty_prob=0))
    same = ops.xinter_count(a, a, backend="pallas")
    lens = np.sum(np.asarray(a) != SENTINEL, axis=1)
    np.testing.assert_array_equal(np.asarray(same), lens)
    b = jnp.asarray(np.where(np.asarray(a) != SENTINEL,
                             np.asarray(a) + 100_000, SENTINEL).astype(np.int32))
    np.testing.assert_array_equal(
        np.asarray(ops.xinter_count(a, b, backend="pallas")), 0)


def test_intersect_empty_rows():
    a = jnp.full((2, 128), SENTINEL, jnp.int32)
    b = jnp.asarray(make_rows(2, 128))
    np.testing.assert_array_equal(
        np.asarray(ops.xinter_count(a, b, backend="pallas")), 0)


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 2**31 - 2))
def test_bound_property(bound):
    a = jnp.asarray(make_rows(4, 128))
    b = jnp.asarray(make_rows(4, 128))
    bounds = jnp.full((4,), bound, jnp.int32)
    got = np.asarray(ops.xinter_count(a, b, bounds, backend="pallas"))
    want = np.asarray(ref.intersect_count_ref(a, b, bounds))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("cap_a,cap_b", [(128, 128), (256, 128)])
def test_mark_pallas_matches_xla(cap_a, cap_b):
    a = jnp.asarray(make_rows(5, cap_a))
    b = jnp.asarray(make_rows(5, cap_b))
    got = ops.xmark(a, b, backend="pallas")
    want = ops.xmark(a, b, backend="xla")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("bounded", [False, True])
def test_sub_count_pallas_matches_xla(bounded):
    a = jnp.asarray(make_rows(6, 256))
    b = jnp.asarray(make_rows(6, 128))
    bounds = jnp.asarray(RNG.choice([SENTINEL, 100, 2000], size=6)
                         .astype(np.int32)) if bounded else None
    got = ops.xsub_count(a, b, bounds, backend="pallas")
    want = ops.xsub_count(a, b, bounds, backend="xla")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_sub_compact_pallas_matches_xla():
    a = jnp.asarray(make_rows(6, 256, hi=800))
    b = jnp.asarray(make_rows(6, 128, hi=800))
    bounds = jnp.asarray(RNG.integers(0, 800, 6).astype(np.int32))
    outs_p = ops.xsub_compact(a, b, bounds, out_cap=256, out_items=512,
                              backend="pallas")
    outs_x = ops.xsub_compact(a, b, bounds, out_cap=256, out_items=512,
                              backend="xla")
    for got, want in zip(outs_p, outs_x):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("op", ["mac", "max", "min"])
def test_vinter_sweep(op):
    a = jnp.asarray(make_rows(5, 256))
    b = jnp.asarray(make_rows(5, 128))
    va = jnp.asarray(RNG.normal(size=(5, 256)).astype(np.float32))
    vb = jnp.asarray(RNG.normal(size=(5, 128)).astype(np.float32))
    got = ops.xvinter_mac(a, va, b, vb, op=op, backend="pallas")
    want = ref.vinter_ref(a, va, b, vb, op=op)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_bitmap_vs_merge():
    a = jnp.asarray(make_rows(4, 256, hi=2000))
    b = jnp.asarray(make_rows(4, 256, hi=2000))
    wa, wb = keys_to_bitmap(a, 2000), keys_to_bitmap(b, 2000)
    got = ops.xbitmap_count(wa, wb, backend="pallas")
    want = ops.xinter_count(a, b, backend="xla")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("cap_a,cap_b", [(128, 128), (256, 384)])
def test_lower_bound_pallas_matches_xla_and_bruteforce(cap_a, cap_b):
    """The lb operand (LevelOp.lb threaded into the tile schedule) must
    agree across backends and with a set-algebra oracle, for INTER and SUB
    counts and both fused compaction paths."""
    a = jnp.asarray(make_rows(6, cap_a))
    b = jnp.asarray(make_rows(6, cap_b))
    ub = jnp.asarray(RNG.choice([SENTINEL, 500, 2000, 3500], size=6)
                     .astype(np.int32))
    lb = jnp.asarray(RNG.choice([-1, 100, 1500, 3000], size=6)
                     .astype(np.int32))
    an, bn = np.asarray(a), np.asarray(b)
    for fn, setop in ((ops.xinter_count, lambda A, B: A & B),
                      (ops.xsub_count, lambda A, B: A - B)):
        got_p = np.asarray(fn(a, b, ub, backend="pallas", lbounds=lb))
        got_x = np.asarray(fn(a, b, ub, backend="xla", lbounds=lb))
        want = [len([k for k in setop(
            set(an[i][an[i] != SENTINEL].tolist()),
            set(bn[i][bn[i] != SENTINEL].tolist()))
            if int(lb[i]) < k < int(ub[i])]) for i in range(6)]
        np.testing.assert_array_equal(got_p, got_x)
        np.testing.assert_array_equal(got_p, want)
    for cfn, cap in ((ops.xinter_compact, min(cap_a, cap_b)),
                     (ops.xsub_compact, cap_a)):
        outs_p = cfn(a, b, ub, out_cap=cap, out_items=6 * cap,
                     backend="pallas", lbounds=lb)
        outs_x = cfn(a, b, ub, out_cap=cap, out_items=6 * cap,
                     backend="xla", lbounds=lb)
        for o_p, o_x in zip(outs_p, outs_x):
            np.testing.assert_array_equal(np.asarray(o_p), np.asarray(o_x))


def test_tile_schedule_skips_tiles_below_lower_bound():
    """A-tiles entirely <= lbound get zero visits (whole-tile skip), and the
    schedule still covers every in-window match."""
    from repro.kernels.intersect import TA, TB, tile_schedule
    a = jnp.asarray(make_rows(8, 512, empty_prob=0.0))
    b = jnp.asarray(make_rows(8, 1024, empty_prob=0.0))
    bounds = jnp.full((8,), SENTINEL, jnp.int32)
    lbounds = jnp.asarray(RNG.integers(0, 4000, 8).astype(np.int32))
    lo, nv = tile_schedule(a, b, bounds, lbounds)
    an, bn = np.asarray(a), np.asarray(b)
    lo, nv, lbn = np.asarray(lo), np.asarray(nv), np.asarray(lbounds)
    skipped = 0
    for i in range(8):
        for t in range(an.shape[1] // TA):
            tile = an[i, t * TA:(t + 1) * TA]
            if tile[TA - 1] <= lbn[i]:          # whole tile out of window
                assert nv[i, t] == 0
                skipped += 1
        common = np.intersect1d(an[i][an[i] != SENTINEL],
                                bn[i][bn[i] != SENTINEL])
        for k in common[common > lbn[i]]:
            ti = np.searchsorted(an[i], k) // TA
            tb = np.searchsorted(bn[i], k) // TB
            assert lo[i, ti] <= tb < lo[i, ti] + nv[i, ti], (i, k)
    assert skipped > 0          # the sweep actually exercised the skip


# ---------------------------------------------------------------------------
# fused multi-operand level kernel + prefix-scan compaction
# ---------------------------------------------------------------------------


def _level_bruteforce(a, bs, pol, ub, lb, excl):
    """Set-algebra oracle for the k-operand level keep/count semantics."""
    counts = []
    for i in range(a.shape[0]):
        banned = set(excl[i].tolist()) if excl is not None else set()
        n = 0
        for x in a[i]:
            if x == SENTINEL or not (lb[i] < x < ub[i]) or int(x) in banned:
                continue
            ok = True
            for r, p in enumerate(pol):
                row = set(bs[r, i][bs[r, i] != SENTINEL].tolist())
                ok &= (int(x) in row) if p else (int(x) not in row)
            n += ok
        counts.append(n)
    return counts


@pytest.mark.parametrize("pol", [(1,), (0,), (1, 0), (1, 1), (0, 0),
                                 (1, 1, 0)])
def test_xlevel_count_pallas_matches_xla_and_bruteforce(pol):
    a = jnp.asarray(make_rows(6, 256, hi=1200))
    bs = jnp.stack([jnp.asarray(make_rows(6, 128, hi=1200)) for _ in pol])
    ub = jnp.asarray(RNG.choice([SENTINEL, 300, 900, 0], size=6)
                     .astype(np.int32))       # 0 = bound-0 dead row
    lb = jnp.asarray(RNG.choice([-1, 100, 600], size=6).astype(np.int32))
    ex = jnp.asarray(RNG.integers(0, 1200, (6, 2)).astype(np.int32))
    got_p = np.asarray(ops.xlevel_count(a, bs, pol, ub, backend="pallas",
                                        lbounds=lb, excludes=ex))
    got_x = np.asarray(ops.xlevel_count(a, bs, pol, ub, backend="xla",
                                        lbounds=lb, excludes=ex))
    want = _level_bruteforce(np.asarray(a), np.asarray(bs), pol,
                             np.asarray(ub), np.asarray(lb), np.asarray(ex))
    np.testing.assert_array_equal(got_p, got_x)
    np.testing.assert_array_equal(got_p, want)


@pytest.mark.parametrize("pol", [(1, 0), (1, 1), (0, 0)])
def test_xlevel_compact_pallas_matches_xla(pol):
    a = jnp.asarray(make_rows(6, 256, hi=800))
    bs = jnp.stack([jnp.asarray(make_rows(6, 128, hi=800)) for _ in pol])
    ub = jnp.asarray(RNG.integers(0, 800, 6).astype(np.int32))
    lb = jnp.asarray(RNG.choice([-1, 200], size=6).astype(np.int32))
    outs_p = ops.xlevel_compact(a, bs, pol, ub, out_cap=256, out_items=2048,
                                backend="pallas", lbounds=lb)
    outs_x = ops.xlevel_compact(a, bs, pol, ub, out_cap=256, out_items=2048,
                                backend="xla", lbounds=lb)
    for got, want in zip(outs_p, outs_x):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_xlevel_k1_degenerates_to_single_op_paths():
    """pol=(1,)/(0,) must reproduce the existing fused single-op entry
    points exactly — same counts, same compacted 6-tuple."""
    a = jnp.asarray(make_rows(6, 256, hi=700))
    b = jnp.asarray(make_rows(6, 128, hi=700))
    ub = jnp.asarray(RNG.integers(0, 700, 6).astype(np.int32))
    lb = jnp.asarray(RNG.choice([-1, 150], size=6).astype(np.int32))
    bs = b[None]
    for backend in ("pallas", "xla"):
        np.testing.assert_array_equal(
            np.asarray(ops.xlevel_count(a, bs, (1,), ub, backend=backend,
                                        lbounds=lb)),
            np.asarray(ops.xinter_count(a, b, ub, backend=backend,
                                        lbounds=lb)))
        np.testing.assert_array_equal(
            np.asarray(ops.xlevel_count(a, bs, (0,), ub, backend=backend,
                                        lbounds=lb)),
            np.asarray(ops.xsub_count(a, b, ub, backend=backend,
                                      lbounds=lb)))
        got = ops.xlevel_compact(a, bs, (1,), ub, out_cap=128,
                                 out_items=1024, backend=backend, lbounds=lb)
        want = ops.xinter_compact(a, b, ub, out_cap=128, out_items=1024,
                                  backend=backend, lbounds=lb)
        for o_g, o_w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(o_g), np.asarray(o_w))
        got = ops.xlevel_compact(a, bs, (0,), ub, out_cap=256,
                                 out_items=2048, backend=backend, lbounds=lb)
        want = ops.xsub_compact(a, b, ub, out_cap=256, out_items=2048,
                                backend=backend, lbounds=lb)
        for o_g, o_w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(o_g), np.asarray(o_w))


def test_xlevel_bound0_and_empty_worklists():
    """bound-0 rows (forest residual kills / padding items) and all-sentinel
    worklists must produce zero counts and no survivors on both backends."""
    a_live = jnp.asarray(make_rows(4, 128, empty_prob=0.0))
    a_dead = jnp.full((4, 128), SENTINEL, jnp.int32)
    bs = jnp.stack([a_live, jnp.asarray(make_rows(4, 128))])
    zero = jnp.zeros((4,), jnp.int32)
    for backend in ("pallas", "xla"):
        np.testing.assert_array_equal(
            np.asarray(ops.xlevel_count(a_live, bs, (1, 0), zero,
                                        backend=backend)), 0)
        np.testing.assert_array_equal(
            np.asarray(ops.xlevel_count(a_dead, bs, (1, 0), backend=backend)),
            0)
        rows, counts, src, verts, total, maxc = ops.xlevel_compact(
            a_dead, bs, (1, 0), out_cap=128, out_items=512, backend=backend)
        assert int(total) == 0 and int(maxc) == 0
        assert np.all(np.asarray(rows) == SENTINEL)
        assert np.all(np.asarray(verts) == 0) and np.all(np.asarray(src) == 0)


def test_xlevel_pol_empty_is_pure_window():
    """k=0 (no membership refs — star-like levels): window + excludes only,
    identical across backends (served by the XLA form on both)."""
    a = jnp.asarray(make_rows(5, 128, hi=500))
    ub = jnp.asarray(RNG.integers(0, 500, 5).astype(np.int32))
    ex = jnp.asarray(RNG.integers(0, 500, (5, 1)).astype(np.int32))
    got = np.asarray(ops.xlevel_count(a, None, (), ub, backend="pallas",
                                      excludes=ex))
    want = _level_bruteforce(np.asarray(a), None, (), np.asarray(ub),
                             np.full(5, -1), np.asarray(ex))
    np.testing.assert_array_equal(got, want)


def test_batch_compact_scan_matches_masked_sort_oracle():
    """The O(B·cap) prefix-scan scatter vs the masked-sort oracle: same
    survivor streams, same row-major item order, same scalars."""
    from repro.core.batch import batch_compact_items, batch_compact_scan
    rows = jnp.asarray(make_rows(16, 256, hi=2000))
    keep = jnp.asarray(RNG.random((16, 256)) < 0.35) & (rows != SENTINEL)
    r2, c2, src, verts, total, maxc = batch_compact_scan(rows, keep, 256,
                                                         16 * 256 + 128)
    want_rows = jnp.sort(jnp.where(keep, rows, SENTINEL), axis=1)
    np.testing.assert_array_equal(np.asarray(r2), np.asarray(want_rows))
    np.testing.assert_array_equal(np.asarray(c2),
                                  np.asarray(jnp.sum(keep, axis=1)))
    src_o, verts_o, total_o, maxc_o = batch_compact_items(
        want_rows, c2, 16 * 256 + 128)
    np.testing.assert_array_equal(np.asarray(src), np.asarray(src_o))
    np.testing.assert_array_equal(np.asarray(verts), np.asarray(verts_o))
    assert int(total) == int(total_o) and int(maxc) == int(maxc_o)


def test_compact_rows_pallas_matches_scan():
    from repro.core.batch import batch_compact_rows
    from repro.kernels.compact import compact_rows_pallas
    rows = jnp.asarray(make_rows(8, 256, hi=1500))
    keep = jnp.asarray(RNG.random((8, 256)) < 0.4) & (rows != SENTINEL)
    for out_cap in (256, 128):
        capped = keep & (jnp.cumsum(keep, axis=1) <= out_cap)
        r_p, c_p = compact_rows_pallas(rows, capped, out_cap, interpret=True)
        r_x, c_x = batch_compact_rows(rows, capped, out_cap)
        np.testing.assert_array_equal(np.asarray(r_p), np.asarray(r_x))
        np.testing.assert_array_equal(np.asarray(c_p), np.asarray(c_x))


def test_compact_indices_scan_matches_index_sort():
    from repro.core.batch import compact_indices_scan
    ok = jnp.asarray(RNG.random(512) < 0.3)
    order, tot = compact_indices_scan(ok)
    idx = jnp.arange(512, dtype=jnp.int32)
    want = jnp.sort(jnp.where(ok, idx, SENTINEL))
    live = int(tot)
    np.testing.assert_array_equal(np.asarray(order)[:live],
                                  np.asarray(want)[:live])
    assert np.all(np.asarray(order)[live:] == 0)
    assert live == int(np.asarray(ok).sum())


def test_tile_schedule_visits_are_sound():
    """Every matching key pair must fall inside the scheduled tile range."""
    from repro.kernels.intersect import TA, TB, tile_schedule
    a = jnp.asarray(make_rows(8, 512))
    b = jnp.asarray(make_rows(8, 1024))
    bounds = jnp.full((8,), SENTINEL, jnp.int32)
    lo, nv = tile_schedule(a, b, bounds)
    an, bn = np.asarray(a), np.asarray(b)
    lo, nv = np.asarray(lo), np.asarray(nv)
    for i in range(8):
        common = np.intersect1d(an[i][an[i] != SENTINEL],
                                bn[i][bn[i] != SENTINEL])
        for k in common:
            ti = np.searchsorted(an[i], k) // TA        # a-tile of k
            tb = np.searchsorted(bn[i], k) // TB        # b-tile of k
            assert lo[i, ti] <= tb < lo[i, ti] + nv[i, ti], (i, k)


# ---------------------------------------------------------------------------
# SMEM row split: a batch cut into several pallas_calls gives the same answer
# as one call and as the XLA twin. The SMEM table budget is shrunk so that a
# call takes ``rows`` rows; 37 rows is not a multiple of the kernels' 8-row
# block, so the padding rows are exercised too.
# ---------------------------------------------------------------------------

SPLIT_B = 37


@pytest.fixture
def smem_rows(monkeypatch):
    """``set_rows(rows, n_schedules, n_a_tiles)`` shrinks the SMEM table
    budget so one pallas_call takes ``rows`` rows, and drops the traced
    entries so they retrace under it."""
    import jax
    from repro.kernels import intersect as K

    def set_rows(rows, n_schedules, n_a_tiles):
        monkeypatch.setattr(K, "SMEM_TABLE_BYTES",
                            rows // K.R * 2 * 4 * n_schedules * n_a_tiles)
        jax.clear_caches()
        assert K.rows_per_call(SPLIT_B, n_schedules, n_a_tiles) == rows

    yield set_rows
    jax.clear_caches()


def _pallas_calls(fn, *args):
    import jax
    return str(jax.make_jaxpr(fn)(*args)).count("pallas_call")


def _split_operands(k=0, cap_a=256, cap_b=384, hi=1500):
    a = jnp.asarray(make_rows(SPLIT_B, cap_a, hi=hi))
    bs = jnp.stack([jnp.asarray(make_rows(SPLIT_B, cap_b, hi=hi))
                    for _ in range(max(k, 1))])
    ub = jnp.asarray(RNG.choice([SENTINEL, 300, 900, 0], size=SPLIT_B)
                     .astype(np.int32))
    lb = jnp.asarray(RNG.choice([-1, 100, 600], size=SPLIT_B)
                     .astype(np.int32))
    return a, bs, ub, lb


@pytest.mark.parametrize("rows", [8, 16])
def test_pair_kernels_split_rows(rows, smem_rows):
    from repro.kernels import intersect as K
    a, bs, ub, lb = _split_operands()
    b = bs[0]
    mark1, cnt1 = K.intersect_expand_pallas(a, b, ub, interpret=True,
                                            lbounds=lb)
    smem_rows(rows, 1, a.shape[1] // K.TA)
    assert _pallas_calls(
        lambda a, b: K.intersect_count_pallas(a, b, interpret=True),
        a, b) == -(-40 // rows)
    mark, cnt = K.intersect_expand_pallas(a, b, ub, interpret=True,
                                          lbounds=lb)
    want = np.asarray(ops.xinter_count(a, b, ub, backend="xla", lbounds=lb))
    for got in (cnt1, cnt, K.intersect_count_pallas(
            a, b, ub, interpret=True, lbounds=lb)):
        np.testing.assert_array_equal(np.asarray(got), want)
    np.testing.assert_array_equal(np.asarray(mark), np.asarray(mark1))
    np.testing.assert_array_equal(
        np.asarray(K.intersect_mark_pallas(a, b, ub, interpret=True,
                                           lbounds=lb)),
        np.asarray(mark1))


@pytest.mark.parametrize("rows", [8, 24])
def test_multi_kernels_split_rows(rows, smem_rows):
    from repro.kernels import intersect as K
    pol = (1, 0)
    a, bs, ub, lb = _split_operands(k=2, cap_b=128, hi=1200)
    ex = jnp.asarray(RNG.integers(0, 1200, (SPLIT_B, 2)).astype(np.int32))
    mark1, _ = K.intersect_multi_pallas(a, bs, pol, ub, interpret=True,
                                        lbounds=lb, excludes=ex)
    smem_rows(rows, len(pol), a.shape[1] // K.TA)
    assert _pallas_calls(
        lambda a, bs: K.intersect_multi_pallas(a, bs, pol, interpret=True),
        a, bs) == -(-40 // rows)
    mark, cnt = K.intersect_multi_pallas(a, bs, pol, ub, interpret=True,
                                         lbounds=lb, excludes=ex)
    np.testing.assert_array_equal(np.asarray(mark), np.asarray(mark1))
    np.testing.assert_array_equal(
        np.asarray(cnt),
        np.asarray(ops.xlevel_count(a, bs, pol, ub, backend="xla",
                                    lbounds=lb, excludes=ex)))
    dyadic = [0.25, 0.5, 0.75, 1.0]
    av = jnp.asarray(RNG.choice(dyadic, size=a.shape).astype(np.float32))
    bv = jnp.asarray(RNG.choice(dyadic, size=bs.shape).astype(np.float32))
    scale = jnp.asarray(RNG.choice(dyadic, size=SPLIT_B).astype(np.float32))
    for op in ("sum", "max"):
        _, c, v = K.intersect_multi_agg_pallas(
            a, bs, pol, av, bv, scale, op, ub, interpret=True, lbounds=lb,
            excludes=ex)
        cx, vx = ops.xlevel_agg(a, bs, pol, av, bv, scale, op=op, bounds=ub,
                                backend="xla", lbounds=lb, excludes=ex)
        np.testing.assert_array_equal(np.asarray(c), np.asarray(cx))
        np.testing.assert_array_equal(np.asarray(v), np.asarray(vx))


def test_vinter_split_rows(smem_rows):
    from repro.kernels.svinter import vinter_pallas
    a, bs, _, _ = _split_operands(cap_b=256)
    va = jnp.asarray(RNG.integers(1, 5, size=a.shape).astype(np.float32))
    vb = jnp.asarray(RNG.integers(1, 5, size=bs[0].shape).astype(np.float32))
    smem_rows(8, 1, a.shape[1] // 128)
    got = vinter_pallas(a, va, bs[0], vb, "mac", interpret=True)
    want = ops.xvinter(a, va, bs[0], vb, op="mac", backend="xla")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
