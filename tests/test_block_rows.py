"""``padded_rows`` as a 128-lane block gather from the lane-aligned plane.

``build_csr`` lays every N(v) out from lane 0 of block ``block_ptr[v]`` of
``blocks`` (SENTINEL elsewhere, one all-SENTINEL block last), and
``padded_rows`` reads ``cap // LANE`` whole blocks per row. Checked here:

  * ``padded_rows`` equals a numpy reference at every LANE-multiple cap
    from 128 to 2,048, and on the edge cases: degree 0, a degree that is
    a multiple of 128, truncation (degree > cap), vertex 0 and the last
    vertex (whose block run is clipped to the all-SENTINEL block);
  * the plane's invariants, and that ``with_edge_values`` keeps it;
  * the lowered gather reads blocks (slice ``(1, 128)``) and no gather
    reads ``indices`` one int32 at a time.
"""
import re

import jax
import numpy as np
import pytest

from repro.core.stream import LANE, SENTINEL
from repro.graph import build_csr, padded_rows, with_edge_values
from repro.graph.generators import erdos_renyi, powerlaw_cluster

N = 600


def _crafted():
    """Holme–Kim on ids 10..609 plus exact degrees: vertex 0 has 256
    neighbours (a LANE multiple), vertex 1 none, vertex 2 has 2,100 (more
    than every cap up to 2,048), the last vertex 128."""
    v = 2600
    hk = powerlaw_cluster(N, 6, seed=3) + 10
    extra = [np.stack([np.zeros(256, int), np.arange(3, 259)], 1),
             np.stack([np.full(2100, 2), np.arange(3, 2103)], 1),
             np.stack([np.full(128, v - 1), np.arange(300, 428)], 1)]
    return build_csr(np.concatenate([hk, *extra]), v)


GRAPHS = {
    "crafted": _crafted(),
    "er": build_csr(erdos_renyi(300, 2400, seed=1), 300),
    "plc": build_csr(powerlaw_cluster(N, 9, seed=0, tri_p=0.27), N),
}
G = GRAPHS["crafted"]
DEG = np.asarray(G.degrees)


def _reference(g, vs, cap):
    indptr = np.asarray(g.indptr)
    indices = np.asarray(g.indices)
    rows = np.full((len(vs), cap), SENTINEL, np.int32)
    lens = np.zeros(len(vs), np.int32)
    for i, v in enumerate(vs):
        nbr = indices[indptr[v]: indptr[v + 1]][:cap]
        rows[i, : nbr.size] = nbr
        lens[i] = nbr.size
    return rows, lens


def _check(g, vs, cap):
    rows, lens = padded_rows(g, np.asarray(vs, np.int32), cap)
    want_rows, want_lens = _reference(g, vs, cap)
    np.testing.assert_array_equal(np.asarray(rows), want_rows)
    np.testing.assert_array_equal(np.asarray(lens), want_lens)


def test_crafted_degrees():
    assert (DEG[0], DEG[1], DEG[2], DEG[-1]) == (256, 0, 2100, 128)


@pytest.mark.parametrize("cap", range(LANE, 2048 + 1, LANE))
def test_padded_rows_equal_the_reference(cap):
    rng = np.random.default_rng(cap)
    vs = rng.integers(0, G.num_vertices, 700)
    _check(G, np.concatenate([[0, 1, 2, G.num_vertices - 1], vs]), cap)


EDGES = {
    "degree 0": 1,
    "degree a multiple of 128": 0,
    "degree above cap": 2,
    "vertex 0": 0,
    "last vertex": G.num_vertices - 1,
}


@pytest.mark.parametrize("cap", [128, 512])
@pytest.mark.parametrize("case", list(EDGES))
def test_padded_rows_edge_cases(case, cap):
    v = EDGES[case]
    _check(G, [v, v], cap)
    _check(G, [v], cap)


def test_last_vertex_block_run_is_clipped():
    """The last vertex's run of 2,048 / 128 blocks passes the plane's end;
    the clip lands on the final block, which holds no key."""
    last = G.num_vertices - 1
    nblocks = G.blocks.shape[0] - 1
    assert int(G.block_ptr[last]) + 2048 // LANE > nblocks
    assert np.all(np.asarray(G.blocks[-1]) == SENTINEL)
    _check(G, [last], 2048)


@pytest.mark.parametrize("name", list(GRAPHS))
def test_block_plane_is_lane_aligned(name):
    g = GRAPHS[name]
    deg = np.asarray(g.degrees)
    indptr = np.asarray(g.indptr)
    indices = np.asarray(g.indices)
    blocks = np.asarray(g.blocks)
    bptr = np.asarray(g.block_ptr)
    nblk = -(-deg // LANE)
    assert blocks.shape == (nblk.sum() + 1, LANE)
    assert blocks.dtype == np.int32 and bptr.dtype == np.int32
    np.testing.assert_array_equal(bptr, np.cumsum(nblk) - nblk)
    flat = blocks.reshape(-1)
    held = np.zeros(flat.size, bool)
    for v in range(g.num_vertices):
        at = bptr[v] * LANE
        np.testing.assert_array_equal(flat[at: at + deg[v]],
                                      indices[indptr[v]: indptr[v + 1]])
        held[at: at + deg[v]] = True
    assert np.all(flat[~held] == SENTINEL)
    assert np.all(blocks[-1] == SENTINEL)


def test_with_edge_values_keeps_the_plane():
    g = GRAPHS["er"]
    gw = with_edge_values(g, np.ones(g.num_edges, np.float32))
    assert gw.blocks is g.blocks and gw.block_ptr is g.block_ptr
    _check(gw, np.arange(g.num_vertices), 128)


def test_capacity_must_be_a_lane_multiple():
    with pytest.raises(AssertionError):
        padded_rows(G, np.array([0], np.int32), 200)


GATHER = re.compile(r"stablehlo\.gather.*slice_sizes = array<i64: ([0-9, ]+)>"
                    r".*:\s*\(tensor<([0-9x]+)xi32>")


def _gathers(cap):
    vs = jax.ShapeDtypeStruct((64,), np.int32)
    text = jax.jit(lambda g, v: padded_rows(g, v, cap)).lower(G, vs).as_text()
    return [(m.group(1), m.group(2)) for m in GATHER.finditer(text)]


@pytest.mark.parametrize("cap", [128, 1024])
def test_lowered_gather_reads_whole_blocks(cap):
    gathers = _gathers(cap)
    blocks_shape = "x".join(map(str, G.blocks.shape))
    assert ("1, 128", blocks_shape) in gathers
    indices_shape = str(G.indices.shape[0])
    assert indices_shape not in {str(G.num_vertices), str(G.num_vertices + 1)}
    assert not [s for s, operand in gathers
                if operand == indices_shape], gathers
