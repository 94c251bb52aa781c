"""Observability (``repro.obs``): registry, span trees, exporters, and the
legacy-stats bit-identity contract.

The telemetry layer makes two promises the rest of the repo leans on:

  * **derived view, not a fork** — the engine's historical ``stats`` dicts
    are live views over the ``MetricsRegistry``; ``dict(runner.stats)``
    must reproduce the pre-registry dicts bit-for-bit (keys, order,
    values, write-through), golden-tested here against values recorded
    before the registry existed;
  * **observationally free** — enabling the tracer changes no counter and
    adds no kernel dispatches; disabling it records no spans at all.

Span-tree structure is pinned per app shape (single triangle query, fused
4-motif forest, mesh-8 sharded query) and the Chrome-trace export is
schema-checked: JSON round-trips, events are "X" phases, and children
nest inside their parent's interval.
"""
import json

import pytest

import jax

from repro.graph import build_csr
from repro.graph.generators import erdos_renyi, powerlaw_cluster
from repro.mining.engine import WaveRunner
from repro.mining.plan import FOUR_MOTIF_SHAPES
from repro.mining.session import Miner
from repro.obs import (LegacyStatsView, MetricsRegistry, Telemetry, Tracer,
                       chrome_trace)

needs8 = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs 8 devices "
           "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")


def _er_graph():
    return build_csr(erdos_renyi(140, 900, seed=13), 140)


def _pl_graph():
    return build_csr(powerlaw_cluster(110, 5, seed=7), 110)


# --------------------------------------------------------------- registry

def test_registry_typed_and_labeled():
    reg = MetricsRegistry()
    c = reg.counter("dispatches")
    c.inc()
    c.inc(4)
    assert reg.value("dispatches") == 5
    # one name is one type: re-requesting as another kind raises
    with pytest.raises(TypeError):
        reg.gauge("dispatches")
    # labeled family: one instrument per label set, shared name
    for s in range(3):
        reg.counter("feed", shard=s).inc(s)
    fam = reg.series("feed")
    assert len(fam) == 3
    assert fam[(("shard", 2),)].value == 2
    snap = reg.snapshot()
    assert snap["dispatches"] == 5
    assert snap["feed"] == {"shard=0": 0, "shard=1": 1, "shard=2": 2}


def test_counter_underflow_raises():
    # the count-rides path subtracts host syncs it knows it never paid;
    # drifting below zero is a bookkeeping bug, not arithmetic to absorb
    reg = MetricsRegistry()
    c = reg.counter("host_syncs")
    c.inc(2)
    c.dec(2)
    assert c.value == 0
    with pytest.raises(ValueError, match="underflow"):
        c.dec()


def test_histogram_snapshot():
    reg = MetricsRegistry()
    h = reg.histogram("batch_sizes")
    for v in (1, 10, 100):
        h.observe(v)
    snap = h.snapshot()
    assert snap["count"] == 3 and snap["sum"] == 111.0
    assert snap["min"] == 1.0 and snap["max"] == 100.0
    assert sum(h.buckets) == 3


def test_legacy_view_write_through_and_order():
    reg = MetricsRegistry()
    view = LegacyStatsView()
    for k in ("b_second", "a_first"):          # registration != sorted order
        view.expose_counter(k, reg)
    assert list(view) == ["b_second", "a_first"]
    view["a_first"] = 7                        # legacy `stats[k] = n` sites
    assert reg.value("a_first") == 7
    view.expose("derived", lambda: 42)         # read-only exposure
    assert view["derived"] == 42
    with pytest.raises(KeyError):
        view["derived"] = 0
    with pytest.raises(TypeError):
        del view["a_first"]


def test_prometheus_text():
    reg = MetricsRegistry()
    reg.counter("items").inc(3)
    reg.counter("feed", shard=1).inc(2)
    reg.histogram("lat").observe(0.5)
    text = reg.prometheus_text()
    assert "# TYPE mining_items counter" in text
    assert "mining_items 3" in text
    assert 'mining_feed{shard="1"} 2' in text
    assert "mining_lat_count 1" in text and "mining_lat_sum 0.5" in text


# ---------------------------------------------------- golden bit-identity

def test_runner_stats_golden_bit_identity():
    """dict(runner.stats) must equal the dict the engine produced before
    the registry existed — values recorded from the pre-obs revision."""
    r = WaveRunner(_er_graph())
    assert r.clique(4) == 14
    assert r.count_edges() == 401
    assert dict(r.stats) == {
        "exec_hits": 0, "exec_misses": 4, "host_syncs": 3,
        "device_compactions": 1, "items": 401,
        "level_kernel_dispatches": 3, "count_rides": 0}
    # write-through: resetting a counter the legacy way hits the registry
    r.stats["exec_misses"] = 0
    assert r.stats["exec_misses"] == 0
    assert r.metrics.value("exec_misses") == 0


def test_session_stats_golden_bit_identity():
    m = Miner(_pl_graph())
    assert m.count("triangle") == 440
    assert list(m.count_many(list(FOUR_MOTIF_SHAPES))) == \
        [78, 1628, 2611, 15782, 68694, 35818]
    st = m.stats
    assert {k: st[k] for k in ("queries", "plan_hits", "plan_misses",
                               "schedule_hits", "schedule_misses")} == \
        {"queries": 2, "plan_hits": 0, "plan_misses": 1,
         "schedule_hits": 0, "schedule_misses": 1}
    assert st["retraces"] == 15
    assert st["exec_cache"] == {"hits": 3, "misses": 15, "entries": 15}
    assert st["runner"] == {
        "exec_hits": 3, "exec_misses": 15, "host_syncs": 13,
        "device_compactions": 3, "items": 19937,
        "level_kernel_dispatches": 10, "count_rides": 0}


@needs8
def test_sharded_stats_golden_bit_identity():
    m = Miner(_pl_graph(), mesh=8)
    assert m.count("triangle") == 440
    assert m.count("4-clique") == 78
    rs = dict(m.runner.stats)
    assert rs["psum_reductions"] == 2
    assert rs["shard_feed_items"] == [160, 160, 158, 158, 158, 158, 158, 158]
    # labeled series carries the same accounting per shard
    fam = m.telemetry.metrics.series("shard_feed_items")
    assert [fam[(("shard", s),)].value for s in range(8)] == \
        rs["shard_feed_items"]


# ------------------------------------------------------------- span trees

def test_span_tree_single_query():
    tel = Telemetry(enabled=True)
    m = Miner(_pl_graph(), telemetry=tel)
    m.count("triangle")
    roots = tel.tracer.finished
    assert [r.name for r in roots] == ["query"]
    q = roots[0]
    assert q.attrs == {"kind": "count", "seq": 0, "query": "triangle"}
    assert [c.name for c in q.children] == ["compile", "execute"]
    ex = q.children[1]
    feeds = ex.find("feed")
    assert feeds and all(f.cat == "level" for f in feeds)
    dispatches = q.find("dispatch")
    assert dispatches
    for d in dispatches:
        assert {"kind", "level", "dispatches", "exec_cached"} <= \
            set(d.attrs)
    # spans nest by wall time: every child interval sits inside its parent
    for sp in q.walk():
        for c in sp.children:
            assert c.t0 >= sp.t0 and c.t1 <= sp.t1
    # per-level exclusive times sum back to the query wall time (no child
    # can be double-counted because self_seconds subtracts direct children)
    total = sum(tel.tracer.level_seconds().values())
    assert total == pytest.approx(q.seconds, rel=1e-6)


def test_span_tree_forest_batch():
    tel = Telemetry(enabled=True)
    m = Miner(_pl_graph(), telemetry=tel)
    m.count_many(list(FOUR_MOTIF_SHAPES))
    q = tel.tracer.last("query")
    assert q.attrs["kind"] == "count_many"
    assert q.attrs["queries"] == len(FOUR_MOTIF_SHAPES)
    names = [c.name for c in q.children]
    assert names[0] == "schedule" and names[-1] == "execute"
    ex = q.children[-1]
    assert ex.attrs.get("forest") is True
    levels = [s for s in ex.walk() if s.cat == "level" and s.name != "feed"]
    assert levels, "forest execute must contain per-level spans"
    assert all(s.name.startswith("L") for s in levels)


@needs8
def test_span_tree_sharded():
    tel = Telemetry(enabled=True)
    m = Miner(_pl_graph(), mesh=8, telemetry=tel)
    assert m.count("triangle") == 440
    q = tel.tracer.last("query")
    dispatches = q.find("dispatch")
    assert dispatches
    # tracing must not change the sharded accounting either
    plain = Miner(_pl_graph(), mesh=8)
    plain.count("triangle")
    assert dict(m.runner.stats) == dict(plain.runner.stats)


# ----------------------------------------------------- disabled telemetry

def test_disabled_telemetry_is_free():
    """Tracing off (the default) records nothing; tracing on changes no
    counter — in particular zero extra kernel dispatches."""
    plain = Miner(_pl_graph())
    plain.count("triangle")
    plain.count_many(list(FOUR_MOTIF_SHAPES))
    assert plain.telemetry.tracer.finished == []

    tel = Telemetry(enabled=True)
    traced = Miner(_pl_graph(), telemetry=tel)
    traced.count("triangle")
    traced.count_many(list(FOUR_MOTIF_SHAPES))
    assert dict(traced.runner.stats) == dict(plain.runner.stats)
    assert traced.stats == plain.stats


# -------------------------------------------------------------- exporters

def test_chrome_trace_schema(tmp_path):
    tel = Telemetry(enabled=True)
    m = Miner(_pl_graph(), telemetry=tel)
    m.count("triangle")
    path = tel.write_trace(tmp_path / "trace.json")
    doc = json.loads(path.read_text())          # JSON round-trips
    events = doc["traceEvents"]
    assert doc["displayTimeUnit"] == "ms"
    assert doc["otherData"]["spans"] == len(events)
    assert doc["otherData"]["metrics"]["level_kernel_dispatches"] > 0
    assert all(e["ph"] == "X" for e in events)
    assert all(e["dur"] >= 0 for e in events)
    # args must be JSON-scalar (Chrome trace viewers choke on objects)
    for e in events:
        for v in e["args"].values():
            assert isinstance(v, (int, float, str, bool, type(None)))
    # the root event spans every other event on its track
    by_tid = {}
    for e in events:
        by_tid.setdefault(e["tid"], []).append(e)
    for track in by_tid.values():
        root = track[0]
        for e in track[1:]:
            assert e["ts"] >= root["ts"] - 1e-3
            assert e["ts"] + e["dur"] <= root["ts"] + root["dur"] + 1e-3


def test_telemetry_snapshot_and_nullspan():
    tel = Telemetry(enabled=True)
    with tel.tracer.span("outer") as sp:
        with tel.tracer.span("inner"):
            pass
    assert sp.t1 is not None
    snap = tel.snapshot()
    assert snap["spans"]["outer"]["count"] == 1
    assert snap["roots"][0]["spans"] == 2
    # disabled tracer: span() yields None and records nothing
    off = Tracer(enabled=False)
    with off.span("x") as sp:
        assert sp is None
    assert off.finished == []
    assert chrome_trace(off)["traceEvents"] == []


# ----------------------------------------- profiler annotations (ix.* spans)

def _profile(tmp_path, fn):
    """Run ``fn`` under ``jax.profiler`` with no Python tracer; return its
    result and the host events named ``ix.*`` as (name, t0, t1, stats)."""
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    pb = sorted(tmp_path.glob("**/*.xplane.pb"))[-1]
    events = [(ev.name, ev.start_ns, ev.end_ns, dict(ev.stats))
              for plane in ProfileData.from_file(str(pb)).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for ev in line.events
              if ev.name.startswith("ix.")]
    return out, events


def _inside(ev, outer) -> bool:
    return any(o[1] <= ev[1] and ev[2] <= o[2] for o in outer)


def test_spans_reach_the_profiler_nested(tmp_path):
    """With the span tree off, every span is still an ``ix.*`` profiler
    annotation: feed bucketing once per feed pass, one ``sync`` with
    ``site=meta`` per device compaction, nested by time as in the tree."""
    m = Miner(_pl_graph())
    m.count("triangle")                       # warm: compile outside
    m.count_many(list(FOUR_MOTIF_SHAPES))
    passes = (1 + m.schedule(list(FOUR_MOTIF_SHAPES))
              .sharing_stats()["feed_passes"]["fused"])
    before = m.runner.stats["device_compactions"]
    counts, events = _profile(tmp_path, lambda: (
        m.count("triangle"), m.count_many(list(FOUR_MOTIF_SHAPES))))
    assert counts[0] == 440 and m.telemetry.tracer.finished == []
    by = {}
    for ev in events:
        by.setdefault(ev[0], []).append(ev)
    queries, execs = by["ix.query"], by["ix.execute"]
    assert sorted(q[3]["seq"] for q in queries) == [2, 3]
    assert len(execs) == 2 and all(_inside(e, queries) for e in execs)
    assert len(by["ix.feed_bucket"]) == passes
    assert all(_inside(f, execs) for f in by["ix.feed_bucket"])
    assert by["ix.dispatch"] and all(_inside(d, execs)
                                     for d in by["ix.dispatch"])
    meta = [s for s in by["ix.sync"] if s[3].get("site") == "meta"]
    assert len(meta) == m.runner.stats["device_compactions"] - before > 0
    expands = [ev for name, evs in by.items()
               if name.startswith("ix.L") and name.endswith(":expand")
               for ev in evs]
    assert all(_inside(s, expands) for s in meta)
    assert all(_inside(f, execs) for f in by["ix.finalize"])


def test_traced_dispatch_never_blocks(monkeypatch):
    """The span tree on, no mining path waits on the device: dispatch
    spans time the enqueue, and the reads sit in ``sync`` spans."""
    def refuse(*a, **k):
        raise AssertionError("block_until_ready called while tracing")
    monkeypatch.setattr(jax, "block_until_ready", refuse)
    tel = Telemetry(enabled=True)
    m = Miner(_pl_graph(), telemetry=tel)
    assert m.count("triangle") == 440
    assert m.count("4-clique") == 78
    assert m.embeddings("triangle").shape == (440, 3)
    sites = {s.attrs["site"] for s in tel.tracer.spans("sync")}
    assert sites == {"meta", "emit"}
    for d in tel.tracer.spans("dispatch"):
        assert isinstance(d.attrs["items"], int)


def _feed_fill_brute(g, chunk: int, gathers_v1: bool = True,
                     ordered: bool = True):
    """Level-1 gather slots and keys from the degrees, edge by edge: half
    edges bucketed by pow2 degree of v0 and, when ``ordered`` (the feed),
    stably sorted by pow2 degree of v1 inside each bucket, then chunked;
    v1 rows at the class of the chunk's largest live v1 degree. With
    ``ordered`` False it counts the CSR-order feed that gathered every v1
    row of a chunk at its widest row, dead slots padded with vertex 0."""
    import numpy as np
    deg = [int(d) for d in np.asarray(g.degrees)]
    indptr = np.asarray(g.indptr)
    indices = np.asarray(g.indices)

    def pow2(d):
        c = 128
        while c < max(d, 1):
            c *= 2
        return c
    buckets: dict[int, list] = {}
    for u in range(g.num_vertices):
        for k in range(int(indptr[u]), int(indptr[u + 1])):
            v = int(indices[k])
            if v < u:
                buckets.setdefault(pow2(deg[u]), []).append((u, v))
    slots = keys = 0
    for cap0, edges in sorted(buckets.items()):
        if ordered:
            edges = sorted(edges, key=lambda e: pow2(deg[e[1]]))
        nb = min(chunk, pow2(len(edges)))
        for lo in range(0, len(edges), nb):
            part = edges[lo: lo + nb]
            v1s = [v for _, v in part]
            if not ordered:
                v1s += [0] * (nb - len(part))
            cap1 = pow2(max(deg[v] for v in v1s)) if gathers_v1 else 0
            slots += nb * (cap0 + cap1)
            keys += sum(min(deg[u], cap0) + (min(deg[v], cap1) if cap1
                                             else 0) for u, v in part)
    return slots, keys


@pytest.mark.parametrize("query", ["triangle", "4-clique"])
def test_feed_fill_counters_brute_force(query):
    g = _pl_graph()
    m = Miner(g, chunk=128)
    m.count(query)
    slots, keys = _feed_fill_brute(g, 128)
    assert m.metrics.value("feed_row_slots") == slots
    assert m.metrics.value("feed_row_keys") == keys
    m.count(query)                              # one feed pass a query
    assert m.metrics.value("feed_row_slots") == 2 * slots
    # registry-only: the legacy stats view does not carry them
    assert "feed_row_slots" not in m.runner.stats


def test_sharded_runner_spans_and_fill_on_one_device():
    """The mesh runner's feed and reads carry the same spans and fill
    counters; on a one-device mesh they equal the single runner's."""
    from repro.distributed.sharding import make_mining_mesh
    from repro.mining.shard import ShardedWaveRunner
    g = _pl_graph()
    tel = Telemetry(enabled=True)
    r = ShardedWaveRunner(g, make_mining_mesh(1), chunk=128, telemetry=tel)
    assert r.clique(4) == 78
    plain = Miner(g, chunk=128)
    plain.count("4-clique")
    for k in ("feed_row_slots", "feed_row_keys"):
        assert r.metrics.value(k) == plain.metrics.value(k) > 0
    assert len(tel.tracer.spans("feed_bucket")) == 1
    meta = [s for s in tel.tracer.spans("sync") if s.attrs["site"] == "meta"]
    assert len(meta) == r.stats["device_compactions"] > 0
