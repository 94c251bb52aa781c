"""Idle time by program span, and the named gathers' device time, on
synthetic event lists."""
import pytest

import ixbench_testkit  # noqa: F401  (sets sys.path)

from ixbench import spans as S  # noqa: E402
from ixbench import trace as T  # noqa: E402

MS = 1e6


def op(name, start_ms, end_ms, kind="glue"):
    return T.Op(name, start_ms * MS, end_ms * MS, kind)


def span(name, start_ms, end_ms):
    return T.Span(name, start_ms * MS, end_ms * MS)


def approx(d):
    return {k: pytest.approx(v) for k, v in d.items()}


def test_innermost_span_wins():
    chips = {"/device:TPU:0": [op("g", 10, 90)]}
    spans = [span("query 0", 0, 100), span("ix.query", 0, 100),
             span("ix.execute", 1, 99), span("ix.feed_bucket", 2, 9),
             span("ix.L2:expand", 92, 98),
             span("ix.sync[site=meta]", 93, 97)]
    got = S.idle_by_span(chips, spans)
    assert got == approx({"ix.feed_bucket": 0.007, "ix.query": 0.002,
                      "ix.execute": 0.005, "ix.sync[site=meta]": 0.004,
                      "ix.L2:expand": 0.002})
    assert sum(got.values()) == pytest.approx(0.020)
    assert list(got)[0] == "ix.feed_bucket"         # largest first


def test_one_gap_splits_across_bucket_sync_and_untraced():
    # one idle stretch, 20..60, under three things the host did in turn
    chips = {"/device:TPU:0": [op("g", 0, 20), op("k", 60, 100, "kernel")]}
    spans = [span("query 0", 0, 100), span("ix.feed_bucket", 15, 30),
             span("ix.sync[site=meta]", 40, 52)]
    assert S.idle_by_span(chips, spans) == approx(
        {"ix.feed_bucket": 0.010, S.UNTRACED: 0.018,
         "ix.sync[site=meta]": 0.012})


def test_chips_are_averaged():
    chips = {"/device:TPU:0": [op("g", 0, 100)],
             "/device:TPU:1": [op("g", 0, 50)]}
    spans = [span("query 0", 0, 100), span("ix.finalize", 60, 100)]
    assert S.idle_by_span(chips, spans) == approx(
        {"ix.finalize": 0.020, S.UNTRACED: 0.005})


def test_scope_is_self_time_and_excludes_kernels():
    chips = {"/device:TPU:0": [
        op("while.1", 0, 40, "scoped"),
        op("fusion.2", 5, 15, "scoped"),          # inside the while
        op("kernel.3", 15, 25, "kernel"),
        op("fusion.4", 50, 58),                   # glue outside the scope
        op("fusion.5", 60, 70, "scoped"),
        op("fusion.6", 95, 120, "scoped"),        # clipped at the window
    ]}
    spans = [span("query 0", 0, 100)]
    assert S.scope_s(chips, spans) == pytest.approx(
        (20 + 10 + 10 + 5) / 1e3)
    # trace.reduce still counts scoped ops as glue
    r = T.reduce(chips, spans)
    assert r.glue_s == pytest.approx((20 + 10 + 8 + 10 + 5) / 1e3)
    two = {"/device:TPU:0": chips["/device:TPU:0"],
           "/device:TPU:1": [op("fusion.2", 0, 5, "scoped")]}
    assert S.scope_s(two, spans) == pytest.approx((45 + 5) / 2 / 1e3)


def test_nothing_to_read_is_none():
    chips = {"/device:TPU:0": [op("g", 0, 10)]}
    # a program that opens no ix.* span, as before these spans existed
    assert S.idle_by_span(chips, [span("query 0", 0, 20)]) is None
    assert S.idle_by_span(chips, [span("ix.execute", 0, 20)]) is None
    assert S.idle_by_span({}, [span("query 0", 0, 20),
                               span("ix.execute", 0, 20)]) is None
    # no op in the padded_rows scope
    assert S.scope_s(chips, [span("query 0", 0, 20)]) is None
    assert S.scope_s({"/device:TPU:0": [op("g", 30, 40, "scoped")]},
                     [span("query 0", 0, 20)]) is None


def test_labels_carry_the_sync_site():
    assert S._label("ix.sync", {"site": "meta"}) == "ix.sync[site=meta]"
    assert S._label("ix.feed_bucket", {"symmetric": True}) == \
        "ix.feed_bucket"


def _varint(x):
    out = bytearray()
    while True:
        out.append((x & 0x7F) | (0x80 if x > 0x7F else 0))
        x >>= 7
        if not x:
            return bytes(out)


def _msg(*fields):
    """A protobuf message from (field, int | bytes | str) pairs."""
    out = b""
    for f, v in fields:
        if isinstance(v, int):
            out += _varint(f << 3) + _varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += _varint(f << 3 | 2) + _varint(len(v)) + v
    return out


def test_op_scopes_read_the_event_metadata():
    # an XSpace as a v5e writes it: tf_op is a stat of the op's metadata,
    # as a string or as a reference to an interned stat-metadata name
    stat_meta = [(5, _msg((1, 1), (2, _msg((1, 1), (2, "tf_op"))))),
                 (5, _msg((1, 2), (2, _msg((1, 2), (2, "source"))))),
                 (5, _msg((1, 3), (2, _msg((1, 3),
                                           (2, "jit(fn)/padded_rows/gather:")))))]

    def event_meta(i, name, *stats):
        return (4, _msg((1, i), (2, _msg((1, i), (2, name), *[
            (5, _msg(*s)) for s in stats]))))
    tpu = _msg((1, 7), (2, "/device:TPU:0"), (3, _msg((2, "XLA Ops"))),
               *stat_meta,
               event_meta(10, "%fusion.7 = s32[8] fusion(...)",
                          [(1, 1), (5, "jit(fn)/padded_rows/gather:")],
                          [(1, 2), (5, "csr.py:165")]),
               event_meta(11, "%fusion.8 = s32[8] fusion(...)",
                          [(1, 1), (7, 3)]),
               event_meta(12, "%scatter.2 = s32[8] scatter(...)",
                          [(1, 1), (5, "jit(fn)/batch_compact_scan/scatter:")]),
               event_meta(13, "%copy-start", [(1, 2), (5, "x")]))
    host = _msg((1, 1), (2, "/host:CPU"), *stat_meta,
                event_meta(10, "ix.sync", [(1, 1), (5, "padded_rows")]))
    got = S.op_scopes(_msg((1, tpu), (1, host)))
    assert got == {"/device:TPU:0": {
        "%fusion.7 = s32[8] fusion(...)": {"jit(fn)/padded_rows/gather:"},
        "%fusion.8 = s32[8] fusion(...)": {"jit(fn)/padded_rows/gather:"},
        "%scatter.2 = s32[8] scatter(...)":
            {"jit(fn)/batch_compact_scan/scatter:"},
        "%copy-start": set()}}


def test_read_xplane_takes_the_query_line_spans(tmp_path):
    """On a CPU profile of a query: the ``query <n>`` annotation and the
    program's ``ix.*`` spans of that thread, sync spans by site."""
    import jax
    from repro.graph import build_csr
    from repro.graph.generators import powerlaw_cluster
    from repro.mining import Miner
    m = Miner(build_csr(powerlaw_cluster(110, 5, seed=7), 110))
    assert m.count("4-clique") == 78
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("query 0"):
            m.count("4-clique")
    finally:
        jax.profiler.stop_trace()
    chips, spans = S.read_xplane(str(tmp_path))
    assert chips == {}                        # no TPU plane on the CPU
    names = {s.name for s in spans}
    assert {"query 0", "ix.query", "ix.execute", "ix.feed_bucket",
            "ix.dispatch", "ix.sync[site=meta]", "ix.finalize"} <= names
    q = next(s for s in spans if s.name == "query 0")
    assert all(q.start_ns <= s.start_ns and s.end_ns <= q.end_ns
               for s in spans)
