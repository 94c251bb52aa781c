"""The trace reduction on synthetic event lists."""
import pytest

import ixbench_testkit  # noqa: F401  (sets sys.path)

from ixbench import trace as T  # noqa: E402

MS = 1e6


def op(name, start_ms, end_ms, kind="glue"):
    return T.Op(name, start_ms * MS, end_ms * MS, kind)


def span(name, start_ms, end_ms):
    return T.Span(name, start_ms * MS, end_ms * MS)


def test_op_kind():
    assert T.op_kind("fusion.3", {"long_name": "custom-call ... "
                                  "custom_call_target=\"tpu_custom_call\""}) \
        == "kernel"
    assert T.op_kind("custom-call.12", {}) == "kernel"
    # the pads and reshapes around a kernel share its name scope
    assert T.op_kind("fusion.9", {"tf_op": "jit(count)/pallas_call"}) \
        == "glue"
    assert T.op_kind("fusion.4", {"long_name": "fusion(%custom-call.2)"}) \
        == "glue"
    assert T.op_kind("gather.7", {"long_name": "gather(...)"}) == "glue"
    # names as a v5e trace gives them: the op's HLO text
    assert T.op_kind(
        "%intersect_count_pallas.1 = s32[2048,1]{1,0:T(8,128)S(1)} "
        "custom-call(s32[512]{0} %get-tuple-element.113, "
        "s32[2048,256]{1,0} %custom-call.32), "
        "custom_call_target=\"tpu_custom_call\", "
        "frontend_attributes={kernel_metadata={}}", {}) == "kernel"
    assert T.op_kind(
        "%fusion.4 = s32[524288]{0:T(1024)S(1)} fusion(s32[2205312]{0} "
        "%copy-done, s32[524288]{0} %custom-call.32), kind=kCustom, "
        "calls=%fused_computation.4", {}) == "glue"
    assert T.op_kind(
        "%custom-call.5 = s32[8]{0} custom-call(s32[8]{0} %p), "
        "custom_call_target=\"AllocateBuffer\"", {}) == "glue"


def test_one_chip_busy_idle_kinds_and_gaps():
    chips = {"/device:TPU:0": [
        op("k", 5, 25, "kernel"),
        op("g", 20, 30),              # overlaps the kernel: union is 5..30
        op("g", 60, 75),
        op("before", -10, 2),         # clipped to the window start
    ]}
    spans = [span("query 0", 0, 50), span("query 1", 50, 100),
             span("edge_chunks", 0, 100)]
    r = T.reduce(chips, spans)
    assert r.window_s == pytest.approx(0.100)
    assert r.busy_s == pytest.approx((2 + 25 + 15) / 1e3)
    # self time: the part of the kernel that g covers is g's
    assert r.kernel_s == pytest.approx(0.015)
    assert r.glue_s == pytest.approx((10 + 15 + 2) / 1e3)
    assert r.kernel_s + r.glue_s == pytest.approx(r.busy_s)
    assert r.device_ops[0] == ["g", pytest.approx(0.025)]
    assert [g[0] for g in r.idle_gaps] == ["query 0 +30.0ms",
                                           "query 1 +25.0ms",
                                           "query 0 +2.0ms"]
    assert [g[1] for g in r.idle_gaps] == pytest.approx([0.030, 0.025,
                                                         0.003])


def test_nested_ops_count_self_time():
    # a while loop spans the ops of its body on the same line
    chips = {"/device:TPU:0": [
        op("while.1", 0, 40),
        op("fusion.2", 2, 12),
        op("kernel.3", 12, 30, "kernel"),
        op("while.1", 50, 60),
        op("fusion.2", 50, 55),
    ]}
    r = T.reduce(chips, [span("query 0", 0, 60)])
    assert r.busy_s == pytest.approx(0.050)
    assert r.kernel_s == pytest.approx(0.018)
    assert r.glue_s == pytest.approx(0.032)
    assert dict(map(tuple, r.device_ops)) == pytest.approx(
        {"kernel.3": 0.018, "fusion.2": 0.015, "while.1": 0.017})


def test_chips_are_averaged():
    chips = {"/device:TPU:0": [op("k", 0, 10, "kernel"), op("a", 10, 12)],
             "/device:TPU:1": [op("k", 0, 30, "kernel")]}
    r = T.reduce(chips, [span("query 0", 0, 40)])
    assert r.chips == 2
    assert r.busy_s == pytest.approx((12 + 30) / 2 / 1e3)
    assert r.kernel_s == pytest.approx(0.020)
    assert r.glue_s == pytest.approx(0.001)


def test_nothing_to_read():
    assert T.reduce({"/device:TPU:0": [op("k", 0, 1)]}, []) is None
    assert T.reduce({}, [span("query 0", 0, 1)]) is None
    assert T.reduce({"/device:TPU:0": [op("k", 5, 6)]},
                    [span("query 0", 0, 1)]) is None
