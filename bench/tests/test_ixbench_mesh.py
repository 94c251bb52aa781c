"""The 4-chip mico deployment: its configuration, its cell, and the
arithmetic of ``bench/mesh_readings.py`` on synthetic 4-chip traces."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from ixbench_testkit import ROOT, TINY, bench_copy, cell

from ixbench import graphs, harness  # noqa: E402
from ixbench import trace as T  # noqa: E402

sys.path.insert(0, str(ROOT / "bench"))
import mesh_readings as M  # noqa: E402

MS = 1e6
CHIPS = [f"/device:TPU:{i}" for i in range(4)]


def _config(name):
    return json.loads((ROOT / "bench/configs" / f"{name}.json").read_text())


def test_config_is_mico_on_four_chips():
    one, four = _config("mico"), _config("mico-4chip")
    differ = {k for k in one.keys() | four.keys() if one.get(k) != four.get(k)}
    assert differ == {"name", "deployment", "chips", "assumed"}
    assert four["chips"] == 4 and four["reduced"] == []
    extra = dict(four["assumed"])
    assert "replicated" in extra.pop("mesh")
    assert extra == one["assumed"]


def test_config_generates_the_mico_graph():
    np.testing.assert_array_equal(graphs.generate(_config("mico-4chip")),
                                  graphs.generate(_config("mico")))


def test_cell_resolves_on_four_chips():
    c = harness.find_cell(ROOT, "mico-4chip.triangle")
    assert c.chips == 4 and c.config["name"] == "mico-4chip"
    assert c.traffic["query"] == "triangle"
    assert c.traffic["answer_gap_limit"] == 0


def op(name, start_ms, end_ms, kind="glue"):
    return T.Op(name, start_ms * MS, end_ms * MS, kind)


def span(name, start_ms, end_ms):
    return T.Span(name, start_ms * MS, end_ms * MS)


def _four_chips():
    """Chip i busy for 40 + 10 i ms of a 100 ms query, an all-reduce of
    2 ms at its end; on chip 3 the all-reduce sits inside a ``while``."""
    chips = {}
    for i, plane in enumerate(CHIPS):
        end = 40 + 10 * i
        body = [op("fusion.1", 0, end - 2)] if i < 3 else \
            [op("fusion.1", 0, end - 4), op("while.2", end - 4, end)]
        chips[plane] = body + [op("all-reduce.7", end - 2, end)]
    return chips


def test_collective_time_is_the_scoped_ops_self_time_per_chip():
    chips = _four_chips()
    spans = [span("query 0", 0, 100)]
    scoped = {p: {"all-reduce.7"} for p in CHIPS}
    assert M.collective_s(chips, spans, scoped) == pytest.approx(0.002)
    # one chip's reduction not in the scope: the sum still divides by four
    scoped[CHIPS[0]] = set()
    assert M.collective_s(chips, spans, scoped) == pytest.approx(0.0015)
    assert M.collective_s(chips, spans, {}) is None
    assert M.collective_s(chips, [], scoped) is None


def test_busy_by_chip_and_its_spread():
    busy = M.busy_by_chip(_four_chips(), [span("query 0", 0, 100)])
    assert busy == {p: pytest.approx((40 + 10 * i) / 1e3)
                    for i, p in enumerate(CHIPS)}
    # (70 - 40) / mean 55
    assert M.spread(busy.values()) == pytest.approx(30 / 55)
    assert M.spread([0.5]) is None
    assert M.spread([0.0, 0.0]) is None
    assert M.busy_by_chip({}, [span("query 0", 0, 100)]) is None


def test_feed_fill():
    assert M.fill_pct(1102614, 6378) == pytest.approx(
        100 * 1102614 / 1108992)
    assert M.fill_pct(10, 0) == 100.0
    assert M.fill_pct(10, None) is None       # a program without the pad
    assert M.fill_pct(None, 3) is None
    assert M.fill_pct(0, 0) is None


def _varint(x):
    out = bytearray()
    while True:
        out.append((x & 0x7F) | (0x80 if x > 0x7F else 0))
        x >>= 7
        if not x:
            return bytes(out)


def _msg(*fields):
    out = b""
    for f, v in fields:
        if isinstance(v, int):
            out += _varint(f << 3) + _varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += _varint(f << 3 | 2) + _varint(len(v)) + v
    return out


def test_collective_ops_read_the_tf_op_scope():
    """As a v5e writes a plane: the all-reduce's ``tf_op`` names the
    scope; a gather's names ``padded_rows``."""
    stat = (5, _msg((1, 1), (2, _msg((1, 1), (2, "tf_op")))))

    def event(i, name, tf_op):
        return (4, _msg((1, i), (2, _msg((1, i), (2, name),
                                         (5, _msg((1, 1), (5, tf_op)))))))
    planes = [_msg((1, 7), (2, p), stat,
                   event(10, "%all-reduce.7 = s32[4] all-reduce(...)",
                         "jit(wrapped)/shard_map/mesh_psum/psum"),
                   event(11, "%fusion.3 = s32[8] fusion(...)",
                         "jit(fn)/padded_rows/gather:"))
              for p in CHIPS[:2]]
    got = M.collective_ops(b"".join(_msg((1, p)) for p in planes))
    assert got == {p: {"%all-reduce.7 = s32[4] all-reduce(...)"}
                   for p in CHIPS[:2]}


MESH_SCRIPT = r"""
import json, pathlib, sys
sys.path[:0] = [sys.argv[2], sys.argv[3]]
import mesh_readings
from ixbench import harness
mesh_readings.ROOT = pathlib.Path(sys.argv[1])
harness.check_devices = lambda root, devices, chips: {}
print(json.dumps(mesh_readings.readings("tiny4.triangle", 7, 0.2)))
"""


def test_readings_on_a_four_device_cpu_mesh(tmp_path):
    """End to end on four CPU devices: the counters the mesh adds are read
    (no TPU plane, so the device readings are null, never 0)."""
    root = bench_copy(tmp_path, {"tiny4": dict(TINY, chips=4)},
                      [cell("tiny4", "triangle", chips=4)])
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(ROOT / "src"))
    p = subprocess.run([sys.executable, "-c", MESH_SCRIPT, str(root),
                        str(ROOT / "bench"), str(ROOT / "bench/tests")],
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["queries"] >= 1 and len(out["answers"]) == 1
    assert out["chips"] == 0 and out["collective_ms"] is None
    assert out["busy_ms"] is None and out["feed_step_idle_ms"] is None
    assert 0 < out["mesh_feed_fill_pct"] <= 100
    assert out["shard_feed_items"] > 0 and out["shard_pad_items"] >= 0
    assert out["dispatches"] > 0 and out["window_compiles"] == 0
