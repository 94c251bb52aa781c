"""The harness: files found by name, a run end to end on the CPU, the
metric readers, and the exits without a chip."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from ixbench_testkit import ROOT, TINY, bench_copy, cell, run_tiny, spec

from ixbench import harness  # noqa: E402
from ixbench import trace as T  # noqa: E402

ENV = dict(os.environ, JAX_PLATFORMS="cpu")


@pytest.mark.parametrize("name", [w["name"] for w in spec()["workloads"]])
def test_every_cell_finds_its_files(name):
    c = harness.find_cell(ROOT, name)
    query = c.traffic["query"]
    assert (ROOT / "bench/reference" / f"{query}.py").is_file()
    assert (ROOT / "bench/work" / f"{query}.py").is_file()
    for m in c.per_layer:
        assert callable(harness.load_module(
            ROOT / "bench/metrics" / f"{m['name']}.py").read)
    assert {m["name"] for m in c.end_to_end} == {"query_s", "setup_s"}


def test_unknown_names_are_rejected(tmp_path):
    with pytest.raises(harness.UnknownName):
        harness.find_cell(ROOT, "no-such.cell")
    root = bench_copy(tmp_path, {"tiny": TINY},
                      [cell("tiny", "no-such-traffic"),
                       dict(cell("nowhere", "triangle"), name="x.triangle")])
    with pytest.raises(harness.UnknownName):
        harness.find_cell(root, "tiny.no-such-traffic")
    with pytest.raises(harness.UnknownName):
        harness.find_cell(root, "x.triangle")
    with pytest.raises(harness.UnknownName):
        harness.load_module(root / "bench/metrics/no_such_metric.py")


def test_a_new_config_file_is_picked_up(tmp_path):
    root = bench_copy(tmp_path, {"tiny": TINY},
                      [cell("tiny", t) for t in ("triangle", "4clique")])
    for t in ("triangle", "4clique"):
        r = run_tiny(root, f"tiny.{t}")
        assert r["correct"] and r["failed"] == 0 and r["attempted"] >= 1
        assert set(r["metrics"]) == {"query_s", "setup_s"}
        assert r["checks"] == {"answer_gap": {"value": 0.0, "limit": 0.0}}
        assert list(r)[-1] == "checks"
    r = run_tiny(root, "tiny.triangle", trace=True)
    assert r["correct"]
    # the CPU trace has no TPU plane: only the counters can be read
    assert set(r["metrics"]) == {"window_compiles", "dispatches_per_query",
                                 "host_syncs_per_query"}
    assert r["metrics"]["window_compiles"]["value"] == 0


def test_metric_readers():
    red = T.Reduced(chips=2, window_s=2.0, busy_s=1.5, kernel_s=1.0,
                    glue_s=0.4, device_ops=[], idle_gaps=[])
    r = harness.Readings(queries=4, counters={"level_kernel_dispatches": 8,
                                              "host_syncs": 6},
                         window_compiles=0, trace=red,
                         peaks={"hbm_bytes_per_s": 1e9}, chips=2,
                         _work=lambda: 10**6)

    def read(name, readings=r):
        return harness.load_module(
            ROOT / "bench/metrics" / f"{name}.py").read(readings)

    assert read("dispatches_per_query") == 2
    assert read("host_syncs_per_query") == 1.5
    assert read("pallas_ms_per_query") == pytest.approx(250)
    assert read("glue_ms_per_query") == pytest.approx(100)
    assert read("device_idle_pct") == pytest.approx(25)
    # 1 MB per query over 2 chips at 1 GB/s: 0.5 ms against 250 ms
    assert read("intersect_roofline") == pytest.approx(0.2)
    blind = harness.Readings(queries=4, counters={}, window_compiles=0,
                             trace=None, peaks={}, chips=1)
    for name in ("pallas_ms_per_query", "intersect_roofline",
                 "glue_ms_per_query", "device_idle_pct"):
        assert read(name, blind) is None


def _run(cwd, *extra):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "mico.triangle", "--seed", "3", "--seconds", "1",
         *extra], cwd=cwd, env=ENV, capture_output=True, text=True,
        timeout=300)


def test_no_tpu_exits_nonzero_without_a_result():
    p = _run(ROOT)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "not a TPU" in p.stderr


class _Dev:
    def __init__(self, platform, kind="TPU v5 lite"):
        self.platform, self.device_kind = platform, kind


@pytest.mark.parametrize("devices,chips,error", [
    ([_Dev("cpu")], 1, harness.NoAccelerator),
    ([_Dev("tpu")], 4, harness.NoAccelerator),
    ([_Dev("tpu", "TPU v0 unknown")], 1, KeyError),
    ([_Dev("tpu")] * 4, 4, None),
], ids=["no-tpu", "too-few-chips", "no-peaks", "four-chips"])
def test_check_devices(devices, chips, error):
    if error is None:
        assert harness.check_devices(ROOT, devices, chips)[
            "hbm_bytes_per_s"] == 819e9
    else:
        with pytest.raises(error):
            harness.check_devices(ROOT, devices, chips)


def test_bare_benchmark_directory_exits_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".jax_cache",
                                                  "__pycache__"))
    p = _run(tmp_path, "--trace", "1")
    assert p.returncode != 0 and p.stdout.strip() == ""


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_spec_is_well_formed():
    s = spec()
    assert s["paths"] == ["bench"] and s["command"] == ["python3",
                                                        "bench/run.py"]
    assert isinstance(s["run_seconds"], int) and 1 <= s["run_seconds"] <= 51
    for part, keys in KEYS.items():
        names = [e["name"] for e in s[part]]
        assert len(names) == len(set(names))
        for e in s[part]:
            assert set(e) - {"workloads"} == keys, e
            assert NAME.match(e["name"]), e["name"]
            for k in ("why", "source", "layer"):
                assert k not in e or _line(e[k]), e[k]
            if "unit" in e:
                assert UNIT.match(e["unit"]) and e["better"] in ("lower",
                                                                  "higher")
    cells = {w["name"] for w in s["workloads"]}
    configs = {c["name"] for c in s["configs"]}
    assert {w["config"] for w in s["workloads"]} == configs
    for c in s["configs"]:
        assert c["file"].startswith("bench/")
        assert json.loads((ROOT / c["file"]).read_text())["name"] == c["name"]
    for m in s["end_to_end"]:
        assert 0 < m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert "setup_s" in {m["name"] for m in s["end_to_end"]}
    e2e = {m["name"] for m in s["end_to_end"]}
    for m in s["per_layer"]:
        assert m["moves"] in e2e and set(m.get("workloads", [])) <= cells
    assert sum(w["chips"] == 4 for w in s["workloads"]) <= max(
        1, len(s["workloads"]) // 2)
    assert len(json.dumps(s)) < 64 * 1024
