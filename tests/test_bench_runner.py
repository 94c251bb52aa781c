"""benchmarks/run.py: a failing suite is recorded, the other suites still
run, and the exit code reports the failure."""
import json
import os

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")


@pytest.fixture
def runner(monkeypatch):
    monkeypatch.syspath_prepend(ROOT)
    from benchmarks import run
    monkeypatch.setattr(run.bench_streams, "run", lambda quick: {"ok": 1})
    return run


def test_failed_suite_sets_exit_code(runner, monkeypatch, tmp_path):
    def boom(quick):
        raise RuntimeError("boom")
    monkeypatch.setattr(runner.bench_kernels, "run", boom)
    out = tmp_path / "results.json"
    assert runner.main(["--only", "kernels,streams", "--out", str(out)]) == 1
    res = json.loads(out.read_text())
    assert "boom" in res["kernels"]["error"]
    assert res["streams"] == {"ok": 1}


def test_clean_run_exits_zero(runner, tmp_path):
    out = tmp_path / "results.json"
    assert runner.main(["--only", "streams", "--out", str(out)]) == 0
    assert json.loads(out.read_text()) == {"streams": {"ok": 1}}
