"""A run with the timed path broken underneath must come out not correct:
an answer altered where it is produced, half of each feed batch left out,
and on a mesh the exchange between chips left out. The harness's look for
a chip is skipped; everything else of a run is driven as on the chip."""
import json
import os
import subprocess
import sys

import pytest

from ixbench_testkit import ROOT, TINY, bench_copy, cell, run_tiny

TRAFFIC = ("triangle", "4clique")


def _answer_altered(monkeypatch):
    from repro.mining.engine import WaveRunner
    finalize = WaveRunner._finalize
    monkeypatch.setattr(WaveRunner, "_finalize",
                        lambda self, plan, parts: finalize(self, plan,
                                                           parts) + 1)


def _half_batch(monkeypatch):
    from repro.mining.engine import WaveRunner
    feed = WaveRunner._edge_feed

    def halved(self, symmetric=True):
        for cap, dv0, dv1, v1h, n in feed(self, symmetric):
            yield cap, dv0, dv1, v1h, n // 2
    monkeypatch.setattr(WaveRunner, "_edge_feed", halved)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_copy(tmp_path_factory.mktemp("bench"), {"tiny": TINY},
                      [cell("tiny", t) for t in TRAFFIC])


@pytest.mark.parametrize("fault", [_answer_altered, _half_batch],
                         ids=["answer-altered", "half-batch"])
@pytest.mark.parametrize("traffic", TRAFFIC)
def test_broken_path_is_not_correct(root, traffic, fault, monkeypatch):
    assert run_tiny(root, f"tiny.{traffic}")["correct"]
    fault(monkeypatch)
    r = run_tiny(root, f"tiny.{traffic}")
    assert not r["correct"]
    assert r["failed"] == r["attempted"] >= 1
    assert r["checks"]["answer_gap"]["value"] > r["checks"]["answer_gap"][
        "limit"]


MESH_SCRIPT = r"""
import json, pathlib, sys
sys.path.insert(0, sys.argv[2])
from ixbench_testkit import run_tiny
root = pathlib.Path(sys.argv[1])
whole = run_tiny(root, "tiny4.triangle")
import jax, jax.numpy as jnp
from repro.mining.shard import ShardedWaveRunner

def no_exchange(self, op, body):
    def wrapped(g, vals, carry, n):
        part = body(g, vals, carry, n)
        return jnp.stack([part[0] >> 16, part[0] & 0xFFFF,
                          part[1] >> 16, part[1] & 0xFFFF])
    return self._shmap(wrapped, self._level_in_specs(op), self._prp)

ShardedWaveRunner._jit_count = no_exchange
broken = run_tiny(root, "tiny4.triangle")
print(json.dumps({"whole": whole, "broken": broken}))
"""


def test_mesh_without_exchange_is_not_correct(tmp_path):
    root = bench_copy(tmp_path, {"tiny4": dict(TINY, chips=4)},
                      [cell("tiny4", "triangle", chips=4)])
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(ROOT / "src"))
    p = subprocess.run([sys.executable, "-c", MESH_SCRIPT, str(root),
                        str(ROOT / "bench/tests")], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["whole"]["correct"] and out["whole"]["device"]["count"] == 4
    assert not out["broken"]["correct"]
    assert out["broken"]["failed"] == out["broken"]["attempted"]
