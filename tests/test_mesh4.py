"""A 4-chip host's mining mesh, on four virtual CPU devices.

``Miner(g, mesh=4)`` is the deployment of the ``mico-4chip`` benchmark
configuration: the CSR replicated on each chip, the level-1 feed dealt
round-robin over four shards. Here it runs on a Holme–Kim graph with
mico's generator parameters (m = 9, closure 0.27) at 2,000 vertices, in a
subprocess that sees four devices (the test process sees one). Checked:

  * counts equal ``mining/reference.py`` and the one-device ``Miner``;
  * ``shard_pad_items`` and ``shard_feed_items`` fill every lockstep
    super-step exactly;
  * the leaf reduction runs in the ``mesh_psum`` name scope, which the
    count executable's ops carry as metadata;
  * a ``feed_step`` span (one feed step's slicing, dealing and upload) is
    never open while a level is dispatched, here and on one device;
  * split into many small chunks, a triangle count still equals the one-
    device count, and each runner reads it once a query (one replicated
    device accumulator on the mesh).
"""
import json
import os
import pathlib
import subprocess
import sys

import pytest

from repro.graph import build_csr
from repro.graph.generators import powerlaw_cluster
from repro.mining.session import Miner
from repro.obs import Telemetry

ROOT = pathlib.Path(__file__).resolve().parents[1]

MESH_SCRIPT = r"""
import json
import numpy as np
from repro.graph import build_csr
from repro.graph.generators import powerlaw_cluster
from repro.mining import Miner, reference
from repro.mining.shard import ShardedWaveRunner, shard_edge_steps
from repro.obs import Telemetry

g = build_csr(powerlaw_cluster(2000, 9, seed=0, tri_p=0.27), 2000)
out = {"devices": __import__("jax").device_count()}

counts = []
dispatch = ShardedWaveRunner._dispatch


def spy(self, op, fn, args, **kw):
    if op.kind == "count" and not counts:
        counts.append((fn, args))
    return dispatch(self, op, fn, args, **kw)


ShardedWaveRunner._dispatch = spy
tel = Telemetry(enabled=True)
m4, m1 = Miner(g, mesh=4, telemetry=tel), Miner(g)
out["parity"] = {q: [m4.count(q), m1.count(q), ref(g)] for q, ref in (
    ("triangle", reference.triangle_count),
    ("4-clique", lambda g: reference.clique_count(g, 4)))}
ShardedWaveRunner._dispatch = dispatch

fn, args = counts[0]
lowered = fn.lower(*args)
out["lowered_scoped"] = "mesh_psum" in lowered.as_text(debug_info=True)
out["all_reduce_scoped"] = [
    "mesh_psum" in line for line in lowered.compile().as_text().splitlines()
    if "all-reduce(" in line]

spans = tel.tracer.spans()
steps = [s for s in spans if s.name == "feed_step"]
dispatches = [s for s in spans if s.name == "dispatch"]
out["feed_steps"] = len(steps)
out["feed_chunks"] = m4.metrics.value("feed_chunks")
out["feed_step_children"] = sum(len(s.children) for s in steps)
out["overlaps"] = sum(d.t0 < s.t1 and s.t0 < d.t1
                      for s in steps for d in dispatches)

chunk = 128
m = Miner(g, mesh=4, chunk=chunk)
one = Miner(g, chunk=chunk)
out["chunked"] = {"counts": [m.count("triangle"), one.count("triangle")],
                  "syncs": [m.runner.stats["host_syncs"],
                            one.runner.stats["host_syncs"]],
                  "dispatches": m.runner.stats["level_kernel_dispatches"]}
slots, last = 0, {}
for cap, v0, v1, n in shard_edge_steps(g, chunk, 4):
    slots += v0.shape[0]
    last[cap] = int(n.sum()) < v0.shape[0]
out["pad"] = {"pad": m.metrics.value("shard_pad_items"),
              "items": sum(m.stats["runner"]["shard_feed_items"]),
              "slots": slots, "buckets": len(last),
              "partial_last": sum(last.values())}
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def mesh4():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(ROOT / "src"))
    p = subprocess.run([sys.executable, "-c", MESH_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["devices"] == 4
    return out


@pytest.mark.parametrize("query", ["triangle", "4-clique"])
def test_mico_shaped_counts_equal_reference_and_one_device(mesh4, query):
    mesh, one, ref = mesh4["parity"][query]
    assert mesh == one == ref > 0


def test_pad_and_feed_items_fill_every_super_step(mesh4):
    """A triangle query has no expand level: every slot of every dealt
    super-step holds a live item or a pad, and the last step of each
    degree bucket is partial here, so padding is really counted."""
    pad = mesh4["pad"]
    assert pad["pad"] + pad["items"] == pad["slots"]
    assert pad["partial_last"] == pad["buckets"] > 1
    assert pad["pad"] > 0


def test_mesh_psum_scope_in_the_count_executable(mesh4):
    assert mesh4["lowered_scoped"]
    assert mesh4["all_reduce_scoped"] and all(mesh4["all_reduce_scoped"])


def test_many_chunks_count_once_on_the_mesh_and_one_device(mesh4):
    ch = mesh4["chunked"]
    assert ch["counts"][0] == ch["counts"][1] == \
        mesh4["parity"]["triangle"][1]
    assert ch["dispatches"] > 1
    assert ch["syncs"] == [1, 1]


def test_feed_step_never_spans_a_dispatch_on_the_mesh(mesh4):
    assert mesh4["feed_steps"] == mesh4["feed_chunks"] > 0
    assert mesh4["feed_step_children"] == 0
    assert mesh4["overlaps"] == 0


def test_feed_step_never_spans_a_dispatch_on_one_device():
    g = build_csr(powerlaw_cluster(110, 5, seed=7), 110)
    tel = Telemetry(enabled=True)
    m = Miner(g, chunk=128, telemetry=tel)
    assert m.count("triangle") == 440
    assert m.count("4-clique") == 78
    steps = tel.tracer.spans("feed_step")
    dispatches = tel.tracer.spans("dispatch")
    assert len(steps) == m.metrics.value("feed_chunks") > 0
    assert dispatches and not any(s.children for s in steps)
    assert not any(d.t0 < s.t1 and s.t0 < d.t1
                   for s in steps for d in dispatches)


def test_pad_items_on_a_one_device_mesh():
    """On a one-device mesh the pad is each bucket's last chunk's tail, as
    the feed cuts it; the single-device runner has no such counter."""
    from repro.distributed.sharding import make_mining_mesh
    from repro.mining.shard import ShardedWaveRunner, shard_edge_steps
    g = build_csr(powerlaw_cluster(110, 5, seed=7), 110)
    r = ShardedWaveRunner(g, make_mining_mesh(1), chunk=128)
    assert r.count_edges() > 0
    want = sum(v0.shape[0] - int(n.sum())
               for _, v0, _, n in shard_edge_steps(g, 128, 1))
    assert r.metrics.value("shard_pad_items") == want > 0
    plain = Miner(g, chunk=128)
    plain.count("triangle")
    assert plain.metrics.value("shard_pad_items") is None
