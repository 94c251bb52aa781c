#!/usr/bin/env python3
"""Bring-up smoke of the mining path on a TPU, through its user entry points.

    python3 chip_smoke.py             # one chip
    python3 chip_smoke.py --xla-twin  # one chip: mico Pallas vs XLA only
    python3 chip_smoke.py --chips 4   # the 4-way mesh path only

One chip (default):

* the ``mico`` twin at scale 1.0 (96.6K vertices, 1.38M undirected edges,
  max degree 2012 — one of the paper's Table IV graphs, generated from its
  seed): one ``Miner`` with the default ``MinerConfig`` (``backend="auto"``,
  which must resolve to the Pallas kernels) counts T, TC, TT, 4C and the
  fused 3-motif batch, and a weighted triangle ``aggregate``. T runs twice
  (cold: trace + compile + run; steady: cached executables). A
  ``Miner(backend="xla")`` on the same chip repeats T and the aggregate, and
  the host InHouseAutoMine baseline recounts T; the fused batch must agree
  with the single-pattern counts;
* ``email-eu-core`` at scale 1.0: T, TC, TT, TM and 4C must equal the
  InHouseAutoMine functions (the ``launch/mine.py --baseline``
  cross-check), and the 4-motif batch must equal the XLA session's;
* a ``MiningService`` serves the ``launch/serve.py --mine`` request mix on
  ``email-eu-core`` for two rounds; its counts must match the session's.

On mico the 4-motif batch and the XLA twin of TC/TT/4C/TM are left out:
on a v5e chip each mico query takes tens of seconds per backend, and the
whole script must finish inside 20 minutes, compilation included.

``--xla-twin`` runs only that twin: mico TC, TT, 4C and TM on a default
``Miner`` (Pallas) and on a ``Miner(backend="xla")``, which must agree.

``--chips 4`` runs only the mesh path: ``Miner(mesh=4)`` (a
``ShardedWaveRunner`` over ``make_mining_mesh``, Pallas inside
``shard_map``) against a one-chip ``Miner`` on T and 4C over mico. Counts
must be bit-identical, a repeat pass must build no executable, and the
replicated CSR and the feed blocks must sit on all four devices.

Proof that the kernels ran compiled: every lowered module is dumped
(``jax_dump_ir_to``, a temporary directory) and the modules holding a
``tpu_custom_call`` are counted per phase — nonzero for the Pallas
sessions, zero for the XLA one.

The last line of standard output is ``{"ok": true, "device": {...}}`` and
the exit code 0 only when every phase passed. Without a TPU the script
exits non-zero and prints no result line.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

BASELINE_T_MICO = 71459        # host InHouseAutoMine triangle count, mico@1.0


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


class KernelModules:
    """Counts dumped lowered modules, and those holding a Mosaic kernel."""

    def __init__(self, jax):
        self._dir = tempfile.TemporaryDirectory()
        self.path = pathlib.Path(self._dir.name)
        jax.config.update("jax_dump_ir_to", str(self.path))
        self._seen: set = set()

    def delta(self) -> tuple[int, int]:
        """(new modules, new modules with a tpu_custom_call) since last."""
        new = [p for p in self.path.iterdir() if p.name not in self._seen]
        self._seen.update(p.name for p in new)
        return len(new), sum("tpu_custom_call" in p.read_text() for p in new)


def _queries(miner, weighted=None):
    from repro.mining import FOUR_MOTIF_SHAPES
    from repro.mining.plan import THREE_CHAIN_INDUCED, TRIANGLE
    motifs = list(FOUR_MOTIF_SHAPES)
    qs = {
        "T": lambda: miner.count("triangle"),
        "TC": lambda: miner.count("three-chain"),
        "TT": lambda: miner.count("tailed-triangle"),
        "4C": lambda: miner.count("4-clique"),
        "TM": lambda: dict(zip(("triangle", "chain"), miner.count_many(
            [TRIANGLE, THREE_CHAIN_INDUCED]))),
        "4M": lambda: dict(zip(motifs, miner.count_many(motifs))),
    }
    if weighted is not None:
        qs["wT"] = lambda: weighted.aggregate("triangle", op="sum")
    return qs


def run_session(name: str, miners, queries: dict, kmods, repeat=()):
    """Run each query (queries in ``repeat`` twice: cold, then steady with
    no new executable allowed) and log times and counters.
    Returns ({query: result}, modules lowered with a Pallas kernel)."""
    out = {}
    for q, fn in queries.items():
        before = sum(m.exec_cache.misses for m in miners)
        t0 = time.perf_counter()
        out[q] = fn()
        cold = time.perf_counter() - t0
        built = sum(m.exec_cache.misses for m in miners) - before
        line = f"{name} {q} = {out[q]}  cold {cold:.2f}s (+{built} executables)"
        if q in repeat:
            t0 = time.perf_counter()
            again = fn()
            steady = time.perf_counter() - t0
            rebuilt = sum(m.exec_cache.misses for m in miners) - before - built
            if again != out[q] or rebuilt:
                raise AssertionError(f"{name} {q}: repeat gave {again} with "
                                     f"{rebuilt} new executables")
            line += f", steady {steady:.2f}s (0 retraces)"
        log(line)
    mods, kern = kmods.delta()
    for m in miners:
        st = m.stats
        log(f"{name} exec cache {st['exec_cache']}, kernel dispatches "
            f"{st['runner']['level_kernel_dispatches']}, host syncs "
            f"{st['runner']['host_syncs']}")
    log(f"{name} lowered modules {mods}, with tpu_custom_call {kern}")
    return out, kern


def _check_equal(what: str, got, want) -> None:
    if got != want:
        raise AssertionError(f"{what}: {got} != {want}")
    log(f"{what}: equal")


def _pick(qs: dict, names) -> dict:
    return {q: qs[q] for q in names}


def single_chip(jax, kmods) -> None:
    from repro.graph import edge_weights, get_dataset, with_edge_values
    from repro.graph.csr import edge_list
    from repro.graph.datasets import dataset_stats
    from repro.kernels import ops
    from repro.launch import serve
    from repro.launch.mine import run_baseline
    from repro.mining import Miner, baseline

    t0 = time.perf_counter()
    g = get_dataset("mico", scale=1.0)
    gw = with_edge_values(g, edge_weights(edge_list(g), seed=0))
    log(f"mico x1.0: {dataset_stats(g)}, padded max degree "
        f"{g.padded_max_degree} ({time.perf_counter() - t0:.1f}s on host)")
    if ops._resolve("auto") != "pallas":
        raise AssertionError("backend 'auto' does not resolve to pallas")

    miner, wminer = Miner(g), Miner(gw)
    log(f"Miner: backend {miner.config.backend!r} -> "
        f"{ops._resolve(miner.config.backend)}, chunk {miner.runner.chunk}")
    pallas, kern = run_session(
        "pallas", [miner, wminer],
        _pick(_queries(miner, wminer), ("T", "TC", "TT", "4C", "TM", "wT")),
        kmods, repeat=("T",))
    if not kern:
        raise AssertionError("no dispatched module holds a tpu_custom_call")
    _check_equal("mico TM vs T, TC", pallas["TM"],
                 {"triangle": pallas["T"], "chain": pallas["TC"]})

    xminer, xwminer = Miner(g, backend="xla"), Miner(gw, backend="xla")
    xla, xkern = run_session("xla", [xminer, xwminer],
                             _pick(_queries(xminer, xwminer), ("T", "wT")),
                             kmods)
    if xkern:
        raise AssertionError("the xla session lowered a Pallas kernel")
    for q in xla:
        _check_equal(f"mico {q} pallas vs xla", pallas[q], xla[q])

    t0 = time.perf_counter()
    host_t = baseline.triangle_count(g)
    log(f"host InHouseAutoMine T = {host_t} "
        f"({time.perf_counter() - t0:.1f}s)")
    _check_equal("mico T pallas vs host baseline", pallas["T"], host_t)
    _check_equal("mico T host baseline vs recorded", host_t, BASELINE_T_MICO)

    email = get_dataset("email-eu-core", scale=1.0)
    em, xem = Miner(email), Miner(email, backend="xla")
    apps = ("T", "TC", "TT", "TM", "4C")
    got, _ = run_session("email", [em], _pick(_queries(em), apps + ("4M",)),
                         kmods)
    for app in apps:
        _check_equal(f"email-eu-core {app} pallas vs InHouseAutoMine",
                     got[app], run_baseline(app, email))
    xgot, _ = run_session("email-xla", [xem], _pick(_queries(xem), ("4M",)),
                          kmods)
    _check_equal("email-eu-core 4M pallas vs xla", got["4M"], xgot["4M"])

    t0 = time.perf_counter()
    served = serve.main(["--mine", "email-eu-core", "--rounds", "2"])
    log(f"service rounds done ({time.perf_counter() - t0:.1f}s)")
    want = {"T": got["T"], "TC": got["TC"], "TT": got["TT"], "4C": got["4C"],
            **got["4M"]}
    _check_equal("service counts vs session", served, want)
    mods, kern = kmods.delta()
    log(f"service lowered modules {mods}, with tpu_custom_call {kern}")


def xla_twin(jax, kmods) -> None:
    from repro.graph import get_dataset
    from repro.mining import Miner

    g = get_dataset("mico", scale=1.0)
    apps = ("TC", "TT", "4C", "TM")
    miner, xminer = Miner(g), Miner(g, backend="xla")
    pallas, kern = run_session("pallas", [miner],
                               _pick(_queries(miner), apps), kmods)
    if not kern:
        raise AssertionError("no dispatched module holds a tpu_custom_call")
    xla, xkern = run_session("xla", [xminer], _pick(_queries(xminer), apps),
                             kmods)
    if xkern:
        raise AssertionError("the xla session lowered a Pallas kernel")
    for q in apps:
        _check_equal(f"mico {q} pallas vs xla", pallas[q], xla[q])


def four_chips(jax, kmods) -> None:
    from repro.graph import get_dataset
    from repro.mining import Miner

    devs = jax.devices()
    if len(devs) < 4:
        raise AssertionError(f"--chips 4 needs 4 devices, have {len(devs)}")
    g = get_dataset("mico", scale=1.0)
    sharded = Miner(g, mesh=4)
    runner = sharded.runner
    log(f"mesh {dict(sharded.mesh.shape)} over "
        f"{[d.id for d in sharded.mesh.devices.flat]}, backend "
        f"{runner.backend!r}, chunk {runner.chunk}")
    mesh_devs = set(sharded.mesh.devices.flat)
    csr = runner.g.indices
    if csr.sharding.device_set != mesh_devs \
            or not csr.sharding.is_fully_replicated:
        raise AssertionError(f"CSR not replicated on the mesh: "
                             f"{csr.sharding}")
    _, dv0, _, _, n = next(iter(runner._edge_feed(True)))
    shards = {s.device for s in dv0.addressable_shards}
    if shards != mesh_devs or dv0.sharding.is_fully_replicated:
        raise AssertionError(f"feed block not split over the mesh: "
                             f"{dv0.sharding}")
    log(f"CSR replicated on {len(csr.sharding.device_set)} devices; feed "
        f"block {dv0.shape} split "
        f"{[s.data.shape for s in dv0.addressable_shards]} over devices "
        f"{sorted(d.id for d in shards)}, live per shard {n.tolist()}")

    apps = ("T", "4C")
    mesh_out, kern = run_session("mesh4", [sharded],
                                 _pick(_queries(sharded), apps), kmods,
                                 repeat=apps)
    if not kern:
        raise AssertionError("no sharded module holds a tpu_custom_call")
    one = Miner(g)
    one_out, _ = run_session("one-chip", [one], _pick(_queries(one), apps),
                             kmods)
    for q in apps:
        _check_equal(f"mico {q} mesh4 vs one chip", mesh_out[q], one_out[q])
    log(f"shard feed items {runner.stats['shard_feed_items']}, psum "
        f"reductions {runner.stats['psum_reductions']}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the single-chip phases; 4: the mesh path only")
    ap.add_argument("--xla-twin", action="store_true",
                    help="one chip: only mico TC, TT, 4C, TM, Pallas vs XLA")
    args = ap.parse_args(argv)
    if args.xla_twin and args.chips != 1:
        ap.error("--xla-twin runs on one chip")
    phase = four_chips if args.chips == 4 else \
        xla_twin if args.xla_twin else single_chip

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"[smoke] no TPU: JAX found {dev.platform!r}", file=sys.stderr)
        return 1
    log(f"device {dev.device_kind} x{len(jax.devices())}, jax "
        f"{jax.__version__}, compile cache {enable_compile_cache()}")
    kmods = KernelModules(jax)
    t0 = time.perf_counter()
    phase(jax, kmods)
    log(f"all phases passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
