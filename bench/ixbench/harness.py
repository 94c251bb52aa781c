"""One run of one cell: the harness behind ``bench/run.py``.

Everything that belongs to one configuration, traffic mix or per-layer
metric sits in a file of its own, found by the name ``BENCHMARK.json``
gives it:

* the configuration: the ``file`` of its ``configs`` entry;
* the traffic mix: ``bench/traffic/<traffic>.json``, naming the ``Miner``
  entry (``count``), the query, and the limit on the answer gap;
* the plain reference and the canonical work of the mix's query:
  ``bench/reference/<query>.py`` and ``bench/work/<query>.py``;
* a per-layer metric: ``bench/metrics/<metric>.py``, whose ``read(r)``
  takes a ``Readings`` and returns a number, or None when it finds
  nothing to read.

A run, in order: the compilation cache in the checkout, the graph from the
seed on the host, the program's ``build_csr`` and one ``Miner``, one warm-up
query (the end of ``setup_s``), the same query back to back for
``--seconds`` (one client, the query in flight finished), then the
reference, once the device numbers are read.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import gc
import importlib.util
import json
import math
import pathlib
import sys
import tempfile
import time
from contextlib import nullcontext

from . import compare, graphs
from . import trace as tracing

CACHE_DIR = "bench/.jax_cache"
TRAFFIC_DIR = "bench/traffic"
METRIC_DIR = "bench/metrics"
REFERENCE_DIR = "bench/reference"
WORK_DIR = "bench/work"
PEAKS = "bench/peaks.json"
COUNTERS = ("level_kernel_dispatches", "host_syncs")


class UnknownName(KeyError):
    """A cell, configuration, traffic mix or metric that has no entry."""


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def _load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: pathlib.Path):
    """Import one of the benchmark's per-name files by its path."""
    if not path.is_file():
        raise UnknownName(f"no file {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}".replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass(frozen=True)
class Cell:
    """One workload of ``BENCHMARK.json`` with everything found by name."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list


def _entry(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise UnknownName(f"unknown {what} {name!r}; known: "
                      f"{sorted(e['name'] for e in entries)}")


def find_cell(root: pathlib.Path, name: str) -> Cell:
    spec = _load_json(root / "BENCHMARK.json")
    w = _entry(spec["workloads"], name, "workload")
    cfg_entry = _entry(spec["configs"], w["config"], "configuration")
    config = _load_json(root / cfg_entry["file"])
    if int(config["chips"]) != int(w["chips"]):
        raise ValueError(f"{name}: the configuration holds "
                         f"{config['chips']} chips, the cell asks for "
                         f"{w['chips']}")
    tpath = root / TRAFFIC_DIR / f"{w['traffic']}.json"
    if not tpath.is_file():
        raise UnknownName(f"unknown traffic {w['traffic']!r}: no {tpath}")
    traffic = _load_json(tpath)
    if traffic["entry"] not in compare.ENTRIES:
        raise ValueError(f"traffic {w['traffic']!r}: entry must be one of "
                         f"{compare.ENTRIES}")
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=spec["end_to_end"],
                per_layer=spec["per_layer"])


@dataclasses.dataclass
class Readings:
    """What a per-layer metric reads: counters of the window, the reduced
    trace (None without one), and the canonical work of one query."""

    queries: int
    counters: dict
    window_compiles: int
    trace: tracing.Reduced | None
    peaks: dict
    chips: int
    _work: object = None

    @functools.cached_property
    def work_bytes(self) -> int:
        return self._work()


def peaks_for(root: pathlib.Path, kind: str) -> dict:
    table = _load_json(root / PEAKS)["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} has no peaks in {PEAKS}")
    return table[kind]


def check_devices(root: pathlib.Path, devices: list, chips: int) -> dict:
    """The peaks of the chips JAX found; NoAccelerator when it found no TPU
    or fewer chips than the cell asks for."""
    if devices[0].platform != "tpu":
        raise NoAccelerator(f"JAX found {devices[0].platform!r}, not a TPU")
    if len(devices) < chips:
        raise NoAccelerator(f"the cell asks for {chips} chips, JAX found "
                            f"{len(devices)}")
    return peaks_for(root, devices[0].device_kind)


def _enable_compile_cache(jax, root: pathlib.Path) -> None:
    """The persistent cache in the checkout, with no size limit: a limit
    set in the environment turns on eviction, which refuses every write
    once one entry in the directory lacks its access-time file."""
    jax.config.update("jax_compilation_cache_dir", str(root / CACHE_DIR))
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def _counters(miner) -> dict:
    stats = miner.runner.stats
    return {k: int(stats[k]) for k in COUNTERS}


def run_cell(root: pathlib.Path, name: str, seed: int, seconds: float,
             trace: bool, t0: float, log=sys.stderr) -> dict:
    """Run one cell once and return the result object (see run.py)."""
    cell = find_cell(root, name)
    reference = load_module(root / REFERENCE_DIR
                            / f"{cell.traffic['query']}.py")
    work = load_module(root / WORK_DIR / f"{cell.traffic['query']}.py")
    readers = {m["name"]: load_module(root / METRIC_DIR / f"{m['name']}.py")
               for m in cell.per_layer} if trace else {}

    import jax
    _enable_compile_cache(jax, root)
    devices = jax.devices()
    dev = devices[0]
    peaks = check_devices(root, devices, cell.chips)

    from repro.graph.csr import build_csr
    from repro.mining import Miner

    marks = [time.perf_counter()]
    hg = graphs.make_graph(cell.config, seed)
    marks.append(time.perf_counter())
    g = build_csr(hg.edges, num_vertices=hg.n, undirected=True)
    miner = Miner(g, mesh=cell.chips if cell.chips > 1 else None)
    query = functools.partial(miner.count, cell.traffic["query"])
    marks.append(time.perf_counter())
    query()
    marks.append(time.perf_counter())
    setup_s = marks[-1] - t0
    phases = zip(("start to chips", "graph", "csr and Miner", "warm-up"),
                 [marks[0] - t0] + [b - a for a, b in zip(marks, marks[1:])])
    print(f"[bench] {name} seed {seed}: {hg.n} vertices, {len(hg.edges)} "
          f"edges, set-up {setup_s:.3f}s ("
          + ", ".join(f"{k} {v:.3f}s" for k, v in phases) + ")",
          file=log, flush=True)

    before, misses = _counters(miner), miner.exec_cache.misses
    tmp = tempfile.TemporaryDirectory(prefix="bench-trace-") if trace \
        else nullcontext()
    answers: list = []
    with tmp as logdir:
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(logdir, profiler_options=opts)
        w0 = time.perf_counter()
        ends = [w0]
        while True:
            mark = jax.profiler.TraceAnnotation(f"query {len(answers)}") \
                if trace else nullcontext()
            try:
                with mark:
                    answers.append(query())
            except Exception as e:   # a failed query is a wrong answer
                answers.append(None)
                print(f"[bench] query {len(answers) - 1} failed: {e!r}",
                      file=log, flush=True)
            w1 = time.perf_counter()
            ends.append(w1)
            if w1 - w0 >= seconds:
                break
        reduced = None
        if trace:
            jax.profiler.stop_trace()
            reduced = tracing.reduce(*tracing.read_xplane(logdir))
    window_s = w1 - w0
    print("[bench] query seconds: " + " ".join(
        f"{b - a:.4f}" for a, b in zip(ends, ends[1:])), file=log, flush=True)
    after = _counters(miner)
    readings = Readings(
        queries=len(answers),
        counters={k: after[k] - before[k] for k in COUNTERS},
        window_compiles=miner.exec_cache.misses - misses,
        trace=reduced, peaks=peaks, chips=cell.chips,
        _work=lambda: work.stream_bytes(hg))
    used = devices[:cell.chips]
    mem = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in used]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": int(max(mem))}
    del query, miner, g
    gc.collect()

    want = compare.exact_answer(reference.values(hg))
    gaps = [compare.answer_gap([a], want) for a in answers]
    gap = max(gaps)
    limit = float(cell.traffic["answer_gap_limit"])
    failed = sum(d > limit for d in gaps)
    print(f"[bench] reference {want!r}, answers {sorted(set(map(repr, answers)))}",
          file=log, flush=True)

    e2e = {"query_s": (window_s / len(answers), "s"), "setup_s": (setup_s, "s")}
    metrics = {}
    if trace:
        for m in cell.per_layer:
            v = readers[m["name"]].read(readings)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        if reduced is not None:
            device["busy_s"] = reduced.busy_s
            device["window_s"] = reduced.window_s
    else:
        for m in cell.end_to_end:
            if m["name"] not in e2e:
                raise UnknownName(f"the harness has no end-to-end metric "
                                  f"{m['name']!r}")
            value, unit = e2e[m["name"]]
            metrics[m["name"]] = {"value": value, "unit": unit}
    result = {"correct": failed == 0, "attempted": len(answers),
              "failed": failed, "metrics": metrics, "device": device}
    if trace and reduced is not None:
        result["breakdown"] = {"device_ops": reduced.device_ops,
                               "idle_gaps": reduced.idle_gaps}
    result["checks"] = {"answer_gap": {
        "value": gap if math.isfinite(gap) else str(gap), "limit": limit}}
    return result


def main(argv=None, t0: float | None = None,
         root: pathlib.Path | None = None) -> int:
    t0 = time.perf_counter() if t0 is None else t0
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    root = root or pathlib.Path(__file__).resolve().parents[2]
    try:
        result = run_cell(root, args.workload, args.seed, args.seconds,
                          bool(args.trace), t0)
    except (NoAccelerator, UnknownName) as e:
        print(f"[bench] {e}", file=sys.stderr, flush=True)
        return 1
    for k, c in result["checks"].items():
        print(f"[bench] check {k} {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0
