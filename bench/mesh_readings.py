#!/usr/bin/env python3
"""One traced window of a cell, read by what a mesh of chips adds.

    python3 bench/mesh_readings.py --workload <cell> --seed <n> --seconds <s>

Builds the cell as ``bench/run.py`` does (graph from the seed, ``build_csr``,
one ``Miner`` over the cell's chips, one warm-up query), then sends the
query back to back for ``--seconds`` under ``jax.profiler``, each wrapped in
``query <n>`` as the harness wraps it. The last line of standard output is
one JSON object, per query unless named otherwise:

* ``collective_ms``: device self time of the ops whose ``tf_op`` holds the
  ``mesh_psum`` scope (the leaf reductions across chips), per chip;
* ``busy_ms_by_chip``: each chip's busy time; ``busy_spread``: their
  (max - min) / mean, a share (the chip with the most work sets the pace
  of every lockstep super-step);
* ``feed_step_idle_ms``, ``feed_bucket_idle_ms``: device idle time under
  ``ix.feed_step`` (slice, deal and upload of one feed step) and under
  ``ix.feed_bucket``, averaged over chips (``ixbench.spans.idle_by_span``);
* ``mesh_feed_fill_pct``: 100 x the window's ``shard_feed_items`` summed
  over shards / (that + ``shard_pad_items``), with both counts;
* ``dispatches``, ``host_syncs``, ``window_compiles``.

A reading the program cannot give (no such span, scope or counter, or one
chip) is null, never 0. Needs a TPU, as ``bench/run.py`` does.
"""
import argparse
import functools
import glob
import json
import os
import pathlib
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]

from ixbench import graphs, harness  # noqa: E402
from ixbench import spans as S  # noqa: E402
from ixbench import trace as T  # noqa: E402

SCOPE = "mesh_psum"


def collective_ops(raw: bytes) -> dict:
    """{device plane: {op name, ...}}: the ops whose ``tf_op`` names the
    ``mesh_psum`` scope, from a serialized ``XSpace``."""
    return {plane: {op for op, tags in ops.items()
                    if any(SCOPE in t for t in tags)}
            for plane, ops in S.op_scopes(raw).items()}


def collective_s(chips: dict, spans: list, ops: dict) -> float | None:
    """Device self time of the ``ops`` in the window, seconds per chip;
    None without one."""
    win = S._window(spans)
    if win is None or not chips:
        return None
    total, seen = 0.0, False
    for plane, plane_ops in chips.items():
        names = ops.get(plane, set())
        for o, d in T._self_times(plane_ops, *win):
            if o.name in names:
                total += d
                seen = True
    return total / len(chips) / 1e9 if seen else None


def busy_by_chip(chips: dict, spans: list) -> dict | None:
    """{device plane: busy seconds in the window}; None without a query
    annotation or a chip."""
    win = S._window(spans)
    if win is None or not chips:
        return None
    return {plane: sum(e - s for s, e in T._merged(ops, *win)) / 1e9
            for plane, ops in sorted(chips.items())}


def spread(values) -> float | None:
    """(max - min) / mean; None for fewer than two values or no time."""
    values = list(values)
    if len(values) < 2 or sum(values) <= 0:
        return None
    return (max(values) - min(values)) / (sum(values) / len(values))


def fill_pct(items, pad) -> float | None:
    if items is None or pad is None or items + pad <= 0:
        return None
    return 100.0 * items / (items + pad)


def _counters(miner) -> dict:
    reg = miner.metrics
    feed = reg.series("shard_feed_items")
    return {"shard_feed_items": (sum(c.value for c in feed.values())
                                 if feed else None),
            "shard_pad_items": reg.value("shard_pad_items"),
            **{k: reg.value(k) for k in harness.COUNTERS}}


def _read(logdir: str):
    files = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    with open(files[-1], "rb") as f:
        ops = collective_ops(f.read())
    chips, spans = S.read_xplane(logdir)
    return chips, spans, ops


def readings(name: str, seed: int, seconds: float) -> dict:
    cell = harness.find_cell(ROOT, name)
    import jax
    harness._enable_compile_cache(jax, ROOT)
    harness.check_devices(ROOT, jax.devices(), cell.chips)
    from repro.graph.csr import build_csr
    from repro.mining import Miner

    hg = graphs.make_graph(cell.config, seed)
    g = build_csr(hg.edges, num_vertices=hg.n, undirected=True)
    miner = Miner(g, mesh=cell.chips if cell.chips > 1 else None)
    query = functools.partial(miner.count, cell.traffic["query"])
    query()
    before, misses = _counters(miner), miner.exec_cache.misses
    answers = []
    with tempfile.TemporaryDirectory(prefix="mesh-trace-") as logdir:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(logdir, profiler_options=opts)
        w0 = time.perf_counter()
        while time.perf_counter() - w0 < seconds:
            with jax.profiler.TraceAnnotation(f"query {len(answers)}"):
                answers.append(query())
        window = time.perf_counter() - w0
        t0 = time.perf_counter()
        jax.profiler.stop_trace()
        t1 = time.perf_counter()
        chips, spans, ops = _read(logdir)
        print(f"[mesh] {len(answers)} queries in {window:.3f}s; stop_trace "
              f"{t1 - t0:.3f}s, reading the trace "
              f"{time.perf_counter() - t1:.3f}s, "
              f"{sum(map(len, chips.values()))} device ops",
              file=sys.stderr, flush=True)
    n = len(answers)
    after = _counters(miner)
    delta = {k: None if after[k] is None or before[k] is None
             else after[k] - before[k] for k in after}
    red = T.reduce(chips, spans)
    idle = S.idle_by_span(chips, spans)
    busy = busy_by_chip(chips, spans)
    coll = collective_s(chips, spans, ops)

    def idle_ms(label):
        if idle is None or not any(s.name == label for s in spans):
            return None
        return idle.get(label, 0.0) * 1e3 / n
    items, pad = delta["shard_feed_items"], delta["shard_pad_items"]
    return {
        "workload": name, "seed": seed, "queries": n, "chips": len(chips),
        "query_s": window / n, "answers": sorted(set(answers)),
        "busy_ms": None if red is None else red.busy_s * 1e3 / n,
        "device_idle_pct": (None if red is None else
                            100.0 * (1.0 - red.busy_s / red.window_s)),
        "collective_ms": None if coll is None else coll * 1e3 / n,
        "busy_ms_by_chip": (None if busy is None else
                            {k: v * 1e3 / n for k, v in busy.items()}),
        "busy_spread": None if busy is None else spread(busy.values()),
        "feed_step_idle_ms": idle_ms("ix.feed_step"),
        "feed_bucket_idle_ms": idle_ms("ix.feed_bucket"),
        "mesh_feed_fill_pct": fill_pct(items, pad),
        "shard_feed_items": None if items is None else items / n,
        "shard_pad_items": None if pad is None else pad / n,
        "dispatches": delta["level_kernel_dispatches"] / n,
        "host_syncs": delta["host_syncs"] / n,
        "window_compiles": miner.exec_cache.misses - misses,
        "idle_ms_by_span": (None if idle is None else
                            {k: v * 1e3 / n for k, v in idle.items()}),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    try:
        out = readings(args.workload, args.seed, args.seconds)
    except (harness.NoAccelerator, harness.UnknownName) as e:
        print(f"[mesh] {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
