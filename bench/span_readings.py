#!/usr/bin/env python3
"""One traced window of a cell, read by the program's own spans.

    python3 bench/span_readings.py --workload <cell> --seed <n> --seconds <s>

Builds the cell as ``bench/run.py`` does (graph from the seed, ``build_csr``,
one ``Miner``, one warm-up query), then sends the query back to back for
``--seconds`` under ``jax.profiler``, each wrapped in ``query <n>`` as the
harness wraps it. The last line of standard output is one JSON object, per
query unless named otherwise:

* ``busy_ms``, ``pallas_ms``, ``glue_ms``, ``device_idle_pct``: as the
  benchmark's readers compute them (``ixbench.trace.reduce``);
* ``feed_idle_ms``: device idle time under ``ix.feed_bucket``;
  ``sync_idle_ms``: under ``ix.sync`` or ``ix.finalize``;
  ``idle_ms_by_span``: the whole table (``ixbench.spans.idle_by_span``);
* ``gather_ms``: device self time of the ops whose ``tf_op`` holds the
  ``padded_rows`` scope;
* ``feed_gather_fill_pct``: 100 x ``feed_row_keys`` / ``feed_row_slots``,
  the registry counters, with both counts;
* ``ix_spans``: ``ix.*`` host events a query; the window's counters.

A reading the program cannot give (no such span, scope or counter) is
null, never 0. Needs a TPU, as ``bench/run.py`` does.
"""
import argparse
import functools
import json
import pathlib
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]

from ixbench import graphs, harness  # noqa: E402
from ixbench import spans as S  # noqa: E402
from ixbench import trace as T  # noqa: E402

FILL = ("feed_row_slots", "feed_row_keys")


def _per(v, n: int, scale: float = 1.0):
    return None if v is None else v * scale / n


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
    before = {k: miner.metrics.value(k) for k in harness.COUNTERS + FILL}
    misses = miner.exec_cache.misses
    answers = []
    with tempfile.TemporaryDirectory(prefix="span-trace-") as logdir:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(logdir, profiler_options=opts)
        w0 = time.perf_counter()
        while time.perf_counter() - w0 < seconds:
            with jax.profiler.TraceAnnotation(f"query {len(answers)}"):
                answers.append(query())
        window = time.perf_counter() - w0
        jax.profiler.stop_trace()
        chips, spans = S.read_xplane(logdir)
    n = len(answers)
    red = T.reduce(chips, spans)
    idle = S.idle_by_span(chips, spans)
    after = {k: miner.metrics.value(k) for k in before}
    delta = {k: None if after[k] is None else after[k] - before[k]
             for k in after}

    def idle_ms(pred):
        if idle is None:
            return None
        return sum(v for k, v in idle.items() if pred(k)) * 1e3 / n
    slots, keys_n = delta["feed_row_slots"], delta["feed_row_keys"]
    return {
        "workload": name, "seed": seed, "queries": n,
        "query_s": window / n, "answers": sorted(set(answers)),
        "busy_ms": red.busy_s * 1e3 / n,
        "pallas_ms": red.kernel_s * 1e3 / n,
        "glue_ms": red.glue_s * 1e3 / n,
        "device_idle_pct": 100.0 * (1.0 - red.busy_s / red.window_s),
        "idle_ms": (red.window_s - red.busy_s) * 1e3 / n,
        "feed_idle_ms": idle_ms(lambda k: k == "ix.feed_bucket"),
        "sync_idle_ms": idle_ms(lambda k: k.startswith("ix.sync")
                                or k == "ix.finalize"),
        "gather_ms": _per(S.scope_s(chips, spans), n, 1e3),
        "feed_gather_fill_pct": (None if not slots
                                 else 100.0 * keys_n / slots),
        "feed_row_slots": _per(slots, n), "feed_row_keys": _per(keys_n, n),
        "ix_spans": sum(s.name.startswith(S.PREFIX) for s in spans) / n,
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
        print(f"[spans] {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
