"""Graph mining driver — one ``Miner`` session serving the paper's workload.

  PYTHONPATH=src python -m repro.launch.mine --app T --dataset wiki-vote
  PYTHONPATH=src python -m repro.launch.mine --app FSM --dataset citeseer \\
      --support 100

The driver is a thin consumer of the session API: it builds a single
``mining.session.Miner`` for the dataset and issues every query against
it, so schedules and executables are derived once per invocation
(``--session-stats`` prints the cache counters that prove it, plus the
full Prometheus-style metrics snapshot).

Observability flags (repro.obs): ``--trace out.json`` enables span
tracing on the session and writes a Chrome-trace/Perfetto JSON of the
query's span tree; ``--jax-profile LOGDIR`` additionally wraps the query
in ``jax.profiler`` start/stop for an XLA-level profile.
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from repro.distributed.fault_tolerance import balanced_vertex_partition
from repro.graph import get_dataset
from repro.graph.datasets import DATASETS, dataset_stats
from repro.mining import baseline, exhaustive
from repro.mining.fsm import fsm, random_labels, sfsm
from repro.mining.plan import FOUR_MOTIF_SHAPES, TRIANGLE, \
    THREE_CHAIN_INDUCED
from repro.mining.session import Miner, MinerConfig

# per-pattern 4-motif codes (auto-scheduled Motif queries, zero engine code)
PATTERN_APPS = {"DM": "diamond", "CY": "4-cycle", "PW": "paw",
                "P4": "4-path", "S4": "4-star"}
# F4M / F3M: the motif batches through the session's schedule stage, with
# the static sharing report printed (4M / TM also fuse — these codes force
# the verbose forest path and honour --independent for A/B runs)
APPS = ["T", "TS", "TC", "TT", "TM", "4C", "5C", "4M", "F3M", "F4M",
        *PATTERN_APPS, "FSM", "sFSM"]

THREE_MOTIF_QUERIES = (TRIANGLE, THREE_CHAIN_INDUCED)


def run_app(app: str, miner: Miner, support: int = 100, labels=None,
            fused: bool = True):
    """Serve one app code from the session."""
    if app == "T":
        return miner.count("triangle")
    if app == "TS":
        return miner.count("triangle-nested")
    if app == "TC":
        return miner.count("three-chain")
    if app == "TT":
        return miner.count("tailed-triangle")
    if app in ("TM", "F3M"):
        if fused:
            t, chains = miner.count_many(list(THREE_MOTIF_QUERIES))
        else:
            t = miner.count(TRIANGLE)
            chains = miner.count(THREE_CHAIN_INDUCED)
        return {"triangle": t, "chain": chains}
    if app == "4C":
        return miner.count("4-clique")
    if app == "5C":
        return miner.count("5-clique")
    if app in ("4M", "F4M"):
        names = list(FOUR_MOTIF_SHAPES)
        if fused:
            return dict(zip(names, miner.count_many(names)))
        return {name: miner.count(name) for name in names}
    if app in PATTERN_APPS:
        return miner.count(PATTERN_APPS[app])
    if app in ("FSM", "sFSM"):
        fn = fsm if app == "FSM" else sfsm
        res = fn(miner.graph, labels, support, miner=miner)
        return {"frequent_patterns": len(res)}
    raise ValueError(app)


def _forest_report(app: str, miner: Miner) -> str:
    """Static sharing stats for the F3M/F4M batches (the session's
    schedule stage: auto matching-order search + forest merge)."""
    queries = list(FOUR_MOTIF_SHAPES) if app == "F4M" \
        else list(THREE_MOTIF_QUERIES)
    st = miner.schedule(queries).sharing_stats()
    levels = sorted({lv for _, lv in st["plan_ops"]})
    per_level = " ".join(
        f"L{lv}:{sum(v for (k, l2), v in st['plan_ops'].items() if l2 == lv)}"
        f"->{sum(v for (k, l2), v in st['forest_ops'].items() if l2 == lv)}"
        for lv in levels)
    return (f"{st['plans']} plans, ops {per_level}, feed passes "
            f"{st['feed_passes']['independent']}->{st['feed_passes']['fused']}")


def run_baseline(app: str, g):
    return {
        "T": lambda: baseline.triangle_count(g),
        "TC": lambda: baseline.three_chain_count(g, induced=True),
        "TT": lambda: baseline.tailed_triangle_count(g),
        "TM": lambda: baseline.three_motif(g),
        "4C": lambda: baseline.clique_count(g, 4),
        "5C": lambda: baseline.clique_count(g, 5),
    }[app]()


def main(argv=None):
    from repro.launch.cli import add_graph_args, add_session_args
    from repro.launch.compile_cache import enable_compile_cache

    ap = argparse.ArgumentParser()
    ap.add_argument("--app", choices=APPS, default="T")
    add_graph_args(ap, choices=list(DATASETS))
    ap.add_argument("--support", type=int, default=100)
    ap.add_argument("--labels", type=int, default=4)
    ap.add_argument("--baseline", action="store_true",
                    help="also run InHouseAutoMine (scalar CPU)")
    ap.add_argument("--independent", action="store_true",
                    help="run motif batches as independent per-pattern plans "
                         "instead of the fused PlanForest")
    ap.add_argument("--check", action="store_true",
                    help="F3M/F4M: assert fused counts == independent "
                         "per-plan counts (and == the brute-force census "
                         "when the graph is small enough)")
    ap.add_argument("--exhaustive", default="",
                    help="also run GRAMER-style exhaustive check for PATTERN")
    ap.add_argument("--partitions", type=int, default=0,
                    help="print degree-balanced partition stats (straggler)")
    add_session_args(ap)
    ap.add_argument("--jax-profile", default="", metavar="LOGDIR",
                    help="wrap the query in jax.profiler start/stop "
                         "(XLA-level trace written to LOGDIR)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    g = get_dataset(args.dataset, scale=args.scale)
    print(f"[mine] {args.dataset} x{args.scale}: {dataset_stats(g)}")
    miner = Miner(g, MinerConfig.from_args(args))
    telemetry = miner.telemetry
    if miner.mesh is not None:
        print(f"[mine] mesh: {args.shards}-way "
              f"({dict(miner.mesh.shape)})")
    labels = random_labels(g.num_vertices, args.labels, seed=1) \
        if args.app in ("FSM", "sFSM") else None
    if args.app in ("F3M", "F4M"):
        print(f"[mine] forest: {_forest_report(args.app, miner)}")
    t0 = time.perf_counter()
    with telemetry.jax_profile(args.jax_profile or None):
        res = run_app(args.app, miner, args.support, labels,
                      fused=not args.independent)
    dt = time.perf_counter() - t0
    print(f"[mine] {args.app} = {res}  ({dt:.2f}s, IntersectX engine)")
    if args.trace:
        path = telemetry.write_trace(args.trace)
        agg = telemetry.tracer.level_seconds()
        top = sorted(agg.items(), key=lambda kv: -kv[1])[:6]
        print(f"[mine] trace: {sum(1 for _ in telemetry.tracer.spans())} "
              f"spans -> {path}; self-time "
              + " ".join(f"{k}={v*1e3:.1f}ms" for k, v in top))
    if args.check and args.app in ("F3M", "F4M"):
        indep = run_app(args.app, miner, args.support, labels, fused=False)
        assert res == indep, (res, indep)
        print("[mine] fused == independent per-plan counts OK")
        if args.app == "F4M" and g.num_vertices <= 256:
            from repro.mining import reference
            census = reference.four_motif_counts(g)
            assert res == census, (res, census)
            print("[mine] fused == brute-force census OK")
    if args.baseline and args.app in ("T", "TC", "TT", "TM", "4C", "5C"):
        t0 = time.perf_counter()
        rb = run_baseline(args.app, g)
        dtb = time.perf_counter() - t0
        assert rb == res, (rb, res)
        print(f"[mine] baseline(InHouseAutoMine) = {rb} ({dtb:.2f}s) "
              f"=> engine speedup {dtb/max(dt,1e-9):.1f}x")
    if args.exhaustive:
        t0 = time.perf_counter()
        re_ = exhaustive.exhaustive_count(g, args.exhaustive)
        print(f"[mine] exhaustive({args.exhaustive}) = {re_} "
              f"({time.perf_counter()-t0:.2f}s, GRAMER-style)")
    if args.partitions:
        assign = balanced_vertex_partition(np.asarray(g.degrees),
                                           args.partitions)
        cost = np.asarray(g.degrees, dtype=np.float64) ** 2
        loads = np.bincount(assign, weights=cost, minlength=args.partitions)
        print(f"[mine] {args.partitions} partitions: load imbalance "
              f"max/mean = {loads.max()/loads.mean():.3f}")
    if args.session_stats:
        st = miner.stats
        print(f"[mine] session: {st['queries']} queries, "
              f"exec cache {st['exec_cache']['hits']} hits / "
              f"{st['exec_cache']['misses']} traces, "
              f"plan cache {st['plan_hits']}/{st['plan_misses']}, "
              f"schedule cache {st['schedule_hits']}/{st['schedule_misses']}")
        if miner.mesh is not None:
            rs = st["runner"]
            fi = rs["shard_feed_items"]
            print(f"[mine] shards: feed items {fi} "
                  f"(max/min {max(fi)/max(min(fi), 1):.2f}), "
                  f"{rs['psum_reductions']} psum reductions")
        print("[mine] metrics:")
        print(telemetry.prometheus_text(), end="")


if __name__ == "__main__":
    main()
