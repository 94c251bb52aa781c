"""CI perf-regression gate over the mining benchmarks.

Runs a small-graph subset of ``bench_mining``'s reports, writes the result
to ``BENCH_mining.json`` (uploaded as a CI artifact) and compares it
against the checked-in ``benchmarks/baseline.json``:

* **exact metrics** — mining counts and structural counters (forest level-2
  dispatch/feed counts, membership dispatches per general level). The datasets are deterministic synthetic generators and the
  counters are schedule facts, so these are machine-independent and must
  match the baseline EXACTLY: any drift is a correctness or scheduling
  regression, not noise.
* **ratio metrics** — wall-clock ratios (plan interpreter overhead, forest
  fusion speedup).
  Ratios, not absolute times, so they transfer across machines, but CI
  runners are noisy: a metric only fails when it is worse than baseline by
  more than its tolerance (per-metric ``tolerances`` in baseline.json,
  direction from ``directions``: for ``higher_better`` a regression is
  ``got < base * (1 - tol)``, for ``lower_better`` it is
  ``got > base * (1 + tol)``).

Usage (CI runs exactly this):

    PYTHONPATH=src python benchmarks/ci_gate.py \
        --out BENCH_mining.json --baseline benchmarks/baseline.json

``--update-baseline`` rewrites baseline.json from the current measurement
(keeping tolerances/directions) — run locally when a PR legitimately moves
a ratio, and say so in the PR.

``--telemetry`` adds the observability parity section (exact keys): the
``repro.obs`` registry-derived stats view must equal the legacy counters
bit-for-bit on the full app mix, tracing on must change nothing (zero
extra dispatches), and span counts per category are recorded as schedule
facts. The traced span timings land in the output JSON (artifact) under
``telemetry_spans`` but are never baselined — they are wall clock.

``--values`` adds the SVPU value-plane section: weighted sum/max/min
aggregates must equal the host-float64 permutation oracle EXACTLY (dyadic
weights make every aggregate representable in f32), the weighted query's
kernel-dispatch and feed-chunk counters must equal the unweighted twin's
(value lanes ride, never add), repeats retrace nothing, and the
weighted-vs-unweighted wall-clock ratio is tolerance-gated.
"""
from __future__ import annotations

import argparse
import json
import platform
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmarks.bench_mining import (forest_fusion_report,  # noqa: E402
                                     general_level_report,
                                     plan_overhead_report,
                                     session_serving_report,
                                     sharded_scaling_report,
                                     svpu_report)

# exact app counts: small + cheap (deterministic synthetic graphs)
COUNT_SETS = [("citeseer", 1.0), ("email-eu-core", 0.25)]
# session-API smoke: one Miner serving the app mix twice on this set
SESSION_SET = ("email-eu-core", 0.25)
# mesh-sharded leg (--sharded, needs >= 8 devices: CI sets
# XLA_FLAGS=--xla_force_host_platform_device_count=8): counts parity,
# shard/psum counters, retraces and the dispatch-scaling bound
SHARDED_SET = ("email-eu-core", 0.25)
SHARDED_WIDTHS = (1, 8)
# telemetry leg (--telemetry): registry-derived stats view must equal the
# legacy counters bit-for-bit, and enabling tracing must not change them
TELEMETRY_SET = ("email-eu-core", 0.25)
# serving leg (--serving): concurrent MiningService facts — cross-request
# feed-pass sharing, steady/load retraces, result-cache counters (exact)
# plus the qps/p99 ratios vs a sequential session (tolerance-gated)
SERVING_SET = ("email-eu-core", 0.25)
# values leg (--values): SVPU weighted aggregates — exact oracle equality,
# dispatch/feed parity vs the unweighted twin, zero repeat retraces (exact)
# plus the weighted-overhead wall-clock ratio (tolerance-gated)
VALUES_SET = ("email-eu-core", 0.25)
# wall-clock ratios + structural counters: dense enough that the timed
# region is hundreds of ms, not noise (see stability note in tolerances)
PERF_SET = ("email-eu-core", 1.0)

# optional gate sections: each key prefix only exists in a run that passed
# the matching flag; compare()/--update-baseline treat absent sections as
# "not run this leg", never as regressions
SECTION_PREFIXES = ("sharded.", "telemetry.", "serving.", "values.")

# ratio tolerances (fractional, see module docstring) — generous because CI
# wall clock is shared-runner noisy; the exact counters carry the precise
# regression signal, the ratios catch order-of-magnitude slumps.
DEFAULT_TOLERANCES = {
    "plan_overhead_4C": 0.6,
    "plan_overhead_TT": 0.8,
    "fusion_speedup": 0.5,
    # service vs sequential: thread scheduling + queueing make these the
    # noisiest gated ratios (p50 is artifact-only for the same reason)
    "qps_vs_sequential": 0.6,
    "p99_vs_sequential": 2.0,
    # weighted vs unweighted wall clock: both sides are warmed identical
    # dispatch sequences, but the value lanes add per-dispatch work inside
    # the kernel, so gate only order-of-magnitude slumps
    "weighted_overhead": 0.8,
}
DIRECTIONS = {
    "plan_overhead_4C": "lower_better",
    "plan_overhead_TT": "lower_better",
    "fusion_speedup": "higher_better",
    "qps_vs_sequential": "higher_better",
    "p99_vs_sequential": "lower_better",
    "weighted_overhead": "lower_better",
}


def measure_sharded(exact: dict) -> None:
    """Mesh-sharded gate section (CI's multi-device leg): every key is an
    exact schedule/count fact under 8 fake CPU devices.

    * counts parity — the sharded mix must equal the 1-device mix
      bit-for-bit (asserted inside ``sharded_scaling_report``; the counts
      land in the baseline once);
    * retraces — a repeated sharded pass builds 0 new executables;
    * dispatch/psum counters — per-shard dispatches and psum leaf
      reductions per pass are schedule facts, including the scaling bound
      ``dispatches_8 <= dispatches_1 / 8 + allowance``;
    * feed balance — the round-robin partitioner's per-shard feed items on
      FULL email-eu-core (host-only sweep, no mining) with the max/min
      ratio <= 2 acceptance bound.
    """
    import jax
    from repro.graph import get_dataset
    from repro.mining.engine import choose_chunk
    from repro.mining.shard import shard_edge_steps
    if jax.device_count() < max(SHARDED_WIDTHS):
        raise SystemExit(
            f"[gate] --sharded needs {max(SHARDED_WIDTHS)} devices, have "
            f"{jax.device_count()}: set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={max(SHARDED_WIDTHS)}")

    name, scale = SHARDED_SET
    g = get_dataset(name, scale=scale)
    tag = f"{name}@{scale}"
    print(f"[gate] {tag}: sharded scaling ...", flush=True)
    sr = sharded_scaling_report(g, SHARDED_WIDTHS)
    s_max = max(SHARDED_WIDTHS)
    many = sr["per_mesh"][str(s_max)]
    exact[f"sharded.{tag}.counts"] = many["counts"]
    exact[f"sharded.{tag}.retraces_second_pass"] = \
        many["retraces_second_pass"]
    exact[f"sharded.{tag}.dispatches_per_pass"] = {
        str(s): sr["per_mesh"][str(s)]["dispatches_per_pass"]
        for s in SHARDED_WIDTHS}
    exact[f"sharded.{tag}.psum_reductions_per_pass"] = \
        many["psum_reductions_per_pass"]
    exact[f"sharded.{tag}.shard_feed_items_{s_max}"] = \
        many["shard_feed_items"]
    exact[f"sharded.{tag}.dispatch_scaling_ok"] = \
        bool(many["dispatch_scaling_ok"])

    # full-graph partitioner balance: host-only feed sweep, no mining
    g_full = get_dataset(name, scale=1.0)
    chunk = min(choose_chunk(g_full.padded_max_degree), 1 << 15)
    items = [0] * s_max
    for _cap, _v0, _v1, n in shard_edge_steps(g_full, chunk, s_max):
        for s in range(s_max):
            items[s] += int(n[s])
    ratio = max(items) / max(min(items), 1)
    exact[f"sharded.{name}.feed_items_{s_max}"] = items
    exact[f"sharded.{name}.feed_balance_ratio_le_2"] = bool(ratio <= 2.0)
    print(f"[gate] sharded: feed ratio {ratio:.3f} on {name}, "
          f"dispatches {exact[f'sharded.{tag}.dispatches_per_pass']}, "
          f"{many['psum_reductions_per_pass']} psums/pass", flush=True)


def measure_telemetry(exact: dict, sharded: bool = False) -> dict:
    """Telemetry gate section (``--telemetry``): the ``repro.obs`` registry
    is the source of truth for runner/session counters and the legacy
    ``stats`` dicts are derived views — this leg runs the full app mix on
    one traced ``Miner`` and one untraced one, then records as exact keys:

    * ``registry_equals_legacy`` — every legacy stats key read back through
      the public ``MetricsRegistry`` API matches the view bit-for-bit
      (including the per-shard ``shard_feed_items`` labeled series);
    * ``enabled_disabled_parity`` — counts AND the complete stats dict are
      identical with tracing on vs off (tracing is observationally free:
      zero extra kernel dispatches, no counter drift);
    * the traced run's runner/session counters and per-category span counts
      — all schedule facts, machine-independent.

    With ``--sharded`` too, the same checks repeat on a mesh=8 session.
    Returns the traced spans summary (seconds — machine-dependent, so it
    rides in the output doc ungated, never in the baseline)."""
    from repro.graph import get_dataset
    from repro.mining.plan import FOUR_MOTIF_SHAPES
    from repro.mining.session import Miner
    from repro.obs import Telemetry

    name, scale = TELEMETRY_SET
    g = get_dataset(name, scale=scale)
    tag = f"{name}@{scale}"
    motifs = list(FOUR_MOTIF_SHAPES)

    def mix(miner):
        return {"T": miner.count("triangle"),
                "TC": miner.count("three-chain"),
                "TT": miner.count("tailed-triangle"),
                "4C": miner.count("4-clique"),
                "4M": list(miner.count_many(motifs))}

    spans_doc: dict = {}
    for mesh in [None] + ([8] if sharded else []):
        mtag = tag if mesh is None else f"{tag}.mesh{mesh}"
        print(f"[gate] {mtag}: telemetry parity ...", flush=True)
        telemetry = Telemetry(enabled=True)
        traced = Miner(g, mesh=mesh, telemetry=telemetry)
        counts = mix(traced)
        plain = Miner(g, mesh=mesh)
        counts_plain = mix(plain)

        # legacy view == registry, re-read through the public metrics API
        # (a drifted exposure — wrong counter bound to a key — fails here)
        reg = telemetry.metrics
        rs = dict(traced.runner.stats)
        reg_ok = all(reg.value(k) == v for k, v in rs.items()
                     if not isinstance(v, list))
        if "shard_feed_items" in rs:
            fam = reg.series("shard_feed_items")
            per = [fam[(("shard", s),)].value
                   for s in range(len(rs["shard_feed_items"]))]
            reg_ok = reg_ok and per == rs["shard_feed_items"]
        sess = traced.stats
        sess_keys = ("queries", "plan_hits", "plan_misses",
                     "schedule_hits", "schedule_misses")
        reg_ok = reg_ok and all(reg.value(k) == sess[k] for k in sess_keys)

        by_cat: dict[str, int] = {}
        for sp in telemetry.tracer.spans():
            by_cat[sp.cat] = by_cat.get(sp.cat, 0) + 1

        exact[f"telemetry.{mtag}.registry_equals_legacy"] = bool(reg_ok)
        exact[f"telemetry.{mtag}.enabled_disabled_parity"] = bool(
            counts == counts_plain and sess == plain.stats)
        exact[f"telemetry.{mtag}.runner_stats"] = rs
        exact[f"telemetry.{mtag}.session_counters"] = {
            k: sess[k] for k in sess_keys}
        exact[f"telemetry.{mtag}.span_counts"] = dict(sorted(by_cat.items()))
        spans_doc[mtag] = telemetry.snapshot()["spans"]
        print(f"[gate] telemetry {mtag}: registry==legacy {reg_ok}, "
              f"spans {by_cat}", flush=True)
    return spans_doc


def measure_serving(exact: dict, ratios: dict, sharded: bool = False,
                    trace_telemetry=None) -> dict:
    """Serving gate section (``--serving``): the concurrent MiningService
    on the small deterministic set.

    Exact keys: the batched counts, the cross-request feed-pass sharing
    facts (``fused < independent`` is the batching acceptance), zero
    steady-state retraces — including under threaded burst load — and the
    result-cache hit/invalidation counters. With ``--sharded`` too, the
    mixed sharded/unsharded pool repeats the batching phase on a mesh=8
    bulk worker and must reproduce the same counts. Gated ratios:
    qps/p99 of the loaded service vs a sequential warmed session.
    Returns the artifact-only wall-clock details (absolute latencies)."""
    from benchmarks.bench_serving import batching_report, cache_report, \
        load_report
    from repro.graph import get_dataset

    name, scale = SERVING_SET
    g = get_dataset(name, scale=scale)
    tag = f"{name}@{scale}"
    print(f"[gate] {tag}: serving (batching + cache) ...", flush=True)
    b = batching_report(g, telemetry=trace_telemetry)
    exact[f"serving.{tag}.counts"] = b["counts"]
    exact[f"serving.{tag}.batch_requests"] = b["batch_requests"]
    exact[f"serving.{tag}.feed_passes"] = [
        b["feed_passes_independent"], b["feed_passes_fused"]]
    exact[f"serving.{tag}.sharing_ok"] = b["sharing_ok"]
    exact[f"serving.{tag}.steady_retraces"] = b["steady_retraces"]
    c = cache_report(g)
    exact[f"serving.{tag}.cache"] = c
    if sharded:
        print(f"[gate] {tag}: serving mixed sharded pool ...", flush=True)
        bm = batching_report(g, shards=8)
        exact[f"serving.{tag}.mesh8.counts_parity"] = \
            bool(bm["counts"] == b["counts"])
        exact[f"serving.{tag}.mesh8.workers"] = bm["workers"]
        exact[f"serving.{tag}.mesh8.sharing_ok"] = bm["sharing_ok"]
        exact[f"serving.{tag}.mesh8.steady_retraces"] = bm["steady_retraces"]
    print(f"[gate] {tag}: serving load ...", flush=True)
    ld = load_report(g, telemetry=trace_telemetry)
    exact[f"serving.{tag}.load_sharing_ok"] = ld["load_sharing_ok"]
    exact[f"serving.{tag}.load_retraces"] = ld["load_retraces"]
    ratios[f"serving.{tag}.qps_vs_sequential"] = ld["qps_vs_sequential"]
    ratios[f"serving.{tag}.p99_vs_sequential"] = ld["p99_vs_sequential"]
    print(f"[gate] serving: feed passes "
          f"{exact[f'serving.{tag}.feed_passes']}, "
          f"load retraces {ld['load_retraces']}, qps x"
          f"{ld['qps_vs_sequential']}, p99 x{ld['p99_vs_sequential']}",
          flush=True)
    return {"sequential": ld["sequential"], "service": ld["service"],
            "p50_vs_sequential": ld["p50_vs_sequential"]}


def measure_values(exact: dict, ratios: dict) -> None:
    """SVPU value-plane gate section (``--values``): every key but the
    overhead ratio is an exact fact.

    * weighted aggregates on the gate set AND the tiny-oracle graph —
      dyadic weights make sum/max/min exactly representable in f32, so
      the values baseline bit-for-bit and ``oracle_exact`` asserts
      engine == host-float64 permutation oracle;
    * dispatch/feed parity — the weighted query's per-pass
      ``level_kernel_dispatches`` and ``feed_chunks`` equal the
      unweighted twin's: value lanes ride existing membership dispatches
      and add ZERO feed passes;
    * retraces — a repeated weighted query builds 0 new executables;
    * ``weighted_overhead`` — warmed weighted/unweighted wall-clock
      ratio, tolerance-gated.
    """
    from repro.graph import get_dataset

    name, scale = VALUES_SET
    g = get_dataset(name, scale=scale)
    tag = f"{name}@{scale}"
    print(f"[gate] {tag}: SVPU value plane ...", flush=True)
    sv = svpu_report(g)
    for app in ("T", "4C"):
        row = sv["queries"][app]
        exact[f"values.{tag}.{app}.aggregate"] = row["aggregate"]["result"]
        exact[f"values.{tag}.{app}.count"] = row["count"]["result"]
        exact[f"values.{tag}.{app}.dispatches"] = [
            row["count"]["dispatches"], row["aggregate"]["dispatches"]]
        exact[f"values.{tag}.{app}.feed_chunks"] = [
            row["count"]["feed_chunks"], row["aggregate"]["feed_chunks"]]
        exact[f"values.{tag}.{app}.dispatch_parity_ok"] = \
            bool(row["dispatch_parity_ok"] and row["feed_parity_ok"])
    exact[f"values.{tag}.retraces_second_pass"] = sv["retraces_second_pass"]
    exact[f"values.{tag}.value_lane_dispatches"] = \
        sv["value_lane_dispatches"]
    exact[f"values.{tag}.oracle_exact"] = \
        bool(sv["oracle_check"]["exact_match"])
    exact[f"values.{tag}.oracle_values"] = sv["oracle_check"]["values"]
    ratios[f"values.{tag}.weighted_overhead"] = sv["weighted_overhead"]
    print(f"[gate] values: oracle exact {sv['oracle_check']['exact_match']}"
          f", dispatch parity "
          f"{[sv['queries'][a]['dispatch_parity_ok'] for a in ('T', '4C')]}"
          f", overhead x{sv['weighted_overhead']}, retraces "
          f"{sv['retraces_second_pass']}", flush=True)


def measure(sharded: bool = False, telemetry: bool = False,
            serving: bool = False, serving_trace: str = "",
            values: bool = False) -> dict:
    from repro.graph import get_dataset
    from repro.mining import Miner
    from repro.mining.plan import FOUR_MOTIF_SHAPES
    exact: dict = {}
    ratios: dict = {}
    for name, scale in COUNT_SETS:
        g = get_dataset(name, scale=scale)
        tag = f"{name}@{scale}"
        print(f"[gate] {tag}: counting ...", flush=True)
        m = Miner(g)
        exact[f"{tag}.T"] = m.count("triangle")
        exact[f"{tag}.TC"] = m.count("three-chain")
        exact[f"{tag}.TT"] = m.count("tailed-triangle")
        exact[f"{tag}.4C"] = m.count("4-clique")
        exact[f"{tag}.4M"] = dict(zip(
            FOUR_MOTIF_SHAPES, m.count_many(list(FOUR_MOTIF_SHAPES))))

    # session-API smoke leg: one Miner serving the full app mix twice —
    # exact counts, the zero-retrace reuse contract and the auto-scheduled
    # forest counters are all schedule facts (machine-independent)
    name, scale = SESSION_SET
    g = get_dataset(name, scale=scale)
    tag = f"{name}@{scale}"
    print(f"[gate] {tag}: session serving ...", flush=True)
    ss = session_serving_report(g)
    exact[f"{tag}.session.counts"] = ss["counts"]
    exact[f"{tag}.session.retraces_second_pass"] = ss["retraces_second_pass"]
    exact[f"{tag}.session.retraces_first_pass"] = ss["retraces_first_pass"]
    exact[f"{tag}.session.exec_cache_entries"] = ss["exec_cache"]["entries"]
    exact[f"{tag}.session.level2_execs_per_pass"] = \
        ss["level2_execs_per_pass"]
    exact[f"{tag}.session.level2_nodes_static"] = ss["level2_nodes_static"]
    exact[f"{tag}.session.feed_passes"] = ss["feed_passes"]

    name, scale = PERF_SET
    g = get_dataset(name, scale=scale)
    tag = f"{name}@{scale}"
    print(f"[gate] {tag}: perf reports ...", flush=True)
    gl = general_level_report(g)
    exact[f"{tag}.CY"] = gl["count"]
    exact[f"{tag}.fused_level.k_general"] = gl["k_general"]
    exact[f"{tag}.fused_level.dispatches_per_general_level"] = {
        "fused": gl["dispatches_per_general_level"]}

    ff = forest_fusion_report(g)
    exact[f"{tag}.forest.level2_execs"] = [
        ff["level2_execs_independent"], ff["level2_execs_fused"]]
    exact[f"{tag}.forest.level2_ops_static"] = list(ff["level2_ops_static"])
    exact[f"{tag}.forest.feed_passes"] = list(ff["feed_passes"])
    ratios[f"{tag}.fusion_speedup"] = ff["fusion_speedup"]

    po = plan_overhead_report(g)
    ratios[f"{tag}.plan_overhead_4C"] = po["4C"]["plan_overhead"]
    ratios[f"{tag}.plan_overhead_TT"] = po["TT"]["plan_overhead"]

    if sharded:
        measure_sharded(exact)
    if values:
        measure_values(exact, ratios)
    out = {
        "meta": {
            "python": platform.python_version(),
            "machine": platform.machine(),
            "measured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        },
        "exact": exact,
        "ratios": ratios,
    }
    if telemetry:
        # spans carry wall-clock seconds: artifact-only, never baselined
        out["telemetry_spans"] = measure_telemetry(exact, sharded=sharded)
    if serving:
        from repro.obs import Telemetry
        trace_tel = Telemetry(enabled=bool(serving_trace))
        # absolute latencies are wall clock: artifact-only, never baselined
        out["serving_latency"] = measure_serving(
            exact, ratios, sharded=sharded, trace_telemetry=trace_tel)
        if serving_trace:
            path = trace_tel.write_trace(serving_trace)
            print(f"[gate] serving trace -> {path}", flush=True)
    return out


def _tolerance_for(metric: str, baseline: dict) -> tuple[float, str]:
    """(tolerance, direction) for a ratio key '<dataset>@<scale>.<name>';
    matched on the final dotted component (the scale contains a dot, so
    splitting on the FIRST dot would eat the metric name)."""
    stem = metric.rsplit(".", 1)[-1]
    tols = baseline.get("tolerances", DEFAULT_TOLERANCES)
    return (float(tols.get(stem, 0.6)),
            baseline.get("directions", DIRECTIONS).get(stem, "lower_better"))


def _section_of(key: str) -> str | None:
    """The optional-section prefix a metric key belongs to, if any."""
    return next((p for p in SECTION_PREFIXES if key.startswith(p)), None)


def _skip_key(key: str, ran: dict) -> bool:
    """True when a baseline key belongs to a section this invocation did
    not run (``sharded.*`` without --sharded, etc.). ``*.mesh*`` keys in
    the telemetry/serving sections additionally need --sharded."""
    sect = _section_of(key)
    if sect is None:
        return False
    if not ran[sect]:
        return True
    return ".mesh" in key and not ran["sharded."]


def compare(got: dict, baseline: dict) -> list[str]:
    """Return a list of regression messages (empty = gate passes).

    The ``sharded.*`` keys only exist when the gate ran with ``--sharded``
    (the multi-device CI leg), ``telemetry.*`` only with ``--telemetry``
    and ``serving.*`` only with ``--serving``. A run without those flags
    skips the matching baseline keys (exact AND ratios) instead of
    failing, so a partial invocation stays green against the full
    baseline."""
    failures = []
    base_exact = baseline.get("exact", {})
    ran = {p: any(k.startswith(p) for d in (got["exact"], got["ratios"])
                  for k in d)
           for p in SECTION_PREFIXES}
    for key, want in base_exact.items():
        if _skip_key(key, ran):
            continue
        have = got["exact"].get(key, "<missing>")
        if have != want:
            failures.append(f"EXACT {key}: baseline {want!r} != got {have!r}")
    for key in got["exact"]:
        if key not in base_exact:
            failures.append(f"EXACT {key}: missing from baseline "
                            "(run --update-baseline)")
    base_ratios = baseline.get("ratios", {})
    for key in got["ratios"]:
        if key not in base_ratios:
            failures.append(f"RATIO {key}: missing from baseline "
                            "(run --update-baseline)")
    for key, base_val in base_ratios.items():
        have = got["ratios"].get(key)
        if have is None:
            if _skip_key(key, ran):
                continue
            failures.append(f"RATIO {key}: not measured")
            continue
        tol, direction = _tolerance_for(key, baseline)
        if direction == "higher_better":
            bad = have < base_val * (1 - tol)
        else:
            bad = have > base_val * (1 + tol)
        if bad:
            failures.append(
                f"RATIO {key}: {have} vs baseline {base_val} "
                f"({direction}, tol {tol:.0%}) — REGRESSION")
    return failures


def _merge_kept(new: dict, old: dict, ran: dict) -> dict:
    """Baseline update for one of the exact/ratios dicts: keep every old
    key whose optional section this invocation did not run (including the
    ``*.mesh*`` keys when --sharded was absent) so a partial
    ``--update-baseline`` never silently drops another leg's baseline."""
    keep = {k: v for k, v in old.items() if _skip_key(k, ran)}
    return {**keep, **new}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_mining.json")
    ap.add_argument("--baseline", default="benchmarks/baseline.json")
    ap.add_argument("--update-baseline", action="store_true")
    ap.add_argument("--sharded", action="store_true",
                    help="also run the mesh-sharded gate section (needs "
                         "8 devices; CI sets XLA_FLAGS="
                         "--xla_force_host_platform_device_count=8)")
    ap.add_argument("--telemetry", action="store_true",
                    help="also run the telemetry parity section: registry-"
                         "derived stats must equal the legacy counters "
                         "bit-for-bit, with tracing on and off")
    ap.add_argument("--serving", action="store_true",
                    help="also run the concurrent-service section: cross-"
                         "request feed-pass sharing, steady/load retraces "
                         "and cache counters (exact) + qps/p99 vs a "
                         "sequential session (ratios); writes the loaded "
                         "service's Perfetto trace next to --out")
    ap.add_argument("--values", action="store_true",
                    help="also run the SVPU value-plane section: weighted "
                         "sum/max/min aggregates vs the host-f64 oracle "
                         "(exact), dispatch/feed parity vs the unweighted "
                         "twin, zero repeat retraces + the weighted-"
                         "overhead wall-clock ratio")
    args = ap.parse_args(argv)

    serving_trace = ""
    if args.serving:
        serving_trace = str(Path(args.out).with_name(
            Path(args.out).stem + "_serving_trace.json"))
    got = measure(sharded=args.sharded, telemetry=args.telemetry,
                  serving=args.serving, serving_trace=serving_trace,
                  values=args.values)
    Path(args.out).write_text(json.dumps(got, indent=2, sort_keys=True))
    print(f"[gate] wrote {args.out}")

    if args.update_baseline:
        ran = {p: any(k.startswith(p)
                      for d in (got["exact"], got["ratios"]) for k in d)
               for p in SECTION_PREFIXES}
        if not all(ran.values()) or not args.sharded:
            # keep the sections recorded by a previous --sharded /
            # --telemetry / --serving update instead of dropping them
            try:
                old = json.loads(Path(args.baseline).read_text())
            except (FileNotFoundError, json.JSONDecodeError):
                old = {}
            got = {**got,
                   "exact": _merge_kept(got["exact"],
                                        old.get("exact", {}), ran),
                   "ratios": _merge_kept(got["ratios"],
                                         old.get("ratios", {}), ran)}
        doc = {
            "_doc": ("CI perf-regression baseline (benchmarks/ci_gate.py). "
                     "'exact' must match bit-for-bit; 'ratios' fail when "
                     "worse than baseline by more than 'tolerances' "
                     "(fractional) in the 'directions' sense. Refresh with "
                     "--update-baseline and justify in the PR."),
            "exact": got["exact"],
            "ratios": got["ratios"],
            "tolerances": DEFAULT_TOLERANCES,
            "directions": DIRECTIONS,
        }
        Path(args.baseline).write_text(
            json.dumps(doc, indent=2, sort_keys=True) + "\n")
        print(f"[gate] baseline refreshed -> {args.baseline}")
        return 0

    baseline = json.loads(Path(args.baseline).read_text())
    failures = compare(got, baseline)
    for f in failures:
        print(f"[gate] {f}", flush=True)
    if failures:
        print(f"[gate] FAIL: {len(failures)} regression(s)")
        return 1
    print("[gate] PASS: counts/counters exact, ratios within tolerance")
    return 0


if __name__ == "__main__":
    sys.exit(main())
