"""Serving driver: batched greedy decoding against the KV/state caches,
or a concurrent graph-mining service (``repro.serving``).

  PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-3b --tokens 32
  PYTHONPATH=src python -m repro.launch.serve --mine email-eu-core --rounds 4
  PYTHONPATH=src python -m repro.launch.serve --mine email-eu-core \\
      --qps 50 --clients 4 --timeout-ms 2000

``--mine`` is a thin driver over ``serving.MiningService``: the app mix
(T/TC/TT/4C + the 4-motif batch) is submitted as CONCURRENT requests and
each round's tick merges them into shared forest schedules across
requests (fused feed passes < sum of the requests' independent
schedules). Round mode is deterministic — steady-state rounds must be
bit-identical with 0 retraces; ``--qps`` switches to the threaded load
generator and reports p50/p99/qps. ``--shards N`` adds a mesh-sharded
worker class serving the heavy motif batch, mixed with the unsharded
default class in one pool.

Observability (repro.obs): ``--session-stats`` appends the service's
Prometheus-style metrics snapshot (the scrape-endpoint text a real server
would expose); ``--trace out.json`` span-traces the service's ticks and
writes the Chrome-trace/Perfetto JSON on exit.
"""
from __future__ import annotations

import argparse
import time


def serve_mining(args) -> dict | None:
    """Serve the app mix through one ``MiningService``.

    Round mode (default): each round submits the mix as concurrent
    requests and ticks once — counts must repeat bit-identically and
    steady-state rounds must retrace nothing; returns the counts by app
    label. ``--qps`` mode drives the threaded ``LoadGenerator`` instead
    (returns None)."""
    from repro.graph import get_dataset
    from repro.graph.datasets import dataset_stats
    from repro.mining import FOUR_MOTIF_SHAPES, MinerConfig
    from repro.obs import Telemetry
    from repro.serving import LoadGenerator, MiningService, WorkerSpec

    if args.rounds < 1:
        raise SystemExit("[serve] --rounds must be >= 1")
    g = get_dataset(args.mine, scale=args.scale)
    print(f"[serve] mining {args.mine} x{args.scale}: {dataset_stats(g)}")
    telemetry = Telemetry(enabled=bool(args.trace))
    # worker pool: an unsharded default class; --shards N adds a
    # mesh-sharded class that serves the heavy motif batch
    specs = [WorkerSpec("default", MinerConfig.from_args(args, mesh=None))]
    bulk = "default"
    if args.shards > 1:
        specs.append(WorkerSpec("bulk", MinerConfig.from_args(args)))
        bulk = "bulk"
    svc = MiningService(
        g, workers=tuple(specs), telemetry=telemetry, cache_results=False,
        timeout_s=(args.timeout_ms / 1e3) if args.timeout_ms else None)
    for spec in specs:
        w = svc.pool.worker(spec.traffic_class)
        if w.mesh is not None:
            print(f"[serve] worker {spec.traffic_class!r}: mesh "
                  f"{dict(w.mesh.shape)}")
    # the request mix: four single-pattern requests + the 4-motif batch,
    # heterogeneous on purpose — the tick merges them across requests
    motif_names = list(FOUR_MOTIF_SHAPES)
    requests = [("triangle",), ("three-chain",), ("tailed-triangle",),
                ("4-clique",), tuple(motif_names)]
    classes = ["default"] * 4 + [bulk]
    labels = ["T", "TC", "TT", "4C"] + motif_names
    queries_per_round = len(requests)

    if args.qps:
        first = None
        lg = LoadGenerator(
            svc, list(zip(requests, classes)), requests=args.requests,
            clients=args.clients, qps=args.qps,
            timeout_s=(args.timeout_ms / 1e3) if args.timeout_ms else None)
        res = lg.run()
        fp = res["feed_passes"]
        print(f"[serve] load: {res['completed']}/{res['requests']} done "
              f"({res['rejected']} rejected, {res['timeouts']} timed out) "
              f"in {res['wall_s']:.2f}s = {res['qps']:.1f} queries/s")
        print(f"[serve] latency: p50 {res['p50_s'] * 1e3:.1f}ms, "
              f"p99 {res['p99_s'] * 1e3:.1f}ms")
        print(f"[serve] sharing: {fp['fused']} fused feed passes vs "
              f"{fp['independent']} independent (cross-request batching)")
    else:
        first = None
        warm_retraces = steady = 0.0
        fp_round = None
        for r in range(args.rounds):
            before = svc.stats["retraces"]
            t0 = time.perf_counter()
            handles = [svc.submit(qs, traffic_class=tc)
                       for qs, tc in zip(requests, classes)]
            tick = svc.tick()
            flat = [v for h in handles for v in h.result(0)]
            res = dict(zip(labels, flat))
            dt = time.perf_counter() - t0
            retraces = svc.stats["retraces"] - before
            fp_round = tick["feed_passes"]
            if first is None:
                first, warm_retraces = res, retraces
            else:
                assert res == first, (res, first)
                assert retraces == 0, \
                    "steady-state round rebuilt an executable"
                steady += dt
            print(f"[serve] round {r}: {dt:.3f}s, "
                  f"{tick['requests']} requests merged, {retraces} retraces"
                  + ("  (warm-up: schedules + traces)" if r == 0 else ""))
        assert fp_round["fused"] < fp_round["independent"], fp_round
        print(f"[serve] sharing: {fp_round['fused']} fused feed passes vs "
              f"{fp_round['independent']} independent per tick "
              f"(cross-request batching)")
        if args.rounds > 1:
            per = steady / (args.rounds - 1)
            print(f"[serve] steady state: {per:.3f}s/round = "
                  f"{queries_per_round / max(per, 1e-9):.1f} queries/s, "
                  f"0 retraces (resident sessions + executable caches; "
                  f"warm-up traced {warm_retraces})")
        print(f"[serve] counts sample: T={first['T']} 4C={first['4C']}")
    st = svc.stats
    print(f"[serve] service: {st['service_requests']} requests "
          f"({st['service_queries']} queries) over {st['service_ticks']} "
          f"ticks, workers {sorted(st['workers'])}, "
          f"{st['retraces']} traces total")
    if args.trace:
        path = svc.write_trace(args.trace)
        print(f"[serve] trace: "
              f"{sum(1 for _ in telemetry.tracer.spans())} spans -> {path}")
    if args.session_stats:
        print("[serve] metrics:")
        print(svc.prometheus_text(), end="")
    return first


def main(argv=None):
    from repro.launch.cli import add_graph_args, add_service_args, \
        add_session_args
    from repro.launch.compile_cache import enable_compile_cache

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--max-len", type=int, default=128)
    add_graph_args(ap, dataset_flag="--mine", default="",
                   help="serve the mining app mix through a MiningService "
                        "on this dataset instead of LLM decoding")
    ap.add_argument("--rounds", type=int, default=3,
                    help="with --mine: deterministic serving rounds")
    add_session_args(ap)
    add_service_args(ap)
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.mine:
        return serve_mining(args)

    import jax
    import jax.numpy as jnp

    from repro.configs import ARCH_NAMES, get_arch
    from repro.distributed.sharding import DEFAULT_RULES, mesh_context
    from repro.launch.mesh import make_host_mesh
    from repro.models.transformer import Model

    if args.arch not in ARCH_NAMES:
        ap.error(f"--arch must be one of {ARCH_NAMES}")

    spec = get_arch(args.arch)
    cfg = spec.smoke_config
    model = Model(cfg)
    mesh = make_host_mesh()
    params, _ = model.init(jax.random.PRNGKey(0))
    caches, _ = model.init_cache(args.batch, args.max_len)
    if cfg.first_dense:
        caches["dense"] = model.init_dense_cache(args.batch, args.max_len)[0]
    enc = encp = None
    if cfg.encoder_layers:
        frames = jnp.zeros((args.batch, 16, cfg.d_model), jnp.float32)
        with mesh_context(mesh, DEFAULT_RULES):
            enc, encp = model._encode(params, {"frames": frames})

    @jax.jit
    def step(params, tok, pos, caches):
        with mesh_context(mesh, DEFAULT_RULES):
            if enc is not None:
                return model.decode_step(params, tok, pos, caches, enc, encp)
            return model.decode_step(params, tok, pos, caches)

    tok = jnp.zeros((args.batch, 1), jnp.int32)
    out = [tok]
    t0 = time.perf_counter()
    for i in range(args.tokens):
        logits, caches = step(params, tok, jnp.int32(i), caches)
        tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)[..., 0][:, None]
        out.append(tok)
    dt = time.perf_counter() - t0
    seqs = jnp.concatenate(out, axis=1)
    print(f"[serve] {args.arch}: {args.batch}x{args.tokens} tokens in "
          f"{dt:.2f}s = {args.batch*args.tokens/dt:.1f} tok/s")
    print("[serve] sample:", seqs[0, :16].tolist())


if __name__ == "__main__":
    main()
