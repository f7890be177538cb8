"""Serving driver: prefill + batched decode with the ring KV cache.

    PYTHONPATH=src python -m repro.launch.serve --arch mixtral-8x7b --smoke \
        --batch 4 --prompt-len 64 --gen 32

LeaFi retrieval serving (the similarity-search substrate) is a thin driver
over :mod:`repro.serving` with ``--arch leafi``: it cold-starts a
:class:`~repro.serving.session.ServingSession` from a checkpoint (or builds
a smoke-sized index and checkpoints it when ``--ckpt`` is given), pre-warms
the per-(bucket, k) programs, and drives a seeded Poisson open-loop trace of
heterogeneous requests (mixed per-query quality targets) through the
dynamic micro-batcher, reporting p50/p95/p99 latency, throughput, pruning
and per-target-group achieved recall.

    PYTHONPATH=src python -m repro.launch.serve --arch leafi --batch 32 \
        --requests 256 --rate 200 --targets 0.9,0.95,0.99 \
        --ckpt /tmp/leafi_ckpt

``--dist`` additionally routes a batch through the leaf-sharded shard_map
search (``core/distributed.py``) over every visible device, timing both
per-shard strategies — with the fixed-width compaction's survivor capacity
auto-tuned from the serving telemetry's observed survivor counts.

Filter-health observability: ``--shadow-rate R`` re-executes a
deterministic fraction R of requests through the exact scan off the
critical path (true recall + per-miss leaf/bound attribution);
``--health-dump PATH`` writes the windowed per-leaf scoreboard JSON
(``Telemetry.filters_needing_attention`` is the programmatic form); and
``--explain RID`` prints one request's full bound-attribution report.
"""
from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp

from .. import configs
from ..models import transformer


def _print_serve_report(report: dict, label: str = "") -> None:
    tag = f" [{label}]" if label else ""
    if report["n_requests"] == 0:
        # zero completions (e.g. an empty trace): throughput/makespan are
        # absent and every windowed stat is NaN — report that, don't crash
        print(f"served{tag} 0 requests in {report['n_batches']} batches "
              f"(no completions)")
        return
    print(f"served{tag} {report['n_requests']} requests in "
          f"{report['n_batches']} batches "
          f"(padding {report['padding_fraction']:.1%}): "
          f"{report['throughput_qps']:.1f} qps, latency "
          f"p50 {report['p50']*1e3:.1f}ms / p95 {report['p95']*1e3:.1f}ms "
          f"/ p99 {report['p99']*1e3:.1f}ms, pruning "
          f"{report['pruning_ratio']:.3f}")
    for t, rec in report["recall_by_target"].items():
        print(f"  target {t:.3f}: achieved recall {rec['recall']:.3f} "
              f"(n={rec['n']})")


def leafi_config(seed: int = 0):
    """The LeaFi build ``--arch leafi`` (and ``chip_smoke.py``) serves.

    DSTree with the paper's leaf capacity; a size threshold
    ``t_filter_over_t_series=20`` (th = 40 series), so every leaf of a
    median-split DSTree carries a filter — the paper's Deep-measured 279
    would need leaves above 558 series and select none at capacity 256.
    """
    from ..core import build, filter_training
    return build.LeaFiConfig(
        backbone="dstree", leaf_capacity=256, n_global=200, n_local=60,
        t_filter_over_t_series=20.0, seed=seed,
        train=filter_training.TrainConfig(epochs=40))


def serve_leafi(args) -> None:
    """Open-loop micro-batched serving over the LeaFi engine."""
    import numpy as np

    from ..core import build
    from ..core.summaries import znormalize
    from ..obs import SpanRecorder, export as obs_export, set_recorder
    from ..serving import MicroBatcher, ServingSession, poisson_trace

    recorder = None
    if args.trace_dump:
        # isolated capture: build + serve spans land here, not in the
        # process default recorder
        recorder = SpanRecorder()
        set_recorder(recorder)

    targets = tuple(float(t) for t in args.targets.split(","))
    # per-leaf health needs the engine's audit stream; shadow/health/explain
    # all imply it (results stay bitwise identical with it on)
    audit = bool(args.shadow_rate > 0 or args.health_dump
                 or args.explain is not None)
    session_kw = dict(strategy=args.strategy, warm_start=args.warm_start,
                      audit=audit, shadow_rate=args.shadow_rate,
                      shadow_seed=args.seed)
    if args.ckpt and os.path.exists(os.path.join(args.ckpt, "DONE")):
        t0 = time.perf_counter()
        session = ServingSession.from_checkpoint(args.ckpt, **session_kw)
        print(f"cold start from {args.ckpt}: "
              f"{time.perf_counter() - t0:.2f}s "
              f"({session.lfi.index.n_series} series, "
              f"{len(session.lfi.leaf_ids)} filters)")
    else:
        rng = np.random.default_rng(args.seed)
        n, m = 20_000, 128
        S = rng.standard_normal((n, m), dtype=np.float32).cumsum(axis=1)
        print(f"building LeaFi index over {n}x{m} series...")
        lfi = build.build_leafi(S, leafi_config())
        session = ServingSession(lfi, **session_kw)
        if args.ckpt:
            session.save(args.ckpt)
            print(f"checkpointed index to {args.ckpt} "
                  f"(next start is a cold start)")

    idx = session.lfi.index
    rng = np.random.default_rng(args.seed + 1)
    pool = znormalize(
        np.asarray(idx.series[rng.integers(0, idx.n_series, 256)])
        + 0.3 * rng.standard_normal((256, idx.length)).astype(np.float32))

    n_warm = session.warmup(max_batch=args.batch, ks=(args.k,),
                            queries=pool, targets=targets)
    print(f"warmed {n_warm} (bucket, k) programs "
          f"[strategy={args.strategy}]")

    trace = poisson_trace(pool, rate=args.rate, n_requests=args.requests,
                          targets=targets, ks=(args.k,), seed=args.seed)
    exact = session.search_exact(np.stack([r.query for r in trace]))
    oracle = {r.rid: float(exact.dists[i, 0])
              for i, r in enumerate(trace)}

    service_time = None
    if args.pipeline:
        # pipelined serving needs an injected virtual clock (the host can't
        # time overlapped execution): model per-batch cost from one timed
        # warm full-bucket search, scaled by bucket fill.
        q = pool[np.arange(args.batch) % len(pool)]
        t = np.asarray(targets)[np.arange(args.batch) % len(targets)]
        t0 = time.perf_counter()
        session._search_async(q, t, args.k).result()
        model_s = time.perf_counter() - t0
        service_time = lambda b: model_s * max(b.bucket / args.batch, 0.25)  # noqa: E731
        print(f"pipeline depth {args.pipeline}: service model "
              f"{model_s*1e3:.1f}ms/full batch")

    report = session.serve(
        trace, batcher=MicroBatcher(max_batch=args.batch,
                                    max_wait=args.max_wait_ms / 1e3),
        recall_oracle=oracle, service_time=service_time,
        pipeline=args.pipeline)
    _print_serve_report(report)

    if "shadow" in report:
        sh = report["shadow"]
        print(f"shadow audit: {sh['n_shadowed']} queries re-executed "
              f"exactly (rate {args.shadow_rate:g}), true recall "
              f"{sh['recall_mean']:.3f}, {len(sh['misses'])} lost true "
              f"neighbor(s)")
        for m in sh["misses"][:5]:
            print(f"  rid {m['rid']}: neighbor #{m['id']} at "
                  f"{m['dist']:.4f} lost to leaf {m['leaf']} "
                  f"({m['bound']} bound)")
    flagged = session.telemetry.filters_needing_attention()
    if audit and flagged:
        print(f"filters needing attention ({len(flagged)} leaves):")
        for r in flagged[:5]:
            print(f"  leaf {r.leaf}: {','.join(r.reasons)} "
                  f"(violation rate {r.violation_rate:.3f}, worst "
                  f"residual {r.resid_min:.3f}, shadow misses "
                  f"{r.shadow_misses})")

    if args.health_dump:
        import json
        with open(args.health_dump, "w") as fh:
            json.dump(session.telemetry.health.snapshot(), fh, indent=2,
                      default=float)
        print(f"health scoreboard dumped to {args.health_dump}")

    if args.explain is not None:
        from ..obs import explain as obs_explain
        from ..serving import explain_query
        match = [r for r in trace if r.rid == args.explain] or [trace[0]]
        r = match[0]
        ctx = explain_query(session, r.query, target=r.quality_target,
                            k=r.k, rid=r.rid)
        print(obs_explain.render_text(ctx))

    if args.dist:
        if args.k == 1:
            serve_leafi_dist_trace(session.lfi, trace, args, oracle)
        else:
            print("(--dist trace serving needs --k 1; the distributed "
                  "exchange reduces a single nn distance)")
        serve_leafi_distributed(session.lfi, pool[:args.batch],
                                session.telemetry)
        session_for_summary = session
    else:
        session_for_summary = session

    if args.summary:
        import json
        print("telemetry summary:")
        print(json.dumps(session_for_summary.telemetry.summary(), indent=2,
                         default=float))

    if args.metrics_dump:
        obs_export.write_metrics(args.metrics_dump,
                                 session.telemetry.registry)
        fmt = ("prometheus" if args.metrics_dump.endswith(".prom")
               else "jsonl")
        print(f"metrics dumped to {args.metrics_dump} ({fmt})")
    if args.trace_dump:
        set_recorder(None)
        obs_export.write_chrome_trace(args.trace_dump,
                                      spans=recorder.drain(),
                                      batch_log=report["batches"])
        print(f"chrome trace dumped to {args.trace_dump} "
              f"(open in https://ui.perfetto.dev)")


def serve_leafi_dist_trace(lfi, trace, args, oracle) -> None:
    """Serve the same open-loop trace through the shard_map executor.

    Shards the index over every visible device on a 1×D mesh and drives the
    identical micro-batched trace through a
    :class:`~repro.serving.session.DistributedExecutor` (per-query conformal
    offset rows through shard_map; pipelined when ``--pipeline``).
    """
    import numpy as np

    from ..core import distributed
    from ..serving import DistributedExecutor, MicroBatcher, ServingSession

    D = max(len(jax.devices()), 1)
    mesh = distributed.make_search_mesh(1, D)
    executor = DistributedExecutor(lfi, mesh, strategy=args.strategy)
    session = ServingSession(lfi, strategy=args.strategy,
                             warm_start=args.warm_start, executor=executor)
    targets = tuple(float(t) for t in args.targets.split(","))
    with mesh:
        session.warmup(max_batch=args.batch, ks=(1,), targets=targets)
        service_time = None
        if args.pipeline:
            q = np.asarray(lfi.index.series[:args.batch])
            t = np.asarray(targets)[np.arange(args.batch) % len(targets)]
            t0 = time.perf_counter()
            session._search_async(q, t, 1).result()
            model_s = time.perf_counter() - t0
            service_time = lambda b: model_s * max(b.bucket / args.batch, 0.25)  # noqa: E731
        report = session.serve(
            trace, batcher=MicroBatcher(max_batch=args.batch,
                                        max_wait=args.max_wait_ms / 1e3),
            recall_oracle=oracle, service_time=service_time,
            pipeline=args.pipeline)
    _print_serve_report(report, label=f"dist x{D}")


def serve_leafi_distributed(lfi, q, telemetry=None) -> None:
    """Route the same requests through the shard_map search (1-NN).

    Shards the index over every visible device on a 1×D mesh; run with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=D`` to smoke the
    multi-shard path off-TPU.  Compares both per-shard strategies — the
    masked scan and the fixed-width survivor compaction (the default, which
    skips non-survivor distance compute with fully static shapes).  When
    serving telemetry is available, the compaction's survivor capacity comes
    from its observed survivor-count percentile instead of the static P/8
    default (conservative: counts were observed on the unsharded leaf set).
    """
    import numpy as np

    from ..core import distributed, engine

    D = max(len(jax.devices()), 1)
    mesh = distributed.make_search_mesh(1, D)
    sharded = distributed.shard_leafi(lfi, mesh=mesh)
    P = sharded.leaf_size.shape[1]
    tuned = None
    if telemetry is not None and telemetry.survivors:
        tuned = telemetry.suggest_max_survivors(P)
        print(f"distributed serve: {D} shard(s), {P} leaf slots/shard, "
              f"max_survivors {tuned} (telemetry-tuned; static default "
              f"{engine.default_max_survivors(P)})")
    else:
        print(f"distributed serve: {D} shard(s), {P} leaf slots/shard")
    for strategy in ("scan", "compact"):
        run, *_ = distributed.make_distributed_search(
            mesh, sharded, strategy=strategy,
            max_survivors=tuned if strategy == "compact" else None)
        with mesh:
            nn, total = run(jnp.asarray(q))         # warmup / compile
            jax.block_until_ready(nn)
            t0 = time.perf_counter()
            nn, total = run(jnp.asarray(q))
            jax.block_until_ready(nn)
            dt = time.perf_counter() - t0
        print(f"serve[dist/{strategy:7s}] {q.shape[0]} queries 1-NN: "
              f"{dt*1e3:.1f}ms  total searched "
              f"{np.asarray(total).mean():.1f} leaves/query")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="codeqwen1.5-7b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--strategy", default="compact",
                    choices=("scan", "compact"),
                    help="engine execution plan for --arch leafi")
    ap.add_argument("--k", type=int, default=5,
                    help="neighbours per request (--arch leafi)")
    ap.add_argument("--requests", type=int, default=128,
                    help="open-loop trace length (--arch leafi)")
    ap.add_argument("--rate", type=float, default=200.0,
                    help="Poisson arrival rate, req/s (--arch leafi)")
    ap.add_argument("--targets", default="0.9,0.95,0.99",
                    help="comma-separated per-request quality targets")
    ap.add_argument("--max-wait-ms", type=float, default=20.0,
                    help="micro-batcher deadline-flush wait")
    ap.add_argument("--ckpt", default=None,
                    help="index checkpoint dir: loads if present, "
                         "else builds and saves (--arch leafi)")
    ap.add_argument("--dist", action="store_true",
                    help="also smoke the sharded (shard_map) search path "
                         "(--arch leafi only; with --k 1 the full trace is "
                         "re-served through the distributed executor; set "
                         "XLA_FLAGS=--xla_force_host_platform_device_count=N "
                         "for N shards off-TPU)")
    ap.add_argument("--pipeline", type=int, default=0,
                    help="pipelined serving depth (batches in flight; "
                         "0 = serial; --arch leafi)")
    ap.add_argument("--warm-start", action="store_true",
                    help="cross-batch bsf warm-starting (--arch leafi)")
    ap.add_argument("--summary", action="store_true",
                    help="print the session telemetry summary (rolling "
                         "percentiles incl. queue-wait/form/execute phases)")
    ap.add_argument("--metrics-dump", default=None, metavar="PATH",
                    help="dump the serving metrics registry on exit: "
                         "JSON-lines, or Prometheus text exposition when "
                         "PATH ends in .prom (--arch leafi)")
    ap.add_argument("--shadow-rate", type=float, default=0.0,
                    help="fraction of requests re-executed exactly off the "
                         "critical path for true-recall auditing "
                         "(deterministic per-rid sampling; --arch leafi)")
    ap.add_argument("--health-dump", default=None, metavar="PATH",
                    help="dump the per-leaf filter-health scoreboard "
                         "(windowed audit + shadow evidence) as JSON on "
                         "exit (--arch leafi; implies audited serving)")
    ap.add_argument("--explain", type=int, default=None, metavar="RID",
                    help="print a per-query explain report (bound "
                         "attribution, residuals, shadow-truth misses) for "
                         "one request id of the trace (--arch leafi)")
    ap.add_argument("--trace-dump", default=None, metavar="PATH",
                    help="dump a Chrome trace-event JSON of the serve run "
                         "(batch dispatch/in-flight/harvest lanes + host "
                         "spans; open in Perfetto) (--arch leafi)")
    args = ap.parse_args()
    from .compile_cache import enable_compile_cache
    enable_compile_cache()

    if args.arch == "leafi":
        serve_leafi(args)
        return

    cfg = (configs.get_smoke(args.arch) if args.smoke
           else configs.get_config(args.arch))
    if cfg.input_mode != "tokens":
        raise SystemExit("serve example drives token models; "
                         "see retrieval_serving.py for embedding backbones")
    total = args.prompt_len + args.gen
    cache_len = configs.decode_cache_len(cfg, total)
    params = transformer.init_params(cfg, jax.random.PRNGKey(args.seed))
    key = jax.random.PRNGKey(args.seed + 1)
    prompt = jax.random.randint(key, (args.batch, args.prompt_len), 0,
                                cfg.vocab)

    prefill = jax.jit(transformer.make_prefill_step(cfg, cache_len))
    decode = jax.jit(transformer.make_decode_step(cfg))

    t0 = time.perf_counter()
    logits, cache = prefill(params, {"tokens": prompt})
    logits.block_until_ready()
    t_prefill = time.perf_counter() - t0
    print(f"prefill {args.batch}x{args.prompt_len}: {t_prefill*1e3:.1f}ms")

    tokens = jnp.argmax(logits[:, -1], axis=-1)[:, None]
    out = [tokens]
    t0 = time.perf_counter()
    for i in range(args.gen - 1):
        pos = jnp.int32(args.prompt_len + i)
        logits, cache = decode(params, cache, {"tokens": tokens}, pos)
        tokens = jnp.argmax(logits[:, -1], axis=-1)[:, None]
        out.append(tokens)
    jax.block_until_ready(tokens)
    t_dec = time.perf_counter() - t0
    gen = jnp.concatenate(out, axis=1)
    print(f"decode {args.gen-1} steps: {t_dec*1e3:.1f}ms "
          f"({t_dec/(args.gen-1)*1e3:.1f}ms/tok/batch)")
    print("generated ids[0,:16]:", gen[0, :16].tolist())


if __name__ == "__main__":
    main()
