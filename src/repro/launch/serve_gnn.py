"""Online GNN inference serving driver.

Serves per-node prediction requests against a synthetic (or named) graph
through the ``repro.serving`` stack: Poisson/Zipf workload → bucketed
micro-batching → fixed-shape neighbor sampling → historical-embedding +
feature caching → jitted forward.  Runs the same workload twice (no-cache
baseline, then the layered cache) and reports the traffic saved.

  PYTHONPATH=src python -m repro.launch.serve_gnn --nodes 512 \
      --requests 256 --arch sage
  PYTHONPATH=src python -m repro.launch.serve_gnn --dataset reddit-like \
      --requests 512 --cache degree --staleness 2

Replicated mode (``--replicas N`` or ``--autoscale``) serves through the
elastic :class:`repro.serving.router.ReplicaRouter` instead: Zipf traffic
spread over N replicas, optional queue-depth/p99 autoscaling, and rolling
weight hot-swap every K completions with per-response version tags::

  PYTHONPATH=src python -m repro.launch.serve_gnn --replicas 2 \
      --hot-swap-every 100 --requests 256
  PYTHONPATH=src python -m repro.launch.serve_gnn --replicas 1 --autoscale \
      --rate 8000 --requests 512 --router-policy least_queue
"""
from __future__ import annotations

import argparse
import copy
import sys


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=512)
    ap.add_argument("--classes", type=int, default=4)
    ap.add_argument("--feat-dim", type=int, default=32)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--arch", default="sage",
                    choices=["gcn", "sage", "gat", "gin", "ggnn"])
    ap.add_argument("--dataset", default="",
                    help="named dataset from repro.graph.datasets; "
                         "default: SBM sized by --nodes")
    ap.add_argument("--requests", type=int, default=256)
    ap.add_argument("--rate", type=float, default=2000.0,
                    help="offered load, requests/s (virtual clock)")
    ap.add_argument("--fanouts", type=int, nargs="+", default=[5, 5])
    ap.add_argument("--buckets", type=int, nargs="+", default=[1, 4, 16, 64])
    ap.add_argument("--max-wait-ms", type=float, default=2.0)
    ap.add_argument("--cache", default="degree",
                    choices=["none", "degree", "importance", "random"])
    ap.add_argument("--cache-frac", type=float, default=0.2,
                    help="fraction of nodes admitted to the caches")
    ap.add_argument("--staleness", type=int, default=0,
                    help="max staleness (version-clock ticks) served")
    ap.add_argument("--wire-codec", default="fp32",
                    choices=["fp32", "bf16", "int8"],
                    help="communication-plane wire codec "
                         "(repro.core.comm) for remote feature pulls "
                         "and cache-fill payloads; fp32 is bit-exact")
    ap.add_argument("--use-kernel", action="store_true",
                    help="Pallas segment-sum for the Gather step")
    ap.add_argument("--reorder", default="none",
                    choices=["none", "degree", "bfs", "rcm"],
                    help="locality-reorder the served graph (survey "
                         "§3.2.4); the sampler and caches operate on "
                         "the packed graph while request node ids map "
                         "in through the inverse permutation and "
                         "responses are reported in original ids")
    ap.add_argument("--replicas", type=int, default=1,
                    help="initial replica count; > 1 (or --autoscale) "
                         "serves through the elastic ReplicaRouter")
    ap.add_argument("--router-policy", default="least_queue",
                    choices=["round_robin", "least_queue"],
                    help="request dispatch policy across replicas")
    ap.add_argument("--private-cache", action="store_true",
                    help="one EmbeddingCache per replica instead of the "
                         "default fleet-shared cache")
    ap.add_argument("--autoscale", action="store_true",
                    help="enable the queue-depth/p99 autoscaling "
                         "controller (KEDA-style; scales replicas "
                         "within [--replicas, --max-replicas])")
    ap.add_argument("--max-replicas", type=int, default=8,
                    help="autoscaler upper bound on the fleet size")
    ap.add_argument("--hot-swap-every", type=int, default=0,
                    help="stage a rolling weight hot-swap every K "
                         "completions (0 = never); new weights are a "
                         "fresh init per version, every response is "
                         "tagged with the one version that served it")
    ap.add_argument("--update-stream", default="",
                    help="JSONL graph-update stream "
                         "(repro.core.updates.GraphUpdateLog format) "
                         "folded into the served graph mid-run: "
                         "incremental delta-frontier cache invalidation "
                         "instead of a cold restart; with --replicas the "
                         "router invalidates every replica")
    ap.add_argument("--update-every", type=int, default=0,
                    help="completions between update folds (0 = auto: "
                         "~4 folds across the run)")
    ap.add_argument("--ckpt-dir", default="",
                    help="write a crash-safe (params, version) "
                         "checkpoint here after the run; if it already "
                         "holds a complete step, resume weights from it")
    ap.add_argument("--train-epochs", type=int, default=0,
                    help="optionally pre-train the model full-graph")
    ap.add_argument("--metrics-out", default="",
                    help="enable telemetry and write the Prometheus "
                         "text-format exposition here on exit "
                         "(repro.core.telemetry)")
    ap.add_argument("--trace-out", default="",
                    help="enable telemetry and write the JSONL span "
                         "trace here on exit")
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


def main(argv=None):
    """Parse args, serve the workload, and (when asked) dump the
    telemetry plane on exit — metrics as Prometheus text, spans as JSONL
    (see docs/observability.md)."""
    args = parse_args(argv)
    from repro.core import telemetry
    if args.metrics_out or args.trace_out:
        telemetry.set_enabled(True)
    try:
        return run(args)
    finally:
        if args.metrics_out:
            telemetry.get_registry().write_prometheus(args.metrics_out)
            print(f"telemetry: metrics -> {args.metrics_out}")
        if args.trace_out:
            n = telemetry.get_registry().tracer.export_jsonl(args.trace_out)
            print(f"telemetry: {n} trace events -> {args.trace_out}")


def run(args):
    """The actual serving driver; ``main`` wraps it with the telemetry
    dump."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.graph import generators as G
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models.gnn import model as GM
    from repro.models.gnn.model import GNNConfig
    from repro.serving import GNNInferenceServer, poisson_workload

    enable_compile_cache()
    if args.dataset:
        from repro.graph.datasets import load
        g = load(args.dataset, seed=args.seed).graph
        feat_dim = g.features.shape[1]
    else:
        g = G.sbm(args.nodes, args.classes, p_in=0.9, p_out=0.02,
                  seed=args.seed)
        g = G.featurize(g, args.feat_dim, seed=args.seed, class_sep=1.5)
        feat_dim = args.feat_dim
    print(f"graph: {g.num_nodes} nodes, {g.num_edges} edges, "
          f"{g.num_classes} classes")

    perm = inv = None
    if args.reorder != "none":
        # the serving stack (sampler, halo, feature + embedding caches)
        # operates entirely on the packed graph; external node ids cross
        # the API boundary through inv (in) and perm (out)
        from repro.core.reordering import locality_report
        from repro.kernels import ops as kops
        g, perm, inv = g.reordered(args.reorder)
        rep = locality_report(g)
        e = g.edges()
        td = kops.record_tile_density(e[:, 0], e[:, 1], g.num_nodes)
        print(f"reorder={args.reorder}: gather stride "
              f"{rep['avg_gather_stride']:.1f}, reuse hit "
              f"{rep['reuse_hit_rate']:.2%}, active tiles "
              f"{td['active_tile_frac']:.2%}")

    cfg = GNNConfig(arch=args.arch, feat_dim=feat_dim, hidden=args.hidden,
                    num_classes=g.num_classes,
                    num_layers=len(args.fanouts),
                    use_kernel=args.use_kernel,
                    wire_codec=args.wire_codec)
    params = GM.init_gnn(cfg, jax.random.PRNGKey(args.seed))

    if args.train_epochs:
        from repro.core.abstraction import DeviceGraph
        from repro.optim import AdamW
        opt = AdamW(lr=1e-2, weight_decay=0.0)
        ostate = opt.init(params)
        dg = DeviceGraph.from_graph(g)
        x = jnp.asarray(g.features)
        y = jnp.asarray(g.labels)
        mask = jnp.ones_like(y, jnp.float32)
        step = jax.jit(GM.make_fullgraph_train_step(cfg, opt))
        for _ in range(args.train_epochs):
            params, ostate, loss = step(params, ostate, dg, x, y, mask)
        print(f"pre-trained {args.train_epochs} epochs, "
              f"loss {float(loss):.4f}")

    # the workload arrives in ORIGINAL node ids (clients know nothing of
    # the packing); ids map into the packed space here, at the boundary
    workload = poisson_workload(args.requests, np.arange(g.num_nodes),
                                args.rate, seed=args.seed + 1)
    if inv is not None:
        for r in workload:
            r.node_id = int(inv[r.node_id])

    def to_original_ids(wl):
        """Report completed responses in the clients' original ids."""
        if perm is not None:
            for r in wl:
                r.node_id = int(perm[r.node_id])
        return wl

    capacity = int(g.num_nodes * args.cache_frac)

    if args.replicas > 1 or args.autoscale:
        out = _run_replicated(args, g, cfg, params, workload, capacity,
                              _update_stream_kw(args, inv))
        to_original_ids(workload)
        return out

    def serve(policy: str) -> dict:
        srv = GNNInferenceServer(
            g, cfg, params, fanouts=args.fanouts, buckets=args.buckets,
            cache_policy=policy, cache_capacity=capacity,
            max_staleness=args.staleness,
            max_wait_s=args.max_wait_ms / 1e3, seed=args.seed)
        srv.warmup()
        # each serve pass folds a fresh copy of the stream into a fresh
        # copy of the graph, so baseline and cached runs stay comparable
        kw = _update_stream_kw(args, inv)
        if kw:
            srv.g = srv.sampler.g = copy.deepcopy(g)
            srv.cache.g = srv.cache.features.g = srv.g
            srv.sampler.apply_delta(np.zeros(0, np.int64))
        wl = copy.deepcopy(workload)
        srv.run(wl, **kw)
        to_original_ids(wl)
        out = srv.summary()
        out["update_seq"] = srv._update_seq
        return out

    base = serve("none")
    print(f"[no-cache ] {base['throughput_rps']:8.1f} req/s  "
          f"p50 {base['p50_ms']:6.2f} ms  p99 {base['p99_ms']:6.2f} ms  "
          f"feature bytes {base['feature_bytes'] / 2**20:.2f} MiB")

    if args.cache == "none":
        print("done (cache disabled)")
        return base

    res = serve(args.cache)
    saved = base["feature_bytes"] - res["feature_bytes"]
    print(f"[{args.cache:9s}] {res['throughput_rps']:8.1f} req/s  "
          f"p50 {res['p50_ms']:6.2f} ms  p99 {res['p99_ms']:6.2f} ms  "
          f"feature bytes {res['feature_bytes'] / 2**20:.2f} MiB")
    print(f"embedding hit rate {res['embedding_hit_ratio']:.2%}  "
          f"feature hit rate {res['feature_hit_ratio']:.2%}  "
          f"pad overhead {res['pad_overhead']:.2%}  "
          f"jit entries {res['jit_entries']}")
    print(f"wire codec {res['wire_codec']}: feature "
          f"{res['feature_bytes'] / 2**20:.2f} MiB + cache-fill "
          f"{res['fill_bytes'] / 2**20:.2f} MiB = "
          f"{res['wire_bytes'] / 2**20:.2f} MiB on the wire")
    print(f"bytes saved vs no-cache: {saved / 2**20:.2f} MiB "
          f"({saved / max(base['feature_bytes'], 1):.1%})")
    return res


def _update_stream_kw(args, inv=None) -> dict:
    """Build the ``run(update_log=, update_every=, update_chunk=)``
    kwargs for ``--update-stream``: default cadence folds after every
    quarter of the workload, spreading the stream across ~4 chunks so
    mutations actually interleave with traffic (an end-of-run fold would
    never exercise mid-run invalidation).  ``inv`` relabels an
    original-id stream into the packed id space under ``--reorder``."""
    if not args.update_stream:
        return {}
    from repro.core.updates import load_update_stream
    log = load_update_stream(args.update_stream)
    if inv is not None:
        log = log.relabel(inv)
    every = args.update_every or max(1, args.requests // 4)
    chunk = max(1, -(-log.last_seq // 4))          # ceil(last_seq / 4)
    print(f"update stream: {log.last_seq} events from "
          f"{args.update_stream}, folding {chunk} events every "
          f"{every} completions")
    return {"update_log": log, "update_every": every,
            "update_chunk": chunk}


def _run_replicated(args, g, cfg, params, workload, capacity, update_kw):
    """Serve through the elastic ReplicaRouter: N replicas, optional
    autoscaling, rolling hot-swap every K completions, crash-safe
    stop/resume via ``--ckpt-dir``."""
    import jax

    from repro.checkpoint import latest_step
    from repro.models.gnn import model as GM
    from repro.serving import AutoscalePolicy, ReplicaRouter, restore_params

    router = ReplicaRouter(
        g, cfg, params,
        n_replicas=args.replicas,
        policy=args.router_policy,
        shared_cache=not args.private_cache,
        cache_policy=args.cache,
        cache_capacity=capacity,
        max_staleness=args.staleness,
        fanouts=args.fanouts,
        buckets=args.buckets,
        max_wait_s=args.max_wait_ms / 1e3,
        seed=args.seed,
        autoscale=AutoscalePolicy(
            min_replicas=args.replicas,
            max_replicas=args.max_replicas) if args.autoscale else None)

    if args.ckpt_dir and latest_step(args.ckpt_dir) is not None:
        resumed, version = restore_params(args.ckpt_dir, params)
        print(f"resumed weights from {args.ckpt_dir} "
              f"(params version {version})")
        if version > 0:
            router.hot_swap(resumed, version=version)
        else:
            router.params = resumed
            for rep in router.replicas:
                rep.server.params = resumed

    def fresh_params(version: int):
        return GM.init_gnn(cfg, jax.random.PRNGKey(args.seed + version))

    stats = router.run(workload,
                       hot_swap_every=args.hot_swap_every,
                       new_params_fn=(fresh_params
                                      if args.hot_swap_every else None),
                       **update_kw)
    out = router.summary()
    if update_kw:
        print(f"graph updates folded through seq {router._update_seq}")
    mode = "autoscale" if args.autoscale else "fixed"
    print(f"[replicated] {args.router_policy}/{mode}  "
          f"{out['throughput_rps']:8.1f} req/s  "
          f"p50 {out['p50_ms']:6.2f} ms  p99 {out['p99_ms']:6.2f} ms")
    print(f"served {out['served']}  dropped {out['dropped']}  "
          f"torn batches {out['torn_batches']}  "
          f"hot swaps {out['hot_swaps']}  "
          f"replicas peak {stats.replicas_peak} "
          f"final {stats.replicas_final}  "
          f"scale events {out['scale_events']}")
    print(f"version counts {out['version_counts']}  "
          f"serving version {out['params_version']}")
    if "embedding_hit_ratio" in out:
        kind = "shared" if out["shared_cache"] else "private"
        print(f"{kind} cache hit rate {out['embedding_hit_ratio']:.2%}  "
              f"wire {out['wire_bytes'] / 2**20:.2f} MiB")
    if args.ckpt_dir:
        path = router.save(args.ckpt_dir)
        print(f"checkpoint -> {path}")
    return out


if __name__ == "__main__":
    sys.exit(0 if main() is not None else 1)
