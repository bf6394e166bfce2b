"""Distributed GNN training driver — the paper-faithful entry point.

Full-graph mode distributes the graph over N (forced-host) devices with a
selectable partitioner and propagation/sync mode; ``--fullgraph`` runs the
staleness-bounded *asynchronous* full-graph path instead (versioned
per-layer ghost buffers, ``--staleness S`` age bound, ``--refresh-frac F``
budget); mini-batch mode runs a selectable sampler + caching policy —
single-device, or partition-parallel when ``--minibatch --devices N``
(repro.distributed: halo-cached remote fetches, double-buffered prefetch,
shard_map psum step).  ``--use-kernel`` routes every path's Gather step
through the differentiable fused Pallas aggregation kernels
(``repro.kernels``; interpret mode off-TPU, same numbers to <= 1e-5).
``--wire-codec {fp32,bf16,int8}`` selects the communication-plane wire
format (``repro.core.comm``) on the paths wired onto it — ghost
refreshes under ``--fullgraph``, remote feature rows under
``--minibatch``; ``fp32`` is bit-exact, ``int8`` cuts bytes/step ~4x
with sender-side error feedback.  The synchronous distributed
full-graph modes (``--mode pull/push/stale/hysync``) still move raw
fp32 and reject other codecs rather than misreport their traffic.

  PYTHONPATH=src python -m repro.launch.train_gnn --devices 8 \
      --partitioner ldg --mode pull --epochs 30 --use-kernel
  PYTHONPATH=src python -m repro.launch.train_gnn --fullgraph --devices 4 \
      --staleness 2 --refresh-frac 0.05 --epochs 30
  PYTHONPATH=src python -m repro.launch.train_gnn --minibatch \
      --sampler neighbor --cache degree --epochs 5
  PYTHONPATH=src python -m repro.launch.train_gnn --minibatch --devices 4 \
      --partitioner ldg --cache degree --epochs 5

See docs/architecture.md for the dataflow of all three paths.
"""
from __future__ import annotations

import argparse
import os
import sys
import time


# A dispatch returns before its device work ends, so timing one dispatch
# times host preparation; the interval between successive returns is the
# step period in steady state (n steps give n - 1 samples)
STEP_SECONDS_HELP = ("interval between successive step dispatch returns "
                     "(the step period in steady state)")


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=1)
    ap.add_argument("--nodes", type=int, default=512)
    ap.add_argument("--avg-degree", type=float, default=8.0)
    ap.add_argument("--classes", type=int, default=4)
    ap.add_argument("--feat-dim", type=int, default=32)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--arch", default="gcn",
                    choices=["gcn", "sage", "gat", "gin", "ggnn", "appnp"])
    ap.add_argument("--dataset", default="",
                    help="named dataset from repro.graph.datasets "
                         "(citeseer-like, pubmed-like, reddit-like, ...); "
                         "default: SBM sized by --nodes")
    ap.add_argument("--partitioner", default="hash",
                    choices=["hash", "ldg", "fennel", "auto"])
    ap.add_argument("--mode", default="pull",
                    choices=["pull", "push", "stale", "hysync"])
    ap.add_argument("--staleness", type=int, default=4,
                    help="staleness bound S: full-epoch snapshot period "
                         "for --mode stale/hysync, per-row ghost age bound "
                         "for --fullgraph")
    ap.add_argument("--epochs", type=int, default=30)
    ap.add_argument("--lr", type=float, default=1e-2)
    ap.add_argument("--minibatch", action="store_true")
    ap.add_argument("--fullgraph", action="store_true",
                    help="staleness-bounded asynchronous full-graph "
                         "training (repro.distributed.async_train): "
                         "per-layer versioned ghost buffers, --staleness S "
                         "age bound, --refresh-frac budget")
    ap.add_argument("--refresh-frac", type=float, default=0.0,
                    help="extra per-step ghost refresh budget as a "
                         "fraction of the ghost set (--fullgraph only)")
    ap.add_argument("--update-stream", default="",
                    help="continual training: a JSONL graph-update "
                         "stream (repro.core.updates.GraphUpdateLog "
                         "format) folded into the training graph "
                         "between epochs — incremental re-shard + "
                         "delta-frontier ghost invalidation, no cold "
                         "restart (--fullgraph only)")
    ap.add_argument("--updates-per-epoch", type=int, default=0,
                    help="events folded between consecutive epochs "
                         "(0 = spread the whole stream evenly across "
                         "the run)")
    ap.add_argument("--sampler", default="neighbor",
                    choices=["neighbor", "importance", "fastgcn", "ladies",
                             "cluster", "saint"])
    ap.add_argument("--cache", default="degree",
                    choices=["none", "degree", "importance", "random"])
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--reorder", default="none",
                    choices=["none", "degree", "bfs", "rcm"],
                    help="locality-reorder the graph before anything "
                         "else touches it (survey §3.2.4: degree = "
                         "ZIPPER, bfs = GNNAdvisor/Rabbit-order "
                         "stand-in, rcm = reverse Cuthill-McKee). "
                         "Partitioners, samplers, halo layouts and "
                         "caches all operate on the packed graph; "
                         "training losses/accuracy are "
                         "relabeling-invariant")
    ap.add_argument("--use-kernel", action="store_true",
                    help="run every aggregation (the Gather hot spot) "
                         "through the differentiable fused Pallas "
                         "kernels (interpret mode off-TPU)")
    ap.add_argument("--wire-codec", default="fp32",
                    choices=["fp32", "bf16", "int8"],
                    help="communication-plane wire codec "
                         "(repro.core.comm) for every remote payload: "
                         "ghost refreshes (--fullgraph) and remote "
                         "feature fetches (--minibatch).  fp32 is "
                         "bit-exact; int8 cuts bytes ~4x with "
                         "error-feedback residuals")
    ap.add_argument("--metrics-out", default="",
                    help="enable telemetry and write the Prometheus "
                         "text-format exposition here on exit "
                         "(repro.core.telemetry)")
    ap.add_argument("--trace-out", default="",
                    help="enable telemetry and write the JSONL span "
                         "trace here on exit")
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


def resolve_edge_cut(g, n_dev: int, method: str) -> str:
    """EASE-style auto selection, constrained to the edge-cut family both
    distributed paths (full-graph shards, mini-batch partitions) require."""
    if method == "auto":
        from repro.core.partitioning import select_partitioner
        method = select_partitioner(g, n_dev)
        if method == "hdrf":
            method = "ldg"
        print(f"auto-selected partitioner: {method}")
    return method


def build_graph(args):
    """The training graph: the named ``--dataset``, or an SBM with
    ``--nodes`` nodes, ``--classes`` communities and ``--feat-dim``
    features, generated from ``--seed``."""
    from repro.graph import generators as G
    if args.dataset:
        from repro.graph.datasets import load
        return load(args.dataset, seed=args.seed).graph
    g = G.sbm(args.nodes, args.classes, p_in=0.9, p_out=0.02,
              seed=args.seed)
    return G.featurize(g, args.feat_dim, seed=args.seed, class_sep=1.5)


def main(argv=None):
    """Parse args, run the selected training path, and (when asked) dump
    the telemetry plane on exit — metrics as Prometheus text, spans as
    JSONL (see docs/observability.md)."""
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
    """The actual training driver (all four paths); ``main`` wraps it
    with the telemetry dump."""
    if args.wire_codec != "fp32" and not (args.minibatch or args.fullgraph):
        # the synchronous full-graph modes (pull/push/stale/hysync) and
        # the single-device full-batch trainer are not on the
        # communication plane; silently ignoring the flag would make
        # their reported traffic a lie
        raise SystemExit("--wire-codec is wired through --fullgraph and "
                         "--minibatch; the synchronous full-graph modes "
                         "move raw fp32")
    if args.update_stream and not args.fullgraph:
        # continual training folds deltas through the async trainer's
        # versioned ghost state; the other paths have no incremental
        # invalidation surface and would silently train a frozen graph
        raise SystemExit("--update-stream requires --fullgraph "
                         "(continual training folds deltas through the "
                         "async trainer's versioned ghost buffers)")
    if args.devices > 1 and "--xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices} "
            + os.environ.get("XLA_FLAGS", ""))

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import caching as CA
    from repro.core import propagation as PR
    from repro.core import sampling as SA
    from repro.core import telemetry
    from repro.core.abstraction import DeviceGraph
    from repro.core.scheduling import PipelinedLoader
    from repro.core.sync import HaloCache, SyncPolicy
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models.gnn import model as GM
    from repro.models.gnn.model import GNNConfig
    from repro.optim import AdamW

    if args.devices > jax.device_count():
        raise SystemExit(f"--devices {args.devices}: only "
                         f"{jax.device_count()} {jax.default_backend()} "
                         f"device(s) visible")
    n_dev = args.devices
    enable_compile_cache()
    rng = np.random.default_rng(args.seed)
    g = build_graph(args)
    feat_dim = g.features.shape[1]
    print(f"graph: {g.num_nodes} nodes, {g.num_edges} edges, "
          f"{g.num_classes} classes, {feat_dim} features; "
          f"devices={jax.device_count()}")

    reorder_inv = None
    if args.reorder != "none":
        # pack BEFORE partitioning/sampling/halo so every downstream
        # structure keys off the packed id space; node ids round-trip
        # through (perm, inv) at the API boundary — training itself is
        # relabeling-invariant, so perm is only needed for reporting
        from repro.core.reordering import locality_report
        from repro.kernels import ops as kops
        g, perm, reorder_inv = g.reordered(args.reorder)
        rep = locality_report(g)
        e = g.edges()
        td = kops.record_tile_density(e[:, 0], e[:, 1], g.num_nodes)
        print(f"reorder={args.reorder}: gather stride "
              f"{rep['avg_gather_stride']:.1f}, reuse hit "
              f"{rep['reuse_hit_rate']:.2%}, active tiles "
              f"{td['active_tile_frac']:.2%}")

    cfg = GNNConfig(arch=args.arch, feat_dim=feat_dim,
                    hidden=args.hidden, num_classes=g.num_classes,
                    use_kernel=args.use_kernel,
                    wire_codec=args.wire_codec)
    params = GM.init_gnn(cfg, jax.random.PRNGKey(args.seed))
    opt = AdamW(lr=args.lr, weight_decay=0.0)
    ostate = opt.init(params)

    # ---- staleness-bounded asynchronous full-graph path --------------
    if args.fullgraph:
        from repro.distributed import AsyncFullGraphTrainer

        if args.arch != "gcn":
            raise SystemExit("--fullgraph implements GCN (like the "
                             "synchronous distributed full-graph mode)")
        method = resolve_edge_cut(g, n_dev, args.partitioner)
        trainer = AsyncFullGraphTrainer(
            g, cfg, opt, n_dev, partitioner=method,
            staleness=max(args.staleness, 0),
            refresh_frac=args.refresh_frac)
        if args.update_stream:
            import math as _math

            from repro.core.updates import load_update_stream
            log = load_update_stream(args.update_stream)
            if reorder_inv is not None:
                # the stream speaks original ids; the trainer's graph is
                # packed — relabel once at the boundary (folding
                # commutes with relabeling)
                log = log.relabel(reorder_inv)
            per = args.updates_per_epoch or _math.ceil(
                log.last_seq / max(args.epochs - 1, 1))
            print(f"update stream: {log.last_seq} events from "
                  f"{args.update_stream}, folding {per}/epoch")
            loss = float("nan")
            for epoch in range(args.epochs):
                params, ostate, loss = trainer.run(params, ostate, 1)
                if trainer._update_seq < log.last_seq:
                    upto = min(trainer._update_seq + per, log.last_seq)
                    fold = trainer.fold_updates(log, upto)
                    print(f"epoch {epoch:3d} loss {float(loss):.4f} "
                          f"folded {fold['events']} events "
                          f"(touched {fold['touched_nodes']} nodes, "
                          f"invalidated {fold['invalidated_rows']} "
                          f"ghost rows)")
        else:
            params, ostate, loss = trainer.run(params, ostate, args.epochs,
                                               log_every=5)
        st = trainer.stats()
        print(f"final accuracy {trainer.accuracy(params):.3f}")
        print(f"ghost rows {st['ghost_rows']}; wire codec "
              f"{st['wire_codec']}; cross-partition "
              f"{st['bytes_per_step'] / 1024:.1f} KiB/step vs "
              f"{st['sync_bytes_per_step'] / 1024:.1f} KiB/step "
              f"synchronous ({st['comm_savings']:.0%} saved); "
              f"{st['mean_step_s'] * 1e3:.1f} ms/step")
        return float(loss)

    if not args.minibatch and (args.arch != "gcn" or args.devices <= 1):
        # generic single-device full-batch trainer (any architecture);
        # the multi-device shard_map path below is GCN-specific
        from repro.core.abstraction import DeviceGraph
        dg = DeviceGraph.from_graph(g)
        x = jnp.asarray(g.features)
        y = jnp.asarray(g.labels)
        mask = jnp.ones_like(y, jnp.float32)
        step = jax.jit(GM.make_fullgraph_train_step(cfg, opt))
        for epoch in range(args.epochs):
            params, ostate, loss = step(params, ostate, dg, x, y, mask)
            if epoch % 5 == 0 or epoch == args.epochs - 1:
                print(f"epoch {epoch:3d} loss {float(loss):.4f}")
        acc = float(GM.accuracy(GM.forward_full(cfg, params, dg, x), y))
        print(f"final accuracy {acc:.3f}")
        return float(loss)

    if not args.minibatch:
        from repro.core.sync import HysyncController

        if args.arch != "gcn":
            raise SystemExit("distributed full-graph mode implements GCN; "
                             "use --minibatch for other architectures")
        method = resolve_edge_cut(g, n_dev, args.partitioner)
        sg = PR.shard_graph(g, n_dev, method=method)

        if args.mode == "push":
            push_arrays = PR.push_layout(sg, g)
            mesh, step = PR.make_distributed_gcn_step(
                opt, n_dev, mode="push", use_kernel=args.use_kernel)
            for epoch in range(args.epochs):
                params, ostate, loss = step(params, ostate, sg,
                                            push_arrays=push_arrays)
                if epoch % 5 == 0 or epoch == args.epochs - 1:
                    print(f"epoch {epoch:3d} loss {float(loss):.4f}")
            return float(loss)

        stale_like = args.mode in ("stale", "hysync")
        mesh, step = PR.make_distributed_gcn_step(
            opt, n_dev, mode="stale" if stale_like else "pull",
            use_kernel=args.use_kernel)
        hysync = HysyncController(stale_s=args.staleness) \
            if args.mode == "hysync" else None
        policy = SyncPolicy(mode="stale" if stale_like else "bsp",
                            staleness=args.staleness)
        halo = HaloCache(sg.x)
        for epoch in range(args.epochs):
            if hysync is not None:
                policy.staleness = hysync.staleness()
            cache_val = halo.maybe_refresh(policy, epoch, sg.x)
            params, ostate, loss = step(params, ostate, sg,
                                        halo_cache=cache_val)
            if hysync is not None:
                mode_now = hysync.observe(epoch, float(loss))
            if epoch % 5 == 0 or epoch == args.epochs - 1:
                extra = f" mode={hysync.mode}" if hysync else ""
                print(f"epoch {epoch:3d} loss {float(loss):.4f}{extra}")
        if args.mode == "stale":
            print(f"halo-exchange savings vs BSP: "
                  f"{halo.comm_savings():.0%}")
        if hysync is not None and hysync.switch_step is not None:
            print(f"hysync switched stale->bsp at epoch "
                  f"{hysync.switch_step}; savings "
                  f"{halo.comm_savings():.0%}")
        return float(loss)

    # ---- distributed mini-batch path (partition-parallel) ------------
    if args.devices > 1:
        from repro.distributed import (DistributedMinibatchSampler,
                                       HostPrefetcher, collate,
                                       make_distributed_minibatch_step)

        if args.sampler not in ("neighbor",):
            raise SystemExit("distributed mini-batch uses the padded "
                             "neighbor sampler (--sampler neighbor)")
        method = resolve_edge_cut(g, n_dev, args.partitioner)
        dsampler = DistributedMinibatchSampler(
            g, n_dev, [5, 5], args.batch, partitioner=method,
            cache_policy=args.cache, cache_capacity=g.num_nodes // 10,
            wire_codec=args.wire_codec, seed=args.seed)
        mesh, dstep = make_distributed_minibatch_step(
            cfg, opt, n_dev, dsampler.block_shapes())

        def make_dist_batch():
            seeds = rng.choice(g.num_nodes, args.batch, replace=False)
            return collate(dsampler.sample_global(seeds), dsampler.out_deg)

        prefetch = HostPrefetcher(make_dist_batch)
        steps_per_epoch = max(1, g.num_nodes // args.batch)
        loss = None
        m_step = telemetry.histogram(
            "train_step_seconds", STEP_SECONDS_HELP, mode="minibatch_dist")
        last = None
        for epoch in range(args.epochs):
            for _ in range(steps_per_epoch):
                arrays = next(prefetch)
                with telemetry.span("train.step", mode="minibatch_dist"):
                    params, ostate, loss = dstep(params, ostate, arrays)
                now = time.perf_counter()
                if last is not None:
                    m_step.observe(now - last)
                last = now
            # monitoring only: the ratio also covers the 1-2 batches the
            # prefetcher sampled ahead; exact byte totals come after close
            st = dsampler.stats()
            print(f"epoch {epoch:3d} loss {float(loss):.4f} "
                  f"halo_hit {st['halo_hit_ratio']:.2%}")
        prefetch.close()
        st = dsampler.stats()
        xpart_mib = st["cross_partition_bytes"] / 2**20
        print(f"cross-partition traffic {xpart_mib:.1f} MiB "
              f"(wire codec {st['wire_codec']}) over "
              f"{prefetch.produced} sampled batches "
              f"({args.epochs * steps_per_epoch} trained); halo_hit "
              f"{st['halo_hit_ratio']:.2%}; ghost fraction "
              f"{st['ghost_fraction']:.2f}; prefetch overlap "
              f"{prefetch.overlap_ratio():.0%}")
        return float(loss)

    # ---- mini-batch path ---------------------------------------------
    if args.sampler == "neighbor":
        sampler = SA.NeighborSampler(g, [5, 5], seed=args.seed)
    elif args.sampler == "importance":
        sampler = SA.ImportanceSampler(g, [5, 5], seed=args.seed)
    elif args.sampler in ("fastgcn", "ladies"):
        sampler = SA.LayerWiseSampler(g, [128, 128],
                                      dependent=args.sampler == "ladies",
                                      seed=args.seed)
    else:
        sampler = None

    cache_ids = CA.CACHE_POLICIES[args.cache](g, g.num_nodes // 10)
    store = CA.FeatureStore(g, cache_ids, codec=args.wire_codec)
    step = jax.jit(GM.make_minibatch_train_step(cfg, opt))

    def make_batch():
        seeds = rng.choice(g.num_nodes, args.batch, replace=False)
        mb = sampler.sample(seeds)
        return mb, seeds

    loader = PipelinedLoader(make_batch, depth=4, n_workers=2)
    steps_per_epoch = max(1, g.num_nodes // args.batch)
    loss = None
    m_step = telemetry.histogram(
        "train_step_seconds", STEP_SECONDS_HELP, mode="minibatch_single")
    last = None
    for epoch in range(args.epochs):
        for i in range(steps_per_epoch):
            mb, seeds = next(loader)
            t0 = time.perf_counter()
            with telemetry.span("train.step", mode="minibatch_single"):
                blocks = [DeviceGraph.from_block(b) for b in mb.blocks]
                # input rows travel the communication plane: cache misses
                # are byte-accounted and arrive wire-decoded (zero rows at
                # pads — pad slots never aggregate, training is unaffected)
                src = mb.blocks[0].src_nodes
                x_in = jnp.asarray(store.fetch_masked(src, src >= 0))
                y = jnp.asarray(g.labels[seeds])
                params, ostate, loss = step(params, ostate, blocks, x_in,
                                            y, jnp.ones_like(y, jnp.float32))
            now = time.perf_counter()
            if last is not None:
                m_step.observe(now - last)
            last = now
            if epoch == 0 and i == 0:
                print(f"step 0 loss {float(loss):.4f} "
                      f"({time.perf_counter() - t0:.1f} s, compile "
                      f"included)")
        print(f"epoch {epoch:3d} loss {float(loss):.4f} "
              f"cache_hit {store.hit_ratio:.2%} "
              f"fetched {store.transferred_bytes / 2**20:.1f} MiB")
    loader.close()
    return float(loss)


if __name__ == "__main__":
    sys.exit(0 if main() is not None else 1)
