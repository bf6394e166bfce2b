"""Smoke run of GNN mini-batch training on a TPU.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # partition-parallel path, 4 chips

One chip: GraphSAGE at Reddit's widths (602 input features, hidden 256,
41 classes, two layers, batch 1024, fanouts [5, 5]) on a 232,965-node
graph generated from the seed.

1. Reference check: on one fixed batch with the same initial parameters,
   the loss and gradients of the Pallas kernel path (``use_kernel=True``)
   must match the ``jax.ops`` path to ``REF_TOL``.
2. One epoch (227 steps) through ``repro.launch.train_gnn.main``, the
   launcher a user runs.  The loss must be finite and end lower than it
   started, and no program may compile after the first step.

``--four-chips`` runs only 3 steps of ``make_distributed_minibatch_step``
over four chips (hash partitioner, same widths) against the one-device
reference step on the same seed batches, as
``tests/distributed_train_check.py`` does on virtual devices.

Everything runs in this one process: a chip belongs to one process at a
time.  Any failure exits non-zero; without a TPU the script exits before
doing any work.  The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import re
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

TRAIN_ARGV = ["--minibatch", "--arch", "sage", "--use-kernel",
              "--nodes", "232965", "--feat-dim", "602", "--classes", "41",
              "--hidden", "256", "--batch", "1024", "--epochs", "1"]
FANOUTS = [5, 5]                 # train_gnn's mini-batch fanouts
DIST_STEPS = 3

# Both paths compute in float32 (matmuls at float32 precision, one-hot
# gathers at HIGHEST); they differ only in the order of the float32
# additions inside each segment sum, which moves results by a few ulp
# (~1e-6 relative).  A gather rounded to bfloat16 would show up at ~4e-3.
REF_TOL = 1e-4
# The partition-parallel step sums gradients across chips in another
# order than the one-device step; Adam turns that rounding into parameter
# differences far below one step's update (lr = 1e-2).
DIST_LOSS_TOL = 1e-5
DIST_PARAM_TOL = 1e-4


class _Tee(io.TextIOBase):
    """Write to the console and keep a copy for parsing."""

    def __init__(self, out):
        self.out = out
        self.buf = io.StringIO()

    def write(self, s):
        self.buf.write(s)
        return self.out.write(s)

    def flush(self):
        self.out.flush()


def _rel_diff(a, b) -> float:
    import numpy as np
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


def _max_tree_diff(a, b) -> float:
    import jax
    import numpy as np
    return max(float(np.max(np.abs(np.asarray(x) - np.asarray(y))))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def _model(args, use_kernel: bool):
    from repro.models.gnn.model import GNNConfig
    return GNNConfig(arch=args.arch, feat_dim=args.feat_dim,
                     hidden=args.hidden, num_classes=args.classes,
                     use_kernel=use_kernel)


def reference_check(argv) -> float:
    """Largest relative difference between the kernel and ``jax.ops``
    paths in the loss and every gradient, on one sampled batch."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.abstraction import DeviceGraph
    from repro.core.sampling import NeighborSampler
    from repro.launch.train_gnn import build_graph, parse_args
    from repro.models.gnn import model as GM

    args = parse_args(argv)
    g = build_graph(args)
    seeds = np.random.default_rng(args.seed).choice(
        g.num_nodes, args.batch, replace=False)
    mb = NeighborSampler(g, FANOUTS, seed=args.seed).sample(seeds)
    blocks = [DeviceGraph.from_block(b) for b in mb.blocks]
    src = mb.blocks[0].src_nodes
    x = jnp.asarray(np.where((src >= 0)[:, None],
                             g.features[np.maximum(src, 0)], 0.0))
    y = jnp.asarray(g.labels[seeds])
    params = GM.init_gnn(_model(args, False), jax.random.PRNGKey(args.seed))

    def loss_grads(use_kernel):
        cfg = _model(args, use_kernel)

        def loss(p, blocks, x, y):
            logits = GM.forward_blocks(cfg, p, blocks, x)
            return GM.nll_loss(logits, y)

        with jax.default_matmul_precision("float32"):
            return jax.jit(jax.value_and_grad(loss))(params, blocks, x, y)

    (lk, gk), (lr, gr) = loss_grads(True), loss_grads(False)
    diffs = {"loss": _rel_diff(lk, lr)}
    for i, (pk, pr) in enumerate(zip(gk, gr)):
        for name in pr:
            diffs[f"grad[{i}].{name}"] = _rel_diff(pk[name], pr[name])
    worst = max(diffs, key=diffs.get)
    print(f"reference check: loss kernel {float(lk):.6f} jax.ops "
          f"{float(lr):.6f}; largest relative difference "
          f"{diffs[worst]:.3e} ({worst}); tolerance {REF_TOL:g}")
    if not diffs[worst] <= REF_TOL:
        raise SystemExit(f"kernel path disagrees with jax.ops: {diffs}")
    return diffs[worst]


def train_run(argv) -> dict:
    """One epoch through the launcher; returns what the run showed."""
    import jax

    from repro.core import telemetry
    from repro.launch import train_gnn
    from repro.models.gnn import model as GM

    compiles = []                # (end time, seconds) per program, built
    hits = []                    # or loaded from the persistent cache

    def on_duration(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append((time.perf_counter(), secs))

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            hits.append(time.perf_counter())

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    telemetry.set_enabled(True)
    reg = telemetry.get_registry()
    tee = _Tee(sys.stdout)
    t0 = time.perf_counter()
    with contextlib.redirect_stdout(tee):
        last = train_gnn.main(argv)
    t_end = time.perf_counter()

    args = train_gnn.parse_args(argv)
    steps = [e for e in reg.tracer.events if e["name"] == "train.step"]
    first_end = steps[0]["ts"] + steps[0]["dur"]
    first = float(re.search(r"step 0 loss (\S+)", tee.buf.getvalue())[1])
    run = {
        "steps": len(steps),
        "first_loss": first,
        "last_loss": float(last),
        "first_step_s": steps[0]["dur"],
        "compile_s_first_step": sum(s for t, s in compiles
                                    if t0 <= t <= first_end),
        "programs": sum(1 for t, _ in compiles if t >= t0),
        "programs_from_cache": sum(1 for t in hits if t >= t0),
        "compiles_after_first_step": sum(1 for t, _ in compiles
                                         if t > first_end),
        "steps_per_s_after_first": (len(steps) - 1) / (t_end - first_end),
    }
    stats = jax.devices()[0].memory_stats() or {}
    run["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
    shapes = jax.eval_shape(lambda: GM.init_gnn(_model(args, True),
                                                jax.random.PRNGKey(0)))
    n_params = sum(a.size for a in jax.tree.leaves(shapes))
    print(f"model: {args.arch} {args.feat_dim}/{args.hidden}/"
          f"{args.classes}, {n_params} parameters, batch {args.batch}, "
          f"fanouts {FANOUTS}")
    for k, v in run.items():
        print(f"  {k}: {v}")
    want = args.epochs * (args.nodes // args.batch)
    if run["steps"] != want:
        raise SystemExit(f"ran {run['steps']} steps, expected {want}")
    if not (abs(first) < float("inf") and abs(run["last_loss"])
            < float("inf") and run["last_loss"] < first):
        raise SystemExit(f"loss went {first} -> {run['last_loss']}")
    if run["compiles_after_first_step"]:
        raise SystemExit("programs compiled after the first step")
    return run


def four_chip_check(argv, n_dev: int = 4) -> dict:
    """``DIST_STEPS`` partition-parallel steps over ``n_dev`` devices
    against the one-device reference step on the same seed batches."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.distributed import (DistributedMinibatchSampler, collate,
                                   device_blocks,
                                   make_distributed_minibatch_step)
    from repro.launch.train_gnn import build_graph, parse_args
    from repro.models.gnn import model as GM
    from repro.optim import AdamW

    args = parse_args(argv + ["--devices", str(n_dev)])
    g = build_graph(args)
    cfg = _model(args, args.use_kernel)
    opt = AdamW(lr=args.lr, weight_decay=0.0)
    params = GM.init_gnn(cfg, jax.random.PRNGKey(args.seed))
    dist = DistributedMinibatchSampler(
        g, n_dev, FANOUTS, args.batch, partitioner="hash",
        cache_policy=args.cache, cache_capacity=g.num_nodes // 10,
        seed=args.seed)
    ref = DistributedMinibatchSampler(g, 1, FANOUTS, args.batch,
                                      partitioner="hash",
                                      cache_policy="none", seed=args.seed)
    _, dstep = make_distributed_minibatch_step(cfg, opt, n_dev,
                                               dist.block_shapes())
    ref_step = jax.jit(GM.make_minibatch_train_step(cfg, opt))
    pd, od = params, opt.init(params)
    pr, orr = params, opt.init(params)
    rng = np.random.default_rng(args.seed)
    loss_diff = 0.0
    with jax.default_matmul_precision("float32"):
        for it in range(DIST_STEPS):
            seeds = rng.choice(g.num_nodes, args.batch, replace=False)
            pd, od, ld = dstep(pd, od, collate(dist.sample_global(seeds),
                                               dist.out_deg))
            rb = ref.sample_global(seeds)[0]
            pr, orr, lr = ref_step(
                pr, orr, device_blocks(rb, ref.out_deg),
                jnp.asarray(rb.x_in), jnp.asarray(rb.labels),
                jnp.asarray(rb.label_mask))
            d = _rel_diff(ld, lr)
            loss_diff = max(loss_diff, d)
            print(f"step {it}: loss {n_dev} chips {float(ld):.6f} one "
                  f"device {float(lr):.6f} (relative difference {d:.3e})")
    param_diff = _max_tree_diff(pd, pr)
    print(f"{n_dev}-chip vs one-device after {DIST_STEPS} steps: largest "
          f"loss difference {loss_diff:.3e} (tolerance {DIST_LOSS_TOL:g}), "
          f"largest parameter difference {param_diff:.3e} (tolerance "
          f"{DIST_PARAM_TOL:g})")
    if not (loss_diff <= DIST_LOSS_TOL and param_diff <= DIST_PARAM_TOL):
        raise SystemExit("partition-parallel step disagrees with the "
                         "one-device reference")
    return {"loss_diff": loss_diff, "param_diff": param_diff}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the partition-parallel step on 4 chips "
                         "against the one-device reference")
    opts = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    need = 4 if opts.four_chips else 1
    if devices[0].platform != "tpu" or len(devices) < need:
        print(f"chip_smoke: needs {need} TPU chip(s), JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 1

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache
    print(f"device: {devices[0].device_kind} x{len(devices)}; compile "
          f"cache {enable_compile_cache()}")
    if opts.four_chips:
        four_chip_check(TRAIN_ARGV, n_dev=4)
    else:
        reference_check(TRAIN_ARGV)
        train_run(TRAIN_ARGV)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
