"""Partition-parallel mini-batch training path: the program's
``DistributedMinibatchSampler``, ``collate`` and
``make_distributed_minibatch_step`` over the cell's chips, one partition
a chip, as ``launch/train_gnn.py --minibatch --devices N`` drives them.

The mix names ``partitions`` (the cell's chips), the ``partitioner`` and
``batch``, the seeds of each partition a step.  The halo cache of each
partition holds ``feature_cache.fraction`` of the graph's nodes, chosen
by ``feature_cache.policy`` among its ghosts.

Per step, on the main thread: wait for the next sampled step from the
program's ``PipelinedLoader`` (``loader_wait``); upload it, each
partition's share to its chip (``fetch``); wait for the step two back
(``device_wait``); dispatch (``dispatch``).  The loader's workers sample
every partition of a step and fetch its input rows through the
partition's feature store (``sample``), then stack the partitions'
batches with ``collate``, as ``train_gnn``'s prefetch thread does.

The batch order is the harness's own: in epoch ``e`` partition ``p``
takes ``batch`` seeds a step from a permutation of the training nodes it
owns drawn from ``(seed, e, p)``; worker ``w`` of ``W`` samples steps
``w, w + W, ...``.  The program's sampler picks a node's neighbours as a
function of the node alone, so every step is the same in every run of a
seed.
"""
from __future__ import annotations

import collections
import threading
import time

import numpy as np

from chipbench import compare, graphs
from chipbench.paths import minibatch as single
from chipbench.references import common

# the per-chip share of the step, compiled for one chip by
# ``chipbench/tests/test_cell_compile.py``: one partition's blocks forward,
# backward and the update, without the gradient psum
STEP_MAKER = single.STEP_MAKER
step_args = single.step_args


class Feed:
    """The ``sample_fn`` of the loader (see the module docstring)."""

    def __init__(self, sampler, pools: list, batch: int, seed: int,
                 n_workers: int, spans):
        from repro.distributed import collate
        self.collate = collate
        self.sampler, self.pools, self.batch = sampler, pools, batch
        self.seed = seed % (1 << 64)
        self.n_workers, self.spans = n_workers, spans
        self.per_epoch = min(len(p) for p in pools) // batch
        self.lock = threading.Lock()
        self.local = threading.local()
        self.workers_seen = 0
        self.perms = {}

    def seeds_of(self, i: int) -> list:
        epoch, k = divmod(i, self.per_epoch)
        with self.lock:
            if epoch not in self.perms:
                self.perms[epoch] = [
                    np.random.default_rng([self.seed, epoch, p]).permutation(
                        len(pool)) for p, pool in enumerate(self.pools)]
            perms = self.perms[epoch]
        return [pool[perm[k * self.batch:(k + 1) * self.batch]]
                for pool, perm in zip(self.pools, perms)]

    def __call__(self):
        loc = self.local
        if not hasattr(loc, "next"):
            with self.lock:
                loc.next = self.workers_seen
                self.workers_seen += 1
        i = loc.next
        loc.next += self.n_workers
        seeds = self.seeds_of(i)
        with self.spans("sample"):
            batches = [self.sampler.sample_partition(p, s)
                       for p, s in enumerate(seeds)]
        return batches, self.collate(batches, self.sampler.out_deg)


class Session:
    """The program's partition-parallel training objects, built once per
    process."""

    def __init__(self, ctx):
        from jax.sharding import NamedSharding, PartitionSpec
        from repro.distributed import (DistributedMinibatchSampler,
                                       make_distributed_minibatch_step)
        from repro.models.gnn.model import GNNConfig
        from repro.optim import AdamW

        self.ctx = ctx
        cfg, mix = ctx.config, ctx.mix
        self.g = ctx.program_graph()
        n = mix["partitions"]
        cache = cfg["feature_cache"]
        with ctx.spans("setup.sampler"):
            self.sampler = DistributedMinibatchSampler(
                self.g, n, mix["fanouts"], mix["batch"],
                partitioner=mix["partitioner"], cache_policy=cache["policy"],
                cache_capacity=int(self.g.num_nodes * cache["fraction"]),
                wire_codec=cfg["wire_codec"])
        m = cfg["model"]
        self.model = GNNConfig(arch=m["arch"], feat_dim=m["in_features"],
                               hidden=m["hidden"], num_classes=m["classes"],
                               num_layers=m["layers"],
                               use_kernel=cfg["use_kernel"],
                               wire_codec=cfg["wire_codec"])
        self.opt = AdamW(**cfg["optimizer"])
        mesh, self.step = make_distributed_minibatch_step(
            self.model, self.opt, n, self.sampler.block_shapes())
        self.shard = NamedSharding(mesh, PartitionSpec(mesh.axis_names[0]))
        self.loader = None
        self.inflight = collections.deque()
        self.arg_specs = None

    def _one_step(self):
        import jax
        sp = self.ctx.spans
        with sp("loader_wait"):
            batches, arrays = next(self.loader)
        with sp("fetch"):
            arrays = jax.device_put(arrays, self.shard)
        if len(self.inflight) >= self.ctx.mix["max_inflight_steps"]:
            with sp("device_wait"):
                self.inflight.popleft().block_until_ready()
        args = (self.params, self.ostate, arrays)
        with sp("dispatch"):
            self.params, self.ostate, loss = self.step(*args)
        self.inflight.append(loss)
        self.arg_specs = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=a.sharding), args)
        # (real destinations, sources, edges) of each layer, summed over
        # the partitions
        counts = np.sum([[(np.sum(b.dst_nodes >= 0), np.sum(b.src_nodes >= 0),
                           np.sum(b.edge_mask)) for b in pb.blocks]
                         for pb in batches], axis=0)
        return loss, [tuple(map(int, c)) for c in counts], batches

    def start(self, seed: int) -> dict:
        """Fresh weights and batch order from ``seed``; runs the first steps
        through the window's own call and feed and keeps them for the
        check."""
        import jax
        from repro.core.scheduling import PipelinedLoader
        ctx, mix = self.ctx, self.ctx.mix
        self.params = ctx.init_params(seed)
        self.ostate = jax.jit(self.opt.init)(self.params)
        params0 = jax.tree.map(np.asarray, self.params)
        train = ctx.graph_arrays()["train_mask"]
        owner = self.sampler.layout.owner
        pools = [np.flatnonzero(train & (owner == p))
                 for p in range(self.sampler.n_parts)]
        self.feed = Feed(self.sampler, pools, mix["batch"], seed,
                         mix["loader_workers"], ctx.spans)
        self.loader = PipelinedLoader(self.feed, depth=mix["loader_depth"],
                                      n_workers=mix["loader_workers"])
        losses, steps, first_m = [], [], None
        for k in range(mix["first_steps"]):
            loss, _, batches = self._one_step()
            losses.append(loss)
            steps.append(batches)
            if k == 0:
                first_m = jax.tree.map(np.asarray, self.ostate["m"])
        jax.block_until_ready((self.params, self.ostate))
        b1 = ctx.config["optimizer"]["b1"]
        return {"losses": [float(l) for l in losses],
                "first_grad": jax.tree.map(lambda m: m / (1 - b1), first_m),
                "params0": params0,
                "params_after": jax.tree.map(np.asarray, self.params),
                "steps": steps}

    def warm(self):
        """As the single-chip mini-batch path's: untimed steps until one
        finds the loader's queue empty, at most ``warm_steps``."""
        for _ in range(self.ctx.mix["warm_steps"]):
            drained = self.loader.q.empty()
            self._one_step()
            if drained:
                break
        import jax
        jax.block_until_ready((self.params, self.ostate))

    def _stores(self, attr: str) -> int:
        return sum(getattr(s, attr) for s in self.sampler.stores)

    def window(self, seconds: float) -> dict:
        import jax
        sp = self.ctx.spans
        bytes0 = self._stores("transferred_bytes")
        hits0, misses0 = self._stores("hits"), self._stores("misses")
        losses, counts = [], []
        t0 = time.perf_counter()
        with sp("window"):
            while True:
                loss, c, _ = self._one_step()
                losses.append(loss)
                counts.append(c)
                if time.perf_counter() - t0 >= seconds:
                    break
            with sp("block"):
                jax.block_until_ready((self.params, self.ostate))
        t1 = time.perf_counter()
        self.inflight.clear()
        steps = len(losses)
        failed = sum(1 for l in losses if not np.isfinite(float(l)))
        seeds = self.ctx.mix["batch"] * self.sampler.n_parts
        return {"t0": t0, "t1": t1, "steps": steps, "failed": failed,
                "end_to_end": {"train_nodes_per_s": steps * seeds / (t1 - t0)},
                "counts": counts,
                "edge_lengths": [e for _, _, e in
                                 self.sampler.block_shapes()],
                "op_names": self._op_names(),
                "counters": {
                    "fetch_bytes": self._stores("transferred_bytes") - bytes0,
                    "cache_hits": self._stores("hits") - hits0,
                    "cache_misses": self._stores("misses") - misses0}}

    def _op_names(self):
        """A function that gives ``{module: {instruction: op_name}}`` of the
        step as it ran, lowered and compiled again at the shapes and
        shardings of its last call; ``None`` where the program's step
        cannot be lowered (a plain function)."""
        if not hasattr(self.step, "lower") or self.arg_specs is None:
            return None
        step, specs = self.step, self.arg_specs

        def op_names():
            import re
            from chipbench import program_trace as P
            text = step.lower(*specs).compile().as_text()
            head = re.match(r"HloModule\s+([\w.\-]+)", text)
            return {P.module_key(head.group(1) if head else ""):
                    P.hlo_op_names(text)}

        return op_names

    def stop(self):
        if self.loader is not None:
            self.loader.close()
            self.loader = None
        self.inflight.clear()

    def close(self):
        self.stop()
        self.params = self.ostate = self.step = None


# ---------------------------------------------------------------------------
# the check
# ---------------------------------------------------------------------------

def reference_batches(arrays: dict, first: dict) -> list:
    """Each first step for the reference: every partition's batch as the
    single-chip path gives it, and its share of the global seed count."""
    out = []
    for batches in first["steps"]:
        parts = single.reference_batches(
            arrays, {"batches": [(b.seeds, b, None) for b in batches]})
        counts = np.array([len(b.seeds) for b in batches], np.float32)
        out.append({"parts": parts, "share": counts / counts.sum()})
    return out


def reference_run(ctx, first: dict, precision: str) -> dict:
    """The reference over the union of the partitions' blocks: the loss is
    the sum over partitions of their seeds' losses over the global seed
    count, each partition's mean weighted by its share of the seeds."""
    def loss(params, batch, prec):
        return sum(w * ctx.reference.loss(params, part, prec)
                   for w, part in zip(batch["share"], batch["parts"]))

    losses, grad, after = common.train(
        loss, first["params0"], reference_batches(ctx.graph_arrays(), first),
        ctx.config["optimizer"], precision)
    return {"losses": losses, "first_grad": grad,
            "params0": first["params0"], "params_after": after}


def structure_numbers(ctx, first: dict) -> dict:
    """The single-chip path's checks of every partition's sampled blocks
    and fetched rows."""
    arrays = ctx.graph_arrays()
    if "keys" not in ctx.memo:
        n = len(arrays["row_ptr"]) - 1
        ctx.memo["keys"] = graphs.directed_keys(arrays)
        ctx.memo["in_deg"] = np.bincount(arrays["col_idx"], minlength=n)
    blocks_bad = rows_bad = 0
    for batches in first["steps"]:
        for b in batches:
            blocks_bad += single.block_faults(
                arrays, ctx.memo["keys"], ctx.memo["in_deg"], b.seeds, b,
                ctx.mix["fanouts"])
            rows_bad += single.row_faults(arrays, b.blocks[0].src_nodes,
                                          b.x_in)
    return {"blocks_bad": blocks_bad, "rows_bad": rows_bad}


def check(ctx, first: dict) -> dict:
    """The numbers that decide ``correct``: the partitions' sampled blocks
    and fetched rows against the graph, and the first steps against the
    reference over the union of the partitions' blocks."""
    import sys
    numbers = structure_numbers(ctx, first)
    more, notes = compare.training_numbers(
        first, reference_run(ctx, first, "highest"))
    for line in notes:
        print(line, file=sys.stderr)
    numbers.update(more)
    return numbers
