"""Mini-batch training path: the program's sampler, feature store and
mini-batch step, driven as ``launch/train_gnn.py --minibatch`` drives them.

Per step, on the main thread: wait for the next sampled batch from the
program's ``PipelinedLoader`` (``loader_wait``); fetch its input rows from
the ``FeatureStore`` and upload the blocks (``fetch``); wait for the step two
back to finish, so that no more than ``max_inflight_steps`` are queued on
the device (``device_wait``); dispatch ``make_minibatch_train_step``
(``dispatch``).  The loader's workers sample (``sample``).

The batch order is the harness's own: epoch ``e`` is a permutation of the
configuration's training nodes drawn from ``(seed, e)``, cut into batches of
``batch`` seeds.  Worker ``w`` of
``W`` samples batches ``w, w + W, ...`` with a copy of the program's
``NeighborSampler`` whose generator is drawn from ``(seed, w)``, so every
batch is the same in every run of a seed; only the order in which the
workers deliver them varies.
"""
from __future__ import annotations

import collections
import copy
import threading
import time

import numpy as np

from chipbench import compare, graphs
from chipbench.references import common


def _seed_words(seed: int) -> int:
    return seed % (1 << 64)


class Feed:
    """The ``sample_fn`` of the loader (see the module docstring)."""

    def __init__(self, sampler, pool: np.ndarray, batch: int, seed: int,
                 n_workers: int, spans):
        self.pool, self.batch, self.seed = pool, batch, _seed_words(seed)
        self.n_workers, self.spans = n_workers, spans
        self.per_epoch = len(pool) // batch
        self.samplers = []
        for w in range(n_workers):
            s = copy.copy(sampler)
            s.rng = np.random.default_rng([self.seed, 1, w])
            self.samplers.append(s)
        self.lock = threading.Lock()
        self.local = threading.local()
        self.workers_seen = 0
        self.perms = {}

    def seeds_of(self, i: int) -> np.ndarray:
        epoch, k = divmod(i, self.per_epoch)
        with self.lock:
            if epoch not in self.perms:
                self.perms[epoch] = np.random.default_rng(
                    [self.seed, 0, epoch]).permutation(len(self.pool))
            perm = self.perms[epoch]
        return self.pool[perm[k * self.batch:(k + 1) * self.batch]]

    def __call__(self):
        loc = self.local
        if not hasattr(loc, "worker"):
            with self.lock:
                loc.worker = self.workers_seen
                self.workers_seen += 1
            loc.next = loc.worker
        i = loc.next
        loc.next += self.n_workers
        seeds = self.seeds_of(i)
        with self.spans("sample"):
            mb = self.samplers[loc.worker].sample(seeds)
        return i, seeds, mb


STEP_MAKER = "make_minibatch_train_step"


def block_shapes(mix: dict) -> list:
    """``(n_dst, n_src, n_edges)`` of each block, innermost first: the
    sampler keeps padded ids, so a batch of ``B`` seeds always yields
    ``B * prod(1 + f)`` source rows."""
    shapes, n_dst = [], mix["batch"]
    for f in reversed(mix["fanouts"]):
        shapes.append((n_dst, n_dst * (1 + f), n_dst * f))
        n_dst *= 1 + f
    return shapes[::-1]


def step_args(cfg: dict, mix: dict, spec) -> tuple:
    """The step's arguments after the weights and optimizer state, as
    ``spec(shape, dtype)`` makes them, at the cell's sizes."""
    import jax.numpy as jnp
    from repro.core.abstraction import DeviceGraph
    blocks = [DeviceGraph(spec((e,), jnp.int32), spec((e,), jnp.int32),
                          spec((e,), jnp.bool_), s, d, spec((d,), jnp.float32),
                          spec((s,), jnp.float32))
              for d, s, e in block_shapes(mix)]
    n_in = block_shapes(mix)[0][1]
    return (blocks, spec((n_in, cfg["model"]["in_features"]), jnp.float32),
            spec((mix["batch"],), jnp.int32),
            spec((mix["batch"],), jnp.float32))


class Session:
    """The program's mini-batch training objects, built once per process."""

    def __init__(self, ctx):
        import jax
        import jax.numpy as jnp
        from repro.core.caching import CACHE_POLICIES, FeatureStore
        from repro.core.sampling import NeighborSampler
        from repro.models.gnn import model as GM
        from repro.models.gnn.model import GNNConfig
        from repro.optim import AdamW

        self.ctx = ctx
        cfg, mix = ctx.config, ctx.mix
        self.g = ctx.program_graph()
        with ctx.spans("setup.sampler"):
            self.sampler = NeighborSampler(self.g, mix["fanouts"], seed=0)
        cache = cfg["feature_cache"]
        with ctx.spans("setup.store"):
            ids = CACHE_POLICIES[cache["policy"]](
                self.g, int(self.g.num_nodes * cache["fraction"]))
            self.store = FeatureStore(self.g, ids, codec=cfg["wire_codec"])
        m = cfg["model"]
        self.model = GNNConfig(arch=m["arch"], feat_dim=m["in_features"],
                               hidden=m["hidden"], num_classes=m["classes"],
                               num_layers=m["layers"],
                               use_kernel=cfg["use_kernel"],
                               wire_codec=cfg["wire_codec"])
        self.opt = AdamW(**cfg["optimizer"])
        self.step = jax.jit(GM.make_minibatch_train_step(self.model,
                                                         self.opt))
        self.label_mask = jnp.ones((mix["batch"],), jnp.float32)
        self.loader = None
        self.inflight = collections.deque()

    def _one_step(self):
        import jax.numpy as jnp
        from repro.core.abstraction import DeviceGraph
        sp = self.ctx.spans
        with sp("loader_wait"):
            i, seeds, mb = next(self.loader)
        with sp("fetch"):
            blocks = [DeviceGraph.from_block(b) for b in mb.blocks]
            src = mb.blocks[0].src_nodes
            rows = self.store.fetch_masked(src, src >= 0)
            x = jnp.asarray(rows)
            y = jnp.asarray(self.g.labels[seeds])
        if len(self.inflight) >= self.ctx.mix["max_inflight_steps"]:
            with sp("device_wait"):
                self.inflight.popleft().block_until_ready()
        with sp("dispatch"):
            self.params, self.ostate, loss = self.step(
                self.params, self.ostate, blocks, x, y, self.label_mask)
        self.inflight.append(loss)
        counts = [(int(np.sum(b.dst_nodes >= 0)), int(np.sum(b.src_nodes >= 0)),
                   int(np.sum(b.edge_mask))) for b in mb.blocks]
        return loss, counts, (seeds, mb, rows)

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
        pool = np.flatnonzero(ctx.graph_arrays()["train_mask"])
        self.feed = Feed(self.sampler, pool, mix["batch"], seed,
                         mix["loader_workers"], ctx.spans)
        self.loader = PipelinedLoader(self.feed, depth=mix["loader_depth"],
                                      n_workers=mix["loader_workers"])
        losses, batches, first_m = [], [], None
        for k in range(mix["first_steps"]):
            loss, _, kept = self._one_step()
            losses.append(loss)
            batches.append(kept)
            if k == 0:
                first_m = jax.tree.map(np.asarray, self.ostate["m"])
        jax.block_until_ready((self.params, self.ostate))
        b1 = ctx.config["optimizer"]["b1"]
        return {"losses": [float(l) for l in losses],
                "first_grad": jax.tree.map(lambda m: m / (1 - b1), first_m),
                "params0": params0,
                "params_after": jax.tree.map(np.asarray, self.params),
                "batches": batches}

    def warm(self):
        """Untimed steps through the window's own call and feed until one
        finds the loader's queue empty, at most ``warm_steps``: the window
        then opens on the loader's steady state, and not on a queue of
        batches sampled before it."""
        import jax
        for _ in range(self.ctx.mix["warm_steps"]):
            drained = self.loader.q.empty()
            self._one_step()
            if drained:
                break
        jax.block_until_ready((self.params, self.ostate))

    def window(self, seconds: float) -> dict:
        import jax
        sp = self.ctx.spans
        bytes0 = self.store.transferred_bytes
        hits0, misses0 = self.store.hits, self.store.misses
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
        batch = self.ctx.mix["batch"]
        return {"t0": t0, "t1": t1, "steps": steps, "failed": failed,
                "end_to_end": {"train_nodes_per_s": steps * batch / (t1 - t0)},
                "counts": counts,
                "edge_lengths": [e for _, _, e in block_shapes(self.ctx.mix)],
                "counters": {
                    "fetch_bytes": self.store.transferred_bytes - bytes0,
                    "cache_hits": self.store.hits - hits0,
                    "cache_misses": self.store.misses - misses0}}

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

def block_faults(arrays: dict, keys: np.ndarray, in_deg: np.ndarray,
                 seeds: np.ndarray, mb, fanouts) -> int:
    """Violations of what the sampled blocks must be: the seeds on top;
    each layer's destinations the next layer's sources and the prefix of
    its own sources; unique sources; every real edge an edge of the graph
    into its destination; every destination with exactly ``min(in-degree,
    fanout)`` distinct neighbours."""
    n = len(arrays["row_ptr"]) - 1
    blocks = mb.blocks
    bad = int(not np.array_equal(blocks[-1].dst_nodes, seeds))
    for l, (b, f) in enumerate(zip(blocks, fanouts)):
        src, dst = np.asarray(b.src_nodes), np.asarray(b.dst_nodes)
        if l + 1 < len(blocks):
            bad += int(not np.array_equal(dst, blocks[l + 1].src_nodes))
        bad += int(not np.array_equal(src[:len(dst)], dst))
        real = src[src >= 0]
        bad += len(real) - len(np.unique(real)) + int(np.sum(real >= n))
        m = np.asarray(b.edge_mask, bool)
        es, ed = np.asarray(b.edge_src)[m], np.asarray(b.edge_dst)[m]
        inside = (es < len(src)) & (ed < len(dst))
        bad += int(np.sum(~inside))
        es, ed = es[inside], ed[inside]
        s, d = src[es], dst[ed]
        ok = (s >= 0) & (d >= 0)
        bad += int(np.sum(~ok))
        s, d, ed = s[ok], d[ok], ed[ok]
        q = s.astype(np.int64) * n + d
        pos = np.minimum(np.searchsorted(keys, q), len(keys) - 1)
        bad += int(np.sum(keys[pos] != q))
        bad += len(q) - len(np.unique(ed.astype(np.int64) * n + s))
        got = np.bincount(ed, minlength=len(dst))
        valid = dst >= 0
        want = np.minimum(in_deg[np.maximum(dst, 0)], f)
        bad += int(np.sum(got[valid] != want[valid]))
        bad += int(np.sum(got[~valid]))
    return bad


def row_faults(arrays: dict, src: np.ndarray, rows: np.ndarray) -> int:
    """Fetched rows that differ from the graph's features (zero rows at
    padded slots)."""
    want = np.where((src >= 0)[:, None],
                    arrays["features"][np.maximum(src, 0)], 0.0)
    return int(np.sum(np.any(rows != want, axis=1)))


def reference_batches(arrays: dict, first: dict) -> list:
    """The first steps' batches for the reference: the sampled structure,
    and input rows and labels read from the graph itself."""
    out = []
    for seeds, mb, _ in first["batches"]:
        src = mb.blocks[0].src_nodes
        out.append({
            "blocks": [{"src": np.asarray(b.edge_src, np.int32),
                        "dst": np.asarray(b.edge_dst, np.int32),
                        "mask": np.asarray(b.edge_mask, bool),
                        "dst_rows": np.zeros(len(b.dst_nodes), np.int8)}
                       for b in mb.blocks],
            "x": np.where((src >= 0)[:, None],
                          arrays["features"][np.maximum(src, 0)],
                          np.float32(0.0)).astype(np.float32),
            "labels": arrays["labels"][seeds].astype(np.int32),
            "label_mask": np.ones(len(seeds), np.float32)})
    return out


def reference_run(ctx, first: dict, precision: str) -> dict:
    arrays = ctx.graph_arrays()
    losses, grad, after = common.train(
        ctx.reference.loss, first["params0"],
        reference_batches(arrays, first), ctx.config["optimizer"], precision)
    return {"losses": losses, "first_grad": grad,
            "params0": first["params0"], "params_after": after}


def structure_numbers(ctx, first: dict) -> dict:
    arrays = ctx.graph_arrays()
    if "keys" not in ctx.memo:
        n = len(arrays["row_ptr"]) - 1
        ctx.memo["keys"] = graphs.directed_keys(arrays)
        ctx.memo["in_deg"] = np.bincount(arrays["col_idx"], minlength=n)
    keys, in_deg = ctx.memo["keys"], ctx.memo["in_deg"]
    fanouts = ctx.mix["fanouts"]
    blocks_bad = rows_bad = 0
    for seeds, mb, rows in first["batches"]:
        blocks_bad += block_faults(arrays, keys, in_deg, seeds, mb, fanouts)
        rows_bad += row_faults(arrays, mb.blocks[0].src_nodes, rows)
    return {"blocks_bad": blocks_bad, "rows_bad": rows_bad}


def check(ctx, first: dict) -> dict:
    """The numbers that decide ``correct``: the sampled blocks and fetched
    rows against the graph, and the first steps against the reference."""
    import sys
    numbers = structure_numbers(ctx, first)
    ref = reference_run(ctx, first, "highest")
    more, notes = compare.training_numbers(first, ref)
    for line in notes:
        print(line, file=sys.stderr)
    numbers.update(more)
    return numbers
