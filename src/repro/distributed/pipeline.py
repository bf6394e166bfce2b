"""Distributed mini-batch training pipeline: collate → prefetch → shard_map.

Three pieces (survey §3.2.5–§3.2.8 applied to the mini-batch path):

* :func:`collate` stacks each partition's fixed-shape
  :class:`~repro.distributed.sampler.PartitionBatch` into arrays with a
  leading partition axis — the layout ``shard_map`` shards over mesh axis
  ``"g"`` (one partition per device, same axis name as the full-graph
  path in :mod:`repro.core.propagation`).
* :class:`HostPrefetcher` double-buffers host-side work: while the jitted
  step consumes batch *t* on device, a worker thread samples and
  feature-fetches batch *t+1* (DistDGL's sampler processes / AGL's
  pipelined stages).  Built on
  :class:`repro.core.scheduling.PipelinedLoader`.
* :func:`make_distributed_minibatch_step` builds the SPMD step: each
  device runs the block forward over its partition's batch, losses are
  combined as psum(sum)/psum(count) and gradients are psum'd before a
  replicated optimizer update — bitwise-faithful to the single-device
  reference mean over the same global seed set.
"""
from __future__ import annotations

import time
from typing import Callable, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import telemetry
from repro.core.abstraction import DeviceGraph
from repro.core.comm import resolve_codec
from repro.core.propagation import AXIS
from repro.core.scheduling import PipelinedLoader
from repro.distributed.sampler import PartitionBatch
from repro.models.gnn import model as GM
from repro.models.gnn.model import GNNConfig


# ---------------------------------------------------------------------------
# collation: per-partition batches -> partition-major arrays
# ---------------------------------------------------------------------------

def collate(batches: List[PartitionBatch], out_deg: np.ndarray) -> dict:
    """Stack P fixed-shape partition batches into shard_map inputs.

    Returns per-layer tuples (leading dim P shards over ``"g"``):
      es/ed/em: (P, E_l) edge indices + mask;  sdeg: (P, S_l) global src
      out-degree (GCN normalization);  x: (P, S0, F);  y/w: (P, B).
    """
    L = len(batches[0].blocks)
    es = tuple(np.stack([b.blocks[l].edge_src for b in batches])
               .astype(np.int32) for l in range(L))
    ed = tuple(np.stack([b.blocks[l].edge_dst for b in batches])
               .astype(np.int32) for l in range(L))
    em = tuple(np.stack([b.blocks[l].edge_mask for b in batches])
               for l in range(L))
    sdeg = tuple(np.stack(
        [out_deg[np.maximum(b.blocks[l].src_nodes, 0)] for b in batches])
        .astype(np.float32) for l in range(L))
    return {
        "es": es, "ed": ed, "em": em, "sdeg": sdeg,
        "x": np.stack([b.x_in for b in batches]),
        "y": np.stack([b.labels for b in batches]).astype(np.int32),
        "w": np.stack([b.label_mask for b in batches]).astype(np.float32),
    }


# ---------------------------------------------------------------------------
# double-buffered host-side prefetch
# ---------------------------------------------------------------------------

class HostPrefetcher:
    """Double-buffered loader: one batch ready in the queue, one being
    produced by the worker thread, one being consumed by the device step —
    sampling + feature fetch of batch *t+1* overlap the jitted step on
    batch *t*.  ``wait_s``/``sample_s`` quantify how much host time the
    overlap actually hid."""

    def __init__(self, make_batch: Callable[[], object], *, depth: int = 2):
        self.sample_s = 0.0
        self.produced = 0
        self._m_stall = telemetry.counter(
            "prefetch_stall_seconds_total",
            "consumer seconds blocked on the prefetch queue (un-hidden "
            "host-side sampling time)")
        self._stall_seen = 0.0

        def timed():
            t0 = time.perf_counter()
            item = make_batch()
            self.sample_s += time.perf_counter() - t0
            self.produced += 1
            return item

        self.loader = PipelinedLoader(timed, depth=max(1, depth - 1),
                                      n_workers=1)

    def __iter__(self):
        return self

    def __next__(self):
        item = next(self.loader)
        # telemetry counters are monotone: feed them the *delta* of the
        # loader's cumulative idle clock since the last batch
        stall = self.loader.idle_s
        self._m_stall.inc(max(0.0, stall - self._stall_seen))
        self._stall_seen = stall
        return item

    @property
    def wait_s(self) -> float:
        """Consumer time spent blocked on the queue (un-hidden sampling)."""
        return self.loader.idle_s

    def overlap_ratio(self) -> float:
        """Fraction of host sampling time hidden behind device compute."""
        if self.sample_s <= 0:
            return 0.0
        return max(0.0, 1.0 - self.wait_s / self.sample_s)

    def close(self):
        self.loader.close()


# ---------------------------------------------------------------------------
# the shard_map training step
# ---------------------------------------------------------------------------

def make_distributed_minibatch_step(cfg: GNNConfig, optimizer, n_dev: int,
                                    caps: Sequence[Tuple[int, int, int]]):
    """Returns (mesh, train_step) for partition-parallel mini-batch
    training.  ``caps`` is the per-layer (dst, src, edge) shape contract
    from ``DistributedMinibatchSampler.block_shapes()`` — static, so the
    step compiles once.

    train_step(params, opt_state, arrays) -> (params, opt_state, loss)
    with ``arrays`` from :func:`collate`; params/opt_state replicated,
    gradients psum'd over ``"g"`` (decentralized all-reduce, scope
    ``dist.grad_psum``).  The step is jitted as a whole, so
    ``train_step.lower(...)`` gives its compiled program.

    ``cfg.use_kernel=True`` runs every block layer's aggregation through
    the differentiable Pallas kernels (``forward_blocks`` forwards the
    flag into each layer, including GAT's softmax denominator) — wire it
    from ``train_gnn --use-kernel``.

    ``cfg.wire_codec`` names the communication-plane codec the feature
    path used: the ``arrays["x"]`` rows from :func:`collate` already
    carry the codec-*decoded* values (remote misses crossed the wire in
    :class:`~repro.distributed.sampler.PartitionFeatureStore`, which the
    launcher must configure with the same codec), so the step itself
    consumes them as-is — the name is resolved here only to fail fast on
    a typo before the first batch is sampled.
    """
    mesh = Mesh(np.array(jax.devices()[:n_dev]), (AXIS,))
    resolve_codec(cfg.wire_codec)    # fail fast on unknown codec names
    caps = list(caps)

    def step(params, opt_state, es, ed, em, sdeg, x, y, w):
        blocks = []
        for l, (dcap, scap, _ecap) in enumerate(caps):
            es_l, ed_l, em_l = es[l][0], ed[l][0], em[l][0]
            mf = em_l.astype(jnp.float32)
            indeg = jnp.maximum(
                jnp.zeros((dcap,), jnp.float32).at[ed_l].add(mf), 1.0)
            blocks.append(DeviceGraph(es_l, ed_l, em_l, scap, dcap, indeg,
                                      sdeg[l][0]))
        x_l, y_l, w_l = x[0], y[0], w[0]
        # global seed count has no parameter dependence, so psum it OUTSIDE
        # the differentiated function: under check_vma=False a psum inside
        # loss_fn transposes to another psum, silently scaling gradients by
        # n_dev — Adam's scale-invariance masks it, exact equivalence
        # (tests/distributed_train_check.py) does not
        cnt = jnp.maximum(jax.lax.psum(jnp.sum(w_l), AXIS), 1.0)

        def loss_fn(p):
            logits = GM.forward_blocks(cfg, p, blocks, x_l)
            total, _ = GM.nll_sum_count(logits, y_l, w_l)
            return total / cnt           # this device's share of the mean

        local_loss, grads = jax.value_and_grad(loss_fn)(params)
        # the loss's psum beside the gradients': XLA may combine them
        with jax.named_scope("dist.grad_psum"):
            loss = jax.lax.psum(local_loss, AXIS)
            grads = jax.tree.map(lambda a: jax.lax.psum(a, AXIS), grads)
        params, opt_state = optimizer.apply(params, grads, opt_state)
        return params, opt_state, loss

    rep, shard = P(), P(AXIS)
    smapped = jax.shard_map(
        step, mesh=mesh,
        in_specs=(rep, rep, shard, shard, shard, shard, shard, shard,
                  shard),
        out_specs=(rep, rep, rep), check_vma=False)

    @jax.jit
    def partition_step(params, opt_state, arrays: dict):
        return smapped(params, opt_state, arrays["es"], arrays["ed"],
                       arrays["em"], arrays["sdeg"], arrays["x"],
                       arrays["y"], arrays["w"])

    return mesh, partition_step
