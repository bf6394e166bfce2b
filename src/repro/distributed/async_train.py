"""Staleness-bounded asynchronous full-graph training (survey §3.2.7).

The third major system family after sampling-based mini-batch training
(``repro.distributed.sampler``/``pipeline``) and online inference
(``repro.serving``): full-graph training where boundary ("ghost")
activations are exchanged with *bounded staleness* instead of a
synchronous halo exchange every layer — the PipeGCN / DistGNN /
SANCUS recipe that hides communication behind compute.

Composition of existing pieces:

* :class:`repro.core.halo.HaloExchange` — versioned per-layer ghost
  buffers under the shared :class:`repro.core.caching.VersionClock`
  (the same staleness implementation serving's ``EmbeddingCache`` uses);
* :func:`repro.models.gnn.model.forward_stale` — the GCN forward that
  aggregates historical activations for non-refreshed ghosts;
* the double-buffering pattern from :class:`~repro.distributed.pipeline.
  HostPrefetcher` — the refresh *plan* for step ``t+1`` (mask selection,
  version stamping, byte accounting) is produced on a host thread while
  the jitted step still computes step ``t``.

Semantics per step ``t`` with bound ``S`` and budget ``F``:

1. the planner marks every ghost row whose staleness would exceed ``S``
   (plus the oldest ``F``-fraction of the rest) for *synchronous* refresh;
2. the shard_map step computes with fresh activations for owned +
   refreshed rows and historical buffer values for everything else;
3. refreshed rows' freshly gathered values are written back to the
   buffers, stamped with the step's clock value.

``S = 0`` forces every ghost row into every plan, degrading exactly to
the synchronous pull step of
:func:`repro.core.propagation.make_distributed_gcn_step` — the
equivalence ``tests/async_train_check.py`` proves to ≤ 1e-5 per
parameter.  Larger ``S`` strictly reduces cross-partition bytes/step
(each row crosses the wire at most every ``S+1`` steps).

Orthogonally, ``cfg.wire_codec`` compresses what DOES cross the wire
through the unified communication plane (:mod:`repro.core.comm`): ghost
refreshes are quantized in-step (``bf16`` truncation or ``int8`` per-row
affine + error-feedback residuals), the historical buffers store the
decoded wire values, and every plan prices rows at the codec's wire
size — int8 cuts bytes/step ~4x at an accuracy gap ≤ 0.02
(``benchmarks/bench_async.py`` asserts both).
"""
from __future__ import annotations

import time
from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import telemetry
from repro.core.comm import resolve_codec
from repro.core.halo import HaloExchange, build_halo
from repro.core.partitioning import EdgeCutPartition
from repro.core.propagation import AXIS, ShardedGraph, shard_graph
from repro.distributed.pipeline import HostPrefetcher
from repro.graph.structure import Graph
from repro.models.gnn import model as GM
from repro.models.gnn.model import GNNConfig


def exchange_for_shards(g: Graph, sg: ShardedGraph,
                        layer_dims: Sequence[int], *,
                        max_staleness: int = 0, refresh_frac: float = 0.0,
                        codec="fp32", clock=None) -> HaloExchange:
    """Build the :class:`HaloExchange` matching a ``ShardedGraph``.

    ``shard_graph`` relabels vertices to contiguous per-device ranges, so
    ownership is recoverable as ``perm[v] // n_local``; the halo layout is
    computed in original ids and the exchange buffers live in the padded
    relabeled space the shard_map step indexes into.

    Args:
        g: the original graph.
        sg: the sharded layout built from it.
        layer_dims: widths of the buffered layer outputs (``[hidden] *
            (num_layers - 1)`` for the GCN stack).
        max_staleness / refresh_frac / codec / clock: forwarded to
            :class:`HaloExchange` (``codec`` selects the wire format of
            the ghost refresh payloads).
    """
    part = EdgeCutPartition(
        assignment=(sg.perm // sg.n_local).astype(np.int64),
        n_parts=sg.n_dev)
    layout = build_halo(g, part)
    return HaloExchange(layout, layer_dims, max_staleness=max_staleness,
                        refresh_frac=refresh_frac, relabel=sg.perm,
                        n_rows=sg.n_local * sg.n_dev, codec=codec,
                        clock=clock)


def make_async_fullgraph_step(optimizer, n_dev: int, *,
                              use_kernel: bool = False, codec="fp32"):
    """Build the jitted staleness-bounded full-graph GCN step.

    Returns ``(mesh, train_step)`` where::

        train_step(params, opt_state, sg, ghosts, refresh, residuals)
            -> (params, opt_state, loss, planes, residuals)

    ``sg`` is a :class:`~repro.core.propagation.ShardedGraph`; ``ghosts``
    are the per-layer ``(N_pad, F_l)`` stale activation planes
    (replicated); ``refresh`` the per-layer ``(N_pad,)`` bool refresh
    masks; ``planes`` the layer outputs *as they crossed the wire*
    (codec-decoded; exact fp32 under the identity codec) to write back;
    ``residuals`` the per-layer error-feedback state (pass ``()`` and
    ignore the returned value under the identity codec, which compiles
    the exact pre-codec step).  Params/opt_state replicated, graph arrays
    sharded over mesh axis ``"g"``, gradients psum'd — identical
    conventions to :func:`repro.core.propagation.make_distributed_gcn_step`.
    ``use_kernel`` runs every layer's aggregation through the fused
    Pallas gather-scale-segment-sum kernel; ``codec`` selects the
    communication-plane wire format (see :mod:`repro.core.comm`).
    """
    mesh = Mesh(np.array(jax.devices()[:n_dev]), (AXIS,))
    codec = resolve_codec(codec)
    quantize = not codec.identity

    def step(params, opt_state, x, es, ed, em, indeg, outdeg, labels,
             lmask, ghosts, refresh, residuals):
        n_local = x.shape[0]
        n_pad = outdeg.shape[0]
        idx = jax.lax.axis_index(AXIS)
        own_rows = (jnp.arange(n_pad, dtype=jnp.int32) // n_local) == idx
        # parameter-free count psum'd OUTSIDE the differentiated function
        # (under check_vma=False a psum inside loss_fn transposes to a
        # second psum, scaling gradients by n_dev — see propagation.py)
        cnt = jnp.maximum(jax.lax.psum(jnp.sum(lmask), AXIS), 1.0)

        def loss_fn(p):
            h, planes, res_out = GM.forward_stale(
                p, x, (es, ed, em, indeg, outdeg, n_local), ghosts,
                refresh, own_rows, axis=AXIS, use_kernel=use_kernel,
                codec=codec if quantize else None,
                residuals=residuals if quantize else None)
            logz = jax.nn.logsumexp(h, axis=-1)
            gold = jnp.take_along_axis(h, labels[:, None], axis=-1)[:, 0]
            return (jnp.sum((logz - gold) * lmask) / cnt,
                    (planes, res_out))

        (local_loss, (planes, res_out)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        loss = jax.lax.psum(local_loss, AXIS)
        grads = jax.tree.map(lambda g_: jax.lax.psum(g_, AXIS), grads)
        params, opt_state = optimizer.apply(params, grads, opt_state)
        return params, opt_state, loss, planes, res_out

    rep, shard = P(), P(AXIS)
    smapped = jax.shard_map(
        step, mesh=mesh,
        in_specs=(rep, rep, shard, shard, shard, shard, shard, rep,
                  shard, shard, rep, rep, rep),
        out_specs=(rep, rep, rep, rep, rep), check_vma=False)
    jitted = jax.jit(smapped)

    def train_step(params, opt_state, sg: ShardedGraph,
                   ghosts: Sequence[jax.Array],
                   refresh: Sequence[jax.Array],
                   residuals: Sequence[jax.Array] = ()):
        return jitted(params, opt_state, sg.x, sg.edge_src_g,
                      sg.edge_dst_l, sg.edge_mask, sg.in_deg, sg.out_deg,
                      sg.labels, sg.label_mask, tuple(ghosts),
                      tuple(refresh), tuple(residuals))

    return mesh, train_step


class AsyncFullGraphTrainer:
    """Host driver for staleness-bounded asynchronous full-graph training.

    Owns the sharded layout, the :class:`HaloExchange`, and the jitted
    step; :meth:`run` overlaps refresh planning with device compute via
    :class:`~repro.distributed.pipeline.HostPrefetcher` and keeps exact
    consumed-plan traffic accounting.

    Args:
        g: the training graph (features + labels required).
        cfg: GCN config (``arch="gcn"``; the full-graph shard_map path is
            GCN-specific, like the synchronous one).  ``cfg.use_kernel``
            routes aggregation through the fused Pallas kernel.
        optimizer: an ``optim``-style optimizer (``init``/``apply``).
        n_dev: mesh size (one partition per device).
        partitioner: edge-cut method name (``hash``/``ldg``/``fennel``).
        staleness: bound ``S`` — a ghost activation may be up to ``S``
            steps old; ``0`` = synchronous halo exchange.
        refresh_frac: extra per-step refresh budget (fraction of ghosts).

    ``cfg.wire_codec`` selects the communication-plane wire format of the
    ghost refresh payloads (``fp32`` is bit-exact with the pre-codec
    trainer; ``bf16``/``int8`` compress, with int8 carrying sender-side
    error-feedback residuals through the step).
    """

    def __init__(self, g: Graph, cfg: GNNConfig, optimizer, n_dev: int, *,
                 partitioner: str = "hash", staleness: int = 0,
                 refresh_frac: float = 0.0):
        if cfg.arch != "gcn":
            raise ValueError("async full-graph training implements GCN "
                             "(like the synchronous shard_map path)")
        self.g = g
        self.cfg = cfg
        self.n_dev = n_dev
        self.partitioner = partitioner
        self.codec = resolve_codec(cfg.wire_codec)
        self.sg = shard_graph(g, n_dev, method=partitioner)
        layer_dims = [cfg.hidden] * (cfg.num_layers - 1)
        self.exchange = exchange_for_shards(
            g, self.sg, layer_dims, max_staleness=staleness,
            refresh_frac=refresh_frac, codec=self.codec)
        self.mesh, self.step = make_async_fullgraph_step(
            optimizer, n_dev, use_kernel=cfg.use_kernel, codec=self.codec)
        # sender-side error-feedback state (error-feedback codecs only):
        # lives next to the ghost buffers so it persists across run()
        # calls — quantization error keeps feeding back epoch over epoch
        self._residuals = (tuple(
            jnp.zeros((self.sg.n_local * n_dev, d), jnp.float32)
            for d in layer_dims) if self.codec.error_feedback else ())
        self.steps_run = 0
        self.consumed_bytes = 0
        self.consumed_rows = 0
        self._update_seq = 0
        self.step_times_s: List[float] = []
        self._m_step = telemetry.histogram(
            "train_step_seconds", "wall time per executed training step",
            buckets=telemetry.DEFAULT_TIME_BUCKETS,
            mode="fullgraph_async")

    # -- dynamic graphs ----------------------------------------------------
    def fold_updates(self, log, upto_seq=None) -> dict:
        """Continual training: fold pending
        :class:`repro.core.updates.GraphUpdateLog` events into the
        training graph between epochs, WITHOUT a cold restart.

        The graph arrays mutate in place, the sharded layout is rebuilt
        (edge deltas change the padded edge lists; ``hash`` keeps the
        same node assignment, ``ldg``/``fennel`` may re-balance), and the
        :class:`HaloExchange` is rebuilt on the SAME version clock with
        every buffer row ported by node id — so untouched ghost rows
        keep their values and version stamps, and their staleness
        accounting survives the fold.  Rows owned by the
        ``(num_layers-1)``-hop delta frontier are then invalidated: the
        next plan force-refreshes exactly them, regardless of the bound
        S, so a stale read never spans a graph mutation
        (``halo_staleness_violations_total`` stays 0).

        The jitted step is reused as-is (it closes over the optimizer and
        mesh, not the layout).  Error-feedback residuals are reset to
        zero — they priced rows of the pre-fold graph.  Idempotent per
        sequence number.  Returns a fold summary dict."""
        from repro.core.updates import fold_in_place
        upto = log.last_seq if upto_seq is None else upto_seq
        if upto <= self._update_seq:
            return {"events": 0, "touched_nodes": 0,
                    "invalidated_rows": 0, "upto_seq": self._update_seq}
        delta, frontier = fold_in_place(
            self.g, log, self._update_seq, upto,
            hops=self.cfg.num_layers - 1)
        old_sg, old_ex = self.sg, self.exchange
        self.sg = shard_graph(self.g, self.n_dev, method=self.partitioner)
        layer_dims = [self.cfg.hidden] * (self.cfg.num_layers - 1)
        self.exchange = exchange_for_shards(
            self.g, self.sg, layer_dims,
            max_staleness=old_ex.max_staleness,
            refresh_frac=old_ex.refresh_frac, codec=self.codec,
            clock=old_ex.clock)
        # port buffer state by NODE id: perm maps original id -> padded
        # row, so row contents and version stamps follow each node across
        # any re-partition; rows nothing maps to keep NEVER (cold)
        for new_buf, old_buf in zip(self.exchange.buffers, old_ex.buffers):
            new_buf.values[self.sg.perm] = old_buf.values[old_sg.perm]
            new_buf.version[self.sg.perm] = old_buf.version[old_sg.perm]
        n_inv = self.exchange.invalidate_rows(self.sg.perm[frontier])
        if self.codec.error_feedback:
            self._residuals = tuple(
                jnp.zeros((self.sg.n_local * self.n_dev, d), jnp.float32)
                for d in layer_dims)
        self._update_seq = upto
        return {"events": delta.n_events,
                "touched_nodes": int(len(delta.nodes)),
                "invalidated_rows": n_inv,
                "upto_seq": upto}

    # -- training loop -----------------------------------------------------
    def run(self, params, opt_state, epochs: int, *, log_every: int = 0,
            prefetch_plans: bool = True):
        """Train ``epochs`` full-graph steps; returns
        ``(params, opt_state, last_loss)``.

        The planner produces exactly ``epochs`` refresh plans (then ``None``
        sentinels), so version stamps and byte accounting correspond
        one-to-one to executed steps even though planning runs ahead on
        the prefetch thread.
        """
        produced = {"n": 0}

        def next_plan():
            if produced["n"] >= epochs:
                return None              # sentinel: planner budget spent
            produced["n"] += 1
            return self.exchange.plan_refresh()

        planner = HostPrefetcher(next_plan) if prefetch_plans else None
        loss = jnp.zeros(())
        # device-resident ghost planes, seeded from the host buffers once;
        # per step only the refreshed rows change (a where(), not a full
        # (N_pad, F) host->device upload), keeping step_ms honest
        ghosts = [jnp.asarray(b) for b in self.exchange.ghost_planes()]
        try:
            for epoch in range(epochs):
                plan = next(planner) if planner else next_plan()
                t0 = time.perf_counter()
                masks = [jnp.asarray(m) for m in plan.masks]
                # residuals are instance state (carried through the step
                # so the wire planes it returns are exactly what
                # receivers decode, and preserved across run() calls)
                (params, opt_state, loss, planes,
                 self._residuals) = self.step(
                    params, opt_state, self.sg, ghosts, masks,
                    self._residuals)
                ghosts = [jnp.where(m[:, None], pl, gh) for m, pl, gh
                          in zip(masks, planes, ghosts)]
                self.exchange.write_planes(
                    plan, [np.asarray(pl) for pl in planes])
                dt = time.perf_counter() - t0
                self.step_times_s.append(dt)
                self._m_step.observe(dt)
                self.steps_run += 1
                self.consumed_bytes += plan.bytes
                self.consumed_rows += plan.rows_moved
                if log_every and (epoch % log_every == 0
                                  or epoch == epochs - 1):
                    print(f"epoch {epoch:3d} loss {float(loss):.4f} "
                          f"refresh_rows {plan.rows_moved} "
                          f"bytes {plan.bytes}")
        finally:
            if planner is not None:
                planner.close()
        return params, opt_state, float(loss)

    # -- evaluation / reporting --------------------------------------------
    def accuracy(self, params) -> float:
        """Full-graph accuracy of ``params`` on a single device (exact,
        no staleness — the number the accuracy-gap benchmark reports)."""
        from repro.core.abstraction import DeviceGraph
        dg = DeviceGraph.from_graph(self.g)
        logits = GM.forward_full(self.cfg, params, dg,
                                 jnp.asarray(self.g.features))
        return float(GM.accuracy(logits, jnp.asarray(self.g.labels)))

    def stats(self) -> dict:
        """Consumed-plan traffic + timing, with the synchronous baseline
        for savings reporting."""
        steps = max(self.steps_run, 1)
        sync = self.exchange.sync_bytes_per_step()
        per_step = self.consumed_bytes / steps
        return {
            "staleness": self.exchange.max_staleness,
            "refresh_frac": self.exchange.refresh_frac,
            "wire_codec": self.codec.name,
            "steps": self.steps_run,
            "ghost_rows": self.exchange.n_ghost,
            "bytes_per_step": per_step,
            "rows_per_step": self.consumed_rows / steps,
            "sync_bytes_per_step": sync,
            "comm_savings": 1.0 - per_step / sync if sync else 0.0,
            "mean_step_s": (sum(self.step_times_s) / steps
                            if self.step_times_s else 0.0),
        }
