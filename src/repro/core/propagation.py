"""Distributed full-graph message propagation (survey §3.2.6 / §2.2.5).

The survey's push/pull taxonomy maps onto SPMD collectives exactly:

* **pull** (GAS/GraphLab/DGL): each device *pulls* the current features of
  all source vertices — ``all_gather`` over the graph axis, then a local
  gather + segment-reduce onto its own destinations.
* **push** (Pregel/NeuGraph): each device computes its local sources'
  contributions to *every* destination and *pushes* partial aggregates —
  a local segment-reduce into a full-size buffer followed by
  ``psum_scatter`` (reduce-scatter) onto the destination owners.

Both compute the same aggregation; they differ in where the reduction
happens and what crosses the wire (features vs partial aggregates) — the
trade-off the survey highlights.  DistGNN's delayed-aggregate mode (§3.2.7)
is the pull variant with a stale feature cache refreshed every ``s`` steps.

Everything here runs under ``shard_map`` over mesh axis ``"g"``; vertices
are range-partitioned after a partitioner-driven relabel (partitioning.py).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import partitioning as part_mod
from repro.core.abstraction import DeviceGraph, gather_scale_segment_sum
from repro.graph.structure import Graph

AXIS = "g"


@dataclasses.dataclass
class ShardedGraph:
    """Host-prepared, device-shardable graph layout.

    Arrays are concatenated per-device segments (axis 0 shards over "g"):
      edge_src_g:  (n_dev * E_loc,) GLOBAL src id         (pull layout)
      edge_dst_l:  (n_dev * E_loc,) LOCAL dst id
      edge_mask:   (n_dev * E_loc,)
      x:           (N_pad, F) permuted features
      labels/mask: (N_pad,)
      in_deg:      (N_pad,) global in-degree (clamped >= 1)
      out_deg:     (N_pad,)
    """
    n_dev: int
    n_local: int
    e_local: int
    perm: np.ndarray
    edge_src_g: jax.Array
    edge_dst_l: jax.Array
    edge_mask: jax.Array
    x: jax.Array
    labels: jax.Array
    label_mask: jax.Array
    in_deg: jax.Array
    out_deg: jax.Array


def shard_graph(g: Graph, n_dev: int, *, method: str = "hash",
                feat: Optional[np.ndarray] = None) -> ShardedGraph:
    """Partition with the chosen edge-cut strategy, relabel vertices to
    contiguous per-device ranges, pad, and build the pull edge layout."""
    p = part_mod.partition(g, n_dev, method)
    assert isinstance(p, part_mod.EdgeCutPartition), \
        "distributed full-graph training uses edge-cut partitioners"
    order, counts = part_mod.contiguousize(g, p)  # order[new] = old
    n_local = int(np.ceil(counts.max() / 1)) if n_dev == 1 else int(
        np.ceil(g.num_nodes / n_dev))
    n_local = max(n_local, int(counts.max()))
    n_pad = n_local * n_dev

    # new id layout: device d owns [d*n_local, d*n_local + counts[d])
    new_of_old = np.full(g.num_nodes, -1, np.int64)
    off = 0
    starts = np.zeros(n_dev, np.int64)
    for d in range(n_dev):
        starts[d] = d * n_local
    pos = starts.copy()
    for new_seq, old in enumerate(order):
        d = p.assignment[old]
        new_of_old[old] = pos[d]
        pos[d] += 1

    e = g.edges()
    src_new = new_of_old[e[:, 0]]
    dst_new = new_of_old[e[:, 1]]
    dst_dev = dst_new // n_local

    # group edges by destination owner, pad each device to e_local
    e_local = 0
    groups = []
    for d in range(n_dev):
        sel = dst_dev == d
        groups.append((src_new[sel], dst_new[sel] - d * n_local))
        e_local = max(e_local, int(sel.sum()))
    e_local = max(e_local, 1)
    es = np.zeros((n_dev, e_local), np.int32)
    ed = np.zeros((n_dev, e_local), np.int32)
    em = np.zeros((n_dev, e_local), bool)
    for d, (s_, d_) in enumerate(groups):
        k = len(s_)
        es[d, :k] = s_
        ed[d, :k] = d_
        em[d, :k] = True

    feats = g.features if feat is None else feat
    F = feats.shape[1]
    x = np.zeros((n_pad, F), np.float32)
    labels = np.zeros((n_pad,), np.int32)
    lmask = np.zeros((n_pad,), np.float32)
    x[new_of_old] = feats
    if g.labels is not None:
        labels[new_of_old] = g.labels
        lmask[new_of_old] = 1.0
    indeg = np.ones((n_pad,), np.float32)
    outdeg = np.ones((n_pad,), np.float32)
    indeg[new_of_old] = np.maximum(g.in_degree(), 1)
    outdeg[new_of_old] = np.maximum(g.out_degree(), 1)

    return ShardedGraph(
        n_dev=n_dev, n_local=n_local, e_local=e_local, perm=new_of_old,
        edge_src_g=jnp.asarray(es.reshape(-1)),
        edge_dst_l=jnp.asarray(ed.reshape(-1)),
        edge_mask=jnp.asarray(em.reshape(-1)),
        x=jnp.asarray(x), labels=jnp.asarray(labels),
        label_mask=jnp.asarray(lmask),
        in_deg=jnp.asarray(indeg), out_deg=jnp.asarray(outdeg))


# ---------------------------------------------------------------------------
# pull / push aggregation primitives (inside shard_map)
# ---------------------------------------------------------------------------

def pull_aggregate(h_loc, edge_src_g, edge_dst_l, edge_mask, n_local,
                   *, coef_e=None, use_kernel=False):
    """All-gather features, local segment-sum onto owned destinations.

    Args (inside shard_map over ``"g"``): ``h_loc`` ``(n_local, F)`` owned
    rows; ``edge_src_g`` global src ids / ``edge_dst_l`` local dst ids /
    ``edge_mask`` validity for this device's ``(E_loc,)`` edge slice;
    ``coef_e`` optional per-edge coefficient.  Returns ``(n_local, F)``
    aggregates; masked (pad) edges contribute zero, so pad rows never
    aggregate.  ``use_kernel=True`` runs gather+scale+reduce as one fused
    Pallas kernel (no (E, F) message tensor in HBM)."""
    h_all = jax.lax.all_gather(h_loc, AXIS, tiled=True)     # (N_pad, F)
    coef = edge_mask.astype(h_all.dtype)
    if coef_e is not None:
        coef = coef * coef_e
    return gather_scale_segment_sum(h_all, edge_src_g, edge_dst_l, coef,
                                    n_local, use_kernel=use_kernel)


def push_aggregate(h_loc, edge_src_l, edge_dst_g, edge_mask, n_pad,
                   *, coef_e=None, use_kernel=False):
    """Local partial aggregates for ALL destinations, reduce-scatter.

    Args mirror :func:`pull_aggregate` with the dual layout: ``edge_src_l``
    local src ids, ``edge_dst_g`` global dst ids, ``n_pad`` the padded
    global row count.  Returns this device's ``(n_local, F)`` slice of the
    psum_scattered aggregate; masked edges contribute zero."""
    coef = edge_mask.astype(h_loc.dtype)
    if coef_e is not None:
        coef = coef * coef_e
    partial = gather_scale_segment_sum(h_loc, edge_src_l, edge_dst_g,
                                       coef, n_pad,
                                       use_kernel=use_kernel)
    # Forward-pass sharding primitive, not the PR 2 class: unlike psum,
    # differentiating through psum_scatter inserts no second reduction.
    # repro-lint: disable=RL001 -- psum_scatter transpose is all_gather, no double reduction
    return jax.lax.psum_scatter(partial, AXIS, scatter_dimension=0,
                                tiled=True)                 # (N_loc, F)


def push_layout(sg: ShardedGraph, g: Graph) -> dict:
    """Re-group the edge list by SOURCE owner (push layout)."""
    e = g.edges()
    src_new = sg.perm[e[:, 0]]
    dst_new = sg.perm[e[:, 1]]
    src_dev = src_new // sg.n_local
    groups = []
    e_local = 1
    for d in range(sg.n_dev):
        sel = src_dev == d
        groups.append((src_new[sel] - d * sg.n_local, dst_new[sel]))
        e_local = max(e_local, int(sel.sum()))
    es = np.zeros((sg.n_dev, e_local), np.int32)
    ed = np.zeros((sg.n_dev, e_local), np.int32)
    em = np.zeros((sg.n_dev, e_local), bool)
    for d, (s_, d_) in enumerate(groups):
        k = len(s_)
        es[d, :k] = s_
        ed[d, :k] = d_
        em[d, :k] = True
    return {"edge_src_l": jnp.asarray(es.reshape(-1)),
            "edge_dst_g": jnp.asarray(ed.reshape(-1)),
            "edge_mask": jnp.asarray(em.reshape(-1))}


# ---------------------------------------------------------------------------
# distributed GCN training step (pull | push | stale-pull)
# ---------------------------------------------------------------------------

def gcn_forward_local(params, h_loc, sg_local, *, mode, halo_cache=None,
                      use_kernel=False):
    """Runs inside shard_map.  ``sg_local`` holds per-device edge slices and
    degree vectors; GCN normalization 1/sqrt(d_out d_in) per edge.
    ``use_kernel`` routes each layer's aggregation through the fused
    Pallas gather-scale-segment-sum kernel."""
    (es, ed, em, indeg_l, outdeg_all, n_local) = sg_local
    h = h_loc
    n_layers = len(params)
    for i, p in enumerate(params):
        hw = h @ p["w"]
        if mode == "pull":
            h_all = jax.lax.all_gather(hw, AXIS, tiled=True)
        elif mode == "stale" and halo_cache is not None and i == 0:
            # DistGNN-style: first-layer halo uses the cached (stale)
            # features; deeper layers still synchronize.
            h_all = halo_cache @ p["w"]
        else:
            h_all = jax.lax.all_gather(hw, AXIS, tiled=True)
        coef = (jax.lax.rsqrt(jnp.take(outdeg_all, es))
                * jax.lax.rsqrt(jnp.take(indeg_l, ed)))
        agg = gather_scale_segment_sum(h_all, es, ed, coef * em, n_local,
                                       use_kernel=use_kernel)
        h = agg + p["b"]
        if i + 1 < n_layers:
            h = jax.nn.relu(h)
    return h


def gcn_forward_push(params, h_loc, push_arrays, outdeg_all, indeg_l,
                     n_local, n_dev, *, use_kernel=False):
    """Push-mode GCN forward (Pregel/NeuGraph): each device computes its
    LOCAL sources' contributions for every destination and reduce-scatters
    partial aggregates."""
    es_l, ed_g, em = push_arrays
    idx = jax.lax.axis_index(AXIS)
    h = h_loc
    n_layers = len(params)
    n_pad = n_local * n_dev
    for i, p in enumerate(params):
        hw = h @ p["w"]
        # per-edge GCN normalization with LOCAL source / GLOBAL dest degree
        outdeg_l = jax.lax.dynamic_slice_in_dim(
            outdeg_all, idx * n_local, n_local, axis=0)
        indeg_all = jax.lax.all_gather(indeg_l, AXIS, tiled=True)
        coef = (jax.lax.rsqrt(jnp.take(outdeg_l, es_l))
                * jax.lax.rsqrt(jnp.take(indeg_all, ed_g)))
        h = push_aggregate(hw, es_l, ed_g, em, n_pad, coef_e=coef,
                           use_kernel=use_kernel) + p["b"]
        if i + 1 < n_layers:
            h = jax.nn.relu(h)
    return h


def make_distributed_gcn_step(optimizer, n_dev: int, *, mode: str = "pull",
                              use_kernel: bool = False):
    """Returns (mesh, train_step) for full-graph distributed GCN.

    mode: "pull" (all-gather features), "stale" (DistGNN delayed halos) or
    "push" (reduce-scatter partial aggregates; requires push-layout edges
    passed via ``train_step(..., push_arrays=...)``).  ``use_kernel``
    runs every layer's aggregation through the differentiable Pallas
    kernels — fused while the (all-gathered) source slab fits VMEM
    (``repro.kernels.segment_sum.fused_fits``), else the unfused blocked
    kernel, dispatched automatically; the gradient-equivalence matrix in
    ``tests/kernel_train_check.py`` proves the kernel path matches this
    reference to <= 1e-5 per parameter.

    train_step(params, opt_state, sg_arrays...) -> (params, opt_state, loss)
    with all graph arrays sharded over axis "g".  Gradients are psum'd
    (decentralized all-reduce coordination; see coordination.py for the
    parameter-server emulation).
    """
    devs = np.array(jax.devices()[:n_dev])
    mesh = Mesh(devs, (AXIS,))

    if mode == "push":
        def pstep(params, opt_state, x, es_l, ed_g, em, indeg, outdeg,
                  labels, lmask):
            n_local = x.shape[0]
            # psum the (parameter-free) count OUTSIDE the differentiated
            # function: under check_vma=False a psum inside loss_fn
            # transposes to another psum, scaling gradients by n_dev
            # (masked by Adam scale-invariance + clipping, caught by the
            # gradient-equivalence matrix in tests/distributed_train_check)
            cnt = jnp.maximum(jax.lax.psum(jnp.sum(lmask), AXIS), 1.0)

            def loss_fn(p):
                h = gcn_forward_push(p, x, (es_l, ed_g, em), outdeg,
                                     indeg, n_local, n_dev,
                                     use_kernel=use_kernel)
                logz = jax.nn.logsumexp(h, axis=-1)
                gold = jnp.take_along_axis(h, labels[:, None],
                                           axis=-1)[:, 0]
                return jnp.sum((logz - gold) * lmask) / cnt

            local_loss, grads = jax.value_and_grad(loss_fn)(params)
            loss = jax.lax.psum(local_loss, AXIS)
            grads = jax.tree.map(lambda g_: jax.lax.psum(g_, AXIS), grads)
            params, opt_state = optimizer.apply(params, grads, opt_state)
            return params, opt_state, loss

        rep = P()
        shard = P(AXIS)
        smapped = jax.shard_map(
            pstep, mesh=mesh,
            in_specs=(rep, rep, shard, shard, shard, shard, shard, rep,
                      shard, shard),
            out_specs=(rep, rep, rep), check_vma=False)

        def train_step(params, opt_state, sg: ShardedGraph, *,
                       push_arrays: dict, halo_cache=None):
            return jax.jit(smapped)(
                params, opt_state, sg.x, push_arrays["edge_src_l"],
                push_arrays["edge_dst_g"], push_arrays["edge_mask"],
                sg.in_deg, sg.out_deg, sg.labels, sg.label_mask)

        return mesh, train_step

    def step(params, opt_state, x, es, ed, em, indeg, outdeg, labels, lmask,
             halo_cache):
        n_local = x.shape[0]
        indeg_l = indeg
        outdeg_all = outdeg  # replicated (N_pad,)
        # count psum'd outside the VJP (see pstep: psum-in-loss_fn would
        # scale gradients by n_dev under check_vma=False)
        cnt = jnp.maximum(jax.lax.psum(jnp.sum(lmask), AXIS), 1.0)

        def loss_fn(p):
            h = gcn_forward_local(
                p, x, (es, ed, em, indeg_l, outdeg_all, n_local),
                mode=mode, halo_cache=halo_cache, use_kernel=use_kernel)
            logz = jax.nn.logsumexp(h, axis=-1)
            gold = jnp.take_along_axis(h, labels[:, None], axis=-1)[:, 0]
            return jnp.sum((logz - gold) * lmask) / cnt

        local_loss, grads = jax.value_and_grad(loss_fn)(params)
        loss = jax.lax.psum(local_loss, AXIS)
        # each device's grad covers only its local psum contribution, so
        # the decentralized combine is a SUM (all-reduce), not a mean
        grads = jax.tree.map(lambda g_: jax.lax.psum(g_, AXIS), grads)
        params, opt_state = optimizer.apply(params, grads, opt_state)
        return params, opt_state, loss

    pspec = P()
    shard = P(AXIS)
    smapped = jax.shard_map(
        step, mesh=mesh,
        in_specs=(pspec, pspec, shard, shard, shard, shard, shard, pspec,
                  shard, shard, pspec),
        out_specs=(pspec, pspec, pspec),
        check_vma=False)

    def train_step(params, opt_state, sg: ShardedGraph, halo_cache=None):
        if halo_cache is None:
            halo_cache = sg.x  # full (replicated) feature matrix
        return jax.jit(smapped)(
            params, opt_state, sg.x, sg.edge_src_g, sg.edge_dst_l,
            sg.edge_mask, sg.in_deg, sg.out_deg, sg.labels, sg.label_mask,
            halo_cache)

    return mesh, train_step
