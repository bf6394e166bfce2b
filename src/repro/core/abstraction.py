"""Programming abstractions for GNNs (survey §3.2.3, Table 5).

Two abstractions are provided:

* **SAGA-NN** (NeuGraph): a GNN layer is Scatter → ApplyEdge → Gather →
  ApplyVertex.  Scatter/Gather are system-provided (gather of source
  features onto edges / segment reduction onto destinations); ApplyEdge and
  ApplyVertex are user-defined tensor functions.
* a **message-passing base class** (DGL/PyG style) implemented on top of
  SAGA-NN, used by the model zoo (GCN/SAGE/GAT/GIN).

TPU adaptation (DESIGN.md §2): edges are padded fixed-shape arrays and the
Gather step is a dense segment reduction (`jax.ops.segment_sum` — oracle
path) or the Pallas-blocked `repro.kernels.segment_sum` kernel.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import telemetry
from repro.core.sampling import Block
from repro.graph.structure import Graph


@dataclasses.dataclass
class DeviceGraph:
    """Padded edge-list graph on device.

    For bipartite blocks ``num_dst != num_src`` and destination nodes are a
    prefix of source nodes."""
    edge_src: jax.Array        # (E,) int32 — index into src features
    edge_dst: jax.Array        # (E,) int32 — index into dst features
    edge_mask: jax.Array       # (E,) bool
    num_src: int
    num_dst: int
    in_deg: jax.Array          # (num_dst,) float32 (masked in-degree)
    out_deg: jax.Array         # (num_src,) float32

    @staticmethod
    def from_graph(g: Graph) -> "DeviceGraph":
        e = g.edges()
        n = g.num_nodes
        src = jnp.asarray(e[:, 0], jnp.int32)
        dst = jnp.asarray(e[:, 1], jnp.int32)
        mask = jnp.ones((len(e),), bool)
        indeg = jnp.asarray(np.maximum(g.in_degree(), 1), jnp.float32)
        outdeg = jnp.asarray(np.maximum(g.out_degree(), 1), jnp.float32)
        return DeviceGraph(src, dst, mask, n, n, indeg, outdeg)

    @staticmethod
    def from_block(b: Block) -> "DeviceGraph":
        """Upload a sampled block; its masked degrees are counted on the
        device by one jitted call (span ``graph.from_block``)."""
        with telemetry.span("graph.from_block"):
            es = jnp.asarray(b.edge_src, jnp.int32)
            ed = jnp.asarray(b.edge_dst, jnp.int32)
            m = jnp.asarray(b.edge_mask)
            indeg, outdeg = _block_degrees(ed, es, m, b.num_dst, b.num_src)
            return DeviceGraph(es, ed, m, b.num_src, b.num_dst, indeg,
                               outdeg)


@functools.partial(jax.jit, static_argnames=("num_dst", "num_src"))
def _block_degrees(edge_dst, edge_src, mask, num_dst: int, num_src: int):
    """Masked in- and out-degrees of a block, at least 1."""
    with jax.named_scope("graph.degrees"):
        m = mask.astype(jnp.float32)
        indeg = jnp.zeros((num_dst,), jnp.float32).at[edge_dst].add(m)
        outdeg = jnp.zeros((num_src,), jnp.float32).at[edge_src].add(m)
        return jnp.maximum(indeg, 1.0), jnp.maximum(outdeg, 1.0)


jax.tree_util.register_dataclass(
    DeviceGraph,
    data_fields=["edge_src", "edge_dst", "edge_mask", "in_deg", "out_deg"],
    meta_fields=["num_src", "num_dst"])


# ---------------------------------------------------------------------------
# segment reductions (the Gather step)
# ---------------------------------------------------------------------------

def segment_sum(msgs, seg_ids, num_segments, *, use_kernel: bool = False):
    """Gather-step segment reduction: ``jax.ops.segment_sum`` oracle or
    the differentiable blocked Pallas kernel (``use_kernel=True``).
    Scope ``gnn.aggregate``, with the implementation under it."""
    with jax.named_scope("gnn.aggregate"):
        if use_kernel:
            from repro.kernels import ops as kops
            if msgs.ndim == 1:          # e.g. per-edge scalars/logits
                return kops.segment_sum(msgs[:, None], seg_ids,
                                        num_segments)[:, 0]
            return kops.segment_sum(msgs, seg_ids, num_segments)
        with jax.named_scope("jax_ops"):
            return jax.ops.segment_sum(msgs, seg_ids, num_segments)


def gather_scale_segment_sum(h, edge_src, edge_dst, coef, num_dst, *,
                             use_kernel: bool = False):
    """Fused Scatter -> ApplyEdge(scale) -> Gather:
    ``out[d] = sum_{e: edge_dst[e]=d} coef[e] * h[edge_src[e]]``.

    ``coef`` is the per-edge coefficient with the validity mask folded in
    (masked/pad edges carry 0).  With ``use_kernel=True`` this runs as
    ONE Pallas kernel that never materializes the (E, F) message tensor
    in HBM (see :mod:`repro.kernels.segment_sum`); the reference path
    spells out the same computation in XLA ops.  Scope
    ``gnn.aggregate``, with the implementation under it (``jax_ops``, or
    the kernel wrapper's own).
    """
    with jax.named_scope("gnn.aggregate"):
        if use_kernel:
            from repro.kernels import ops as kops
            return kops.gather_scale_segment_sum(h, edge_src, edge_dst,
                                                 coef, num_dst)
        with jax.named_scope("jax_ops"):
            msgs = jnp.take(h, edge_src, axis=0) * coef[:, None]
            return jax.ops.segment_sum(msgs, edge_dst, num_dst)


def segment_mean(msgs, seg_ids, num_segments, deg, *,
                 use_kernel: bool = False):
    """Degree-normalized segment reduction (``use_kernel`` forwarded to
    the underlying :func:`segment_sum`)."""
    s = segment_sum(msgs, seg_ids, num_segments, use_kernel=use_kernel)
    return s / deg[:, None]


def segment_max(msgs, seg_ids, num_segments):
    # no Pallas counterpart: max has no MXU-friendly one-hot form and is
    # never the hot path (GAT uses it once for numerical stability)
    return jax.ops.segment_max(msgs, seg_ids, num_segments,
                               indices_are_sorted=False)


def segment_softmax(logits, seg_ids, num_segments, mask):
    """Per-destination softmax over incoming edges (GAT), per column of
    ``logits`` ((E,) or (E, heads)); masked edges get weight 0.  Plain
    ``jax.ops``: the kernel paths compute theirs inside
    :func:`repro.kernels.ops.gat_attention`."""
    m = mask[:, None] if logits.ndim > 1 else mask
    logits = jnp.where(m, logits, jnp.asarray(-1e30, logits.dtype))
    mx = segment_max(logits, seg_ids, num_segments)
    ex = jnp.exp(logits - mx[seg_ids]) * m
    den = jax.ops.segment_sum(ex, seg_ids, num_segments)
    return ex / (den[seg_ids] + 1e-9)


# ---------------------------------------------------------------------------
# SAGA-NN
# ---------------------------------------------------------------------------

def saga_layer(g: DeviceGraph,
               x_src: jax.Array,
               x_dst: jax.Array,
               *,
               apply_edge: Callable,
               gather: str = "sum",
               apply_vertex: Callable,
               edge_data: Optional[jax.Array] = None,
               use_kernel: bool = False) -> jax.Array:
    """One SAGA-NN step.

    scatter:      src features -> edges (system)
    apply_edge:   (src_feat_on_edge, dst_feat_on_edge, edge_data) -> msgs
    gather:       segment reduce msgs onto destinations (system)
    apply_vertex: (aggregated, x_dst) -> new dst features
    """
    feat_e = jnp.take(x_src, g.edge_src, axis=0)              # Scatter
    dst_e = jnp.take(x_dst, g.edge_dst, axis=0)
    msgs = apply_edge(feat_e, dst_e, edge_data)               # ApplyEdge
    msgs = msgs * g.edge_mask[:, None].astype(msgs.dtype)
    if gather == "sum":                                        # Gather
        agg = segment_sum(msgs, g.edge_dst, g.num_dst,
                          use_kernel=use_kernel)
    elif gather == "mean":
        agg = segment_mean(msgs, g.edge_dst, g.num_dst, g.in_deg,
                           use_kernel=use_kernel)
    elif gather == "max":
        agg = segment_max(msgs, g.edge_dst, g.num_dst)
        agg = jnp.where(jnp.isfinite(agg), agg, 0.0)
    else:
        raise ValueError(gather)
    return apply_vertex(agg, x_dst)                            # ApplyVertex


class MessagePassing:
    """DGL/PyG-style base class on top of SAGA-NN.  Subclasses override
    ``message``/``aggregate``/``update`` and provide ``init``."""

    aggregate = "sum"

    def message(self, p, src_feat, dst_feat, edge_data):
        return src_feat

    def update(self, p, agg, self_feat):
        raise NotImplementedError

    def __call__(self, p, g: DeviceGraph, x_src, x_dst=None, *,
                 use_kernel=False):
        if x_dst is None:
            x_dst = x_src[:g.num_dst]
        return saga_layer(
            g, x_src, x_dst,
            apply_edge=lambda s, d, e: self.message(p, s, d, e),
            gather=self.aggregate,
            apply_vertex=lambda a, h: self.update(p, a, h),
            use_kernel=use_kernel)
