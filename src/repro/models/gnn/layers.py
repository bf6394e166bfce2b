"""GNN layers built on the SAGA-NN / message-passing abstraction
(survey Table 5 algorithms: GCN, GraphSAGE, GAT, GIN).

Each layer names its device work with ``jax.named_scope``: ``gnn.dense``
for projections and updates, ``gnn.norm`` for degree coefficients and
normalisation, ``gnn.aggregate`` (set by the aggregation itself) for the
gather and reduction.  The scopes reach the compiled program's
``op_name`` metadata, under ``transpose(jvp(...))`` in the backward pass.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.abstraction import (DeviceGraph, MessagePassing,
                                    gather_scale_segment_sum,
                                    segment_softmax)
from repro.core.comm import QuantizedRows


def _dense(key, din, dout):
    return (jax.random.normal(key, (din, dout), jnp.float32)
            / np.sqrt(din))


class GCNLayer(MessagePassing):
    """Kipf & Welling: h' = ReLU(D^-1/2 A D^-1/2 H W)."""

    aggregate = "sum"

    @staticmethod
    def init(key, din, dout):
        return {"w": _dense(key, din, dout),
                "b": jnp.zeros((dout,), jnp.float32)}

    def __call__(self, p, g: DeviceGraph, x_src, x_dst=None, *,
                 use_kernel=False):
        if isinstance(x_src, QuantizedRows):
            x_src = jnp.asarray(x_src.dequantize())   # projects first
        with jax.named_scope("gnn.dense"):
            h = x_src @ p["w"]
        with jax.named_scope("gnn.norm"):
            norm_src = jax.lax.rsqrt(g.out_deg)
            norm_dst = jax.lax.rsqrt(g.in_deg)
            coef = (jnp.take(norm_src, g.edge_src)
                    * jnp.take(norm_dst, g.edge_dst) * g.edge_mask)
        # fused gather+scale+reduce: the (E, F) message tensor only ever
        # exists tile-by-tile in VMEM on the kernel path
        agg = gather_scale_segment_sum(h, g.edge_src, g.edge_dst, coef,
                                       g.num_dst, use_kernel=use_kernel)
        with jax.named_scope("gnn.dense"):
            return agg + p["b"]


class SAGELayer(MessagePassing):
    """GraphSAGE-mean: h' = W_self h + W_nbr mean(neighbors).

    The neighbor mean routes through the fused
    gather→scale→segment-sum (mask as the per-edge coefficient, degree
    normalization after) — same math as the previous ``segment_mean``
    path, but on the kernel path the (E, F) message tensor stays in
    VMEM, and because features aggregate *before* any projection,
    layer 0 can consume :class:`~repro.core.comm.QuantizedRows` int8
    wire rows directly: the kernel dequantizes per source slab, so the
    wire fetch never takes a decode round-trip through HBM
    (``--wire-codec int8 --use-kernel``)."""

    aggregate = "mean"

    @staticmethod
    def init(key, din, dout):
        k1, k2 = jax.random.split(key)
        return {"w_self": _dense(k1, din, dout),
                "w_nbr": _dense(k2, din, dout),
                "b": jnp.zeros((dout,), jnp.float32)}

    def update(self, p, agg, self_feat):
        with jax.named_scope("gnn.dense"):
            return self_feat @ p["w_self"] + agg @ p["w_nbr"] + p["b"]

    def __call__(self, p, g: DeviceGraph, x_src, x_dst=None, *,
                 use_kernel=False):
        if x_dst is None:
            # the self path needs fp32 rows; only the num_dst prefix
            # is ever dequantized host-side on the int8-in path
            with jax.named_scope("gnn.dense"):
                x_dst = (jnp.asarray(
                    x_src.rows(slice(0, g.num_dst)).dequantize())
                    if isinstance(x_src, QuantizedRows)
                    else x_src[:g.num_dst])
        with jax.named_scope("gnn.norm"):
            coef = g.edge_mask.astype(jnp.float32)
        agg = gather_scale_segment_sum(x_src, g.edge_src, g.edge_dst,
                                       coef, g.num_dst,
                                       use_kernel=use_kernel)
        with jax.named_scope("gnn.norm"):
            agg = agg / g.in_deg[:, None]
        return self.update(p, agg, x_dst)


class GATLayer(MessagePassing):
    """GAT [Velickovic+ 2018] as PyG's ``GATConv`` builds it, with the skip
    path of PyG's ogbn-products example (``examples/ogbn_products_gat.py``)::

        Wh      = x_src @ W                   one projection, heads x C wide
        z[e, k] = leaky_relu(a_src[k] . Wh[src e, k] + a_dst[k] . Wh[dst e, k], 0.2)
        alpha   = softmax of z over each destination's in-edges, per head k
        conv[d] = concat_k or mean_k (sum_e alpha[e, k] Wh[src e, k]) + b
        h'[d]   = conv[d] + x_dst[d] @ W_skip + b_skip

    Each destination attends to itself too: the layer drops the graph's
    own self-loops and adds one per destination (PyG's
    ``remove_self_loops`` then ``add_self_loops``), with source row ``i``
    as destination ``i``, since a block's destinations are the prefix of
    its sources.  Hidden layers concatenate the heads; the last layer
    averages them (``concat=False``), which the layer reads from the width
    of its bias.  Attention vectors are ``(1, heads, C)``, PyG's layout.

    Scopes: ``gnn.dense`` (the projection, the attention halves, the
    bias), ``gnn.aggregate`` with the implementation under it and, where
    the softmax runs as operations of its own, ``edge_softmax`` under
    that; ``gnn.skip``."""

    @staticmethod
    def init(key, din, dout, heads: int = 4, concat: bool = True):
        """``dout`` is the layer's output width: ``heads * C`` where the
        heads are concatenated, ``C`` where they are averaged."""
        if concat and dout % heads:
            raise ValueError(f"a concatenating GAT layer of width {dout} "
                             f"cannot split over {heads} heads")
        c = dout // heads if concat else dout
        k1, k2, k3, k4 = jax.random.split(key, 4)
        return {"w": _dense(k1, din, heads * c),
                "a_src": jax.random.normal(k2, (1, heads, c), jnp.float32)
                / np.sqrt(c),
                "a_dst": jax.random.normal(k3, (1, heads, c), jnp.float32)
                / np.sqrt(c),
                "b": jnp.zeros((dout,), jnp.float32),
                "w_skip": _dense(k4, din, dout),
                "b_skip": jnp.zeros((dout,), jnp.float32)}

    def __call__(self, p, g: DeviceGraph, x_src, x_dst=None, *,
                 use_kernel=False):
        if isinstance(x_src, QuantizedRows):
            # attention projects before aggregating, so the int8-in
            # kernel path does not apply — decode up front
            x_src = jnp.asarray(x_src.dequantize())
        prefix = x_dst is None
        if prefix:
            x_dst = x_src[:g.num_dst]
        _, heads, c = p["a_src"].shape
        with jax.named_scope("gnn.dense"):
            hs = x_src @ p["w"]                              # (S, H*C)
            # the destinations' rows are the sources' first rows
            hd = hs[:g.num_dst] if prefix else x_dst @ p["w"]
            es = jnp.sum(hs.reshape(-1, heads, c) * p["a_src"], axis=-1)
            ed = jnp.sum(hd.reshape(-1, heads, c) * p["a_dst"], axis=-1)
        with jax.named_scope("gnn.aggregate"):
            loops = jnp.arange(g.num_dst, dtype=g.edge_src.dtype)
            src = jnp.concatenate([g.edge_src, loops])
            dst = jnp.concatenate([g.edge_dst, loops])
            mask = jnp.concatenate([g.edge_mask & (g.edge_src != g.edge_dst),
                                    jnp.ones((g.num_dst,), bool)])
            if use_kernel:
                # one-pass fused online-softmax kernel where its slabs fit
                # VMEM, else the multi-pass kernel path
                from repro.kernels import ops as kops
                out = kops.gat_attention(hs, es, ed, src, dst, mask,
                                         g.num_dst, heads=heads)
            else:
                with jax.named_scope("jax_ops"):
                    with jax.named_scope("edge_softmax"):
                        logits = jax.nn.leaky_relu(
                            jnp.take(es, src, axis=0)
                            + jnp.take(ed, dst, axis=0), 0.2)   # (E, H)
                        alpha = segment_softmax(logits, dst, g.num_dst,
                                                mask)
                    msgs = (jnp.take(hs.reshape(-1, heads, c), src, axis=0)
                            * alpha[..., None])
                    out = jax.ops.segment_sum(msgs.reshape(-1, heads * c),
                                              dst, g.num_dst)
        with jax.named_scope("gnn.dense"):
            if p["b"].shape[0] != heads * c:                 # head mean
                out = out.reshape(-1, heads, c).mean(axis=1)
            out = out + p["b"]
        with jax.named_scope("gnn.skip"):
            return out + x_dst @ p["w_skip"] + p["b_skip"]


class GINLayer(MessagePassing):
    """GIN: h' = MLP((1 + eps) h + sum(neighbors))."""

    aggregate = "sum"

    @staticmethod
    def init(key, din, dout):
        k1, k2 = jax.random.split(key)
        return {"w1": _dense(k1, din, dout),
                "w2": _dense(k2, dout, dout),
                "b1": jnp.zeros((dout,), jnp.float32),
                "b2": jnp.zeros((dout,), jnp.float32),
                "eps": jnp.zeros((), jnp.float32)}

    def update(self, p, agg, self_feat):
        with jax.named_scope("gnn.dense"):
            h = (1.0 + p["eps"]) * self_feat + agg
            h = jax.nn.relu(h @ p["w1"] + p["b1"])
            return h @ p["w2"] + p["b2"]


class GGNNLayer(MessagePassing):
    """Gated Graph NN [Li+ 2015] (survey Table 5): GRU update over the
    aggregated neighbor messages; dimensions stay constant across layers."""

    aggregate = "sum"

    @staticmethod
    def init(key, din, dout):
        # GG-NN requires din == dout (recurrent state); project if needed
        ks = jax.random.split(key, 4)
        return {"w_msg": _dense(ks[0], dout, dout),
                "w_zrh": _dense(ks[1], dout, 3 * dout),
                "u_zrh": _dense(ks[2], dout, 3 * dout),
                "proj": _dense(ks[3], din, dout) if din != dout else None,
                "b": jnp.zeros((3 * dout,), jnp.float32)}

    def __call__(self, p, g, x_src, x_dst=None, *, use_kernel=False):
        if isinstance(x_src, QuantizedRows):
            x_src = jnp.asarray(x_src.dequantize())   # projects first
        with jax.named_scope("gnn.dense"):
            if p.get("proj") is not None:
                x_src = x_src @ p["proj"]
            if x_dst is None:
                x_dst = x_src[:g.num_dst]
            hm = x_src @ p["w_msg"]
        agg = gather_scale_segment_sum(
            hm, g.edge_src, g.edge_dst,
            g.edge_mask.astype(hm.dtype), g.num_dst,
            use_kernel=use_kernel)
        d = x_dst.shape[-1]
        with jax.named_scope("gnn.dense"):
            gates = agg @ p["w_zrh"] + x_dst @ p["u_zrh"] + p["b"]
            z = jax.nn.sigmoid(gates[:, :d])
            r = jax.nn.sigmoid(gates[:, d:2 * d])
            # candidate uses reset-gated state through the U path
            h_tilde = jnp.tanh(agg @ p["w_zrh"][:, 2 * d:]
                               + (r * x_dst) @ p["u_zrh"][:, 2 * d:])
            return (1 - z) * x_dst + z * h_tilde


class APPNPLayer(MessagePassing):
    """APPNP [Klicpera+ 2019] (PyG's Table 5 list): personalized-PageRank
    propagation h' = (1-α)·Â h + α·h0 (no weights; pair with an MLP head)."""

    aggregate = "sum"

    def __init__(self, alpha: float = 0.1):
        self.alpha = alpha

    @staticmethod
    def init(key, din, dout):
        return {"w": _dense(key, din, dout)}  # used only by the first hop

    def propagate(self, g, h, h0, *, use_kernel=False):
        with jax.named_scope("gnn.norm"):
            coef = (jax.lax.rsqrt(g.out_deg)[g.edge_src]
                    * jax.lax.rsqrt(g.in_deg)[g.edge_dst] * g.edge_mask)
        agg = gather_scale_segment_sum(h, g.edge_src, g.edge_dst, coef,
                                       g.num_dst, use_kernel=use_kernel)
        with jax.named_scope("gnn.dense"):
            return (1 - self.alpha) * agg + self.alpha * h0


LAYER_TYPES = {"gcn": GCNLayer, "sage": SAGELayer, "gat": GATLayer,
               "gin": GINLayer, "ggnn": GGNNLayer}
