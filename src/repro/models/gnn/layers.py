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
                                    segment_softmax, segment_sum)
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
    """Single-projection multi-head GAT with per-destination softmax."""

    def __init__(self, heads: int = 4):
        self.heads = heads

    @staticmethod
    def init(key, din, dout, heads: int = 4):
        hd = dout // heads
        k1, k2, k3 = jax.random.split(key, 3)
        return {"w": _dense(k1, din, dout),
                "a_src": jax.random.normal(k2, (heads, hd), jnp.float32) * 0.1,
                "a_dst": jax.random.normal(k3, (heads, hd), jnp.float32) * 0.1}

    def __call__(self, p, g: DeviceGraph, x_src, x_dst=None, *,
                 use_kernel=False):
        if isinstance(x_src, QuantizedRows):
            # attention projects before aggregating, so the int8-in
            # kernel path does not apply — decode up front
            x_src = jnp.asarray(x_src.dequantize())
        if x_dst is None:
            x_dst = x_src[:g.num_dst]
        heads, hd = p["a_src"].shape
        with jax.named_scope("gnn.dense"):
            hs = (x_src @ p["w"]).reshape(-1, heads, hd)
            hdst = (x_dst @ p["w"]).reshape(-1, heads, hd)
            es = jnp.einsum("nhd,hd->nh", hs, p["a_src"])
            ed = jnp.einsum("nhd,hd->nh", hdst, p["a_dst"])
        with jax.named_scope("gnn.aggregate"):
            if use_kernel:
                # one-pass fused online-softmax kernel: edge logits and
                # alphas never reach HBM (falls back to the multi-pass
                # kernel path when the VMEM capacity predicate says no)
                from repro.kernels import ops as kops
                return kops.gat_attention(
                    hs.reshape(-1, heads * hd), es, ed, g.edge_src,
                    g.edge_dst, g.edge_mask, g.num_dst, heads=heads)
            logits = jax.nn.leaky_relu(
                jnp.take(es, g.edge_src, axis=0)
                + jnp.take(ed, g.edge_dst, axis=0), 0.2)    # (E, heads)
            alpha = segment_softmax(logits, g.edge_dst, g.num_dst,
                                    g.edge_mask, use_kernel=use_kernel)
            msgs = jnp.take(hs, g.edge_src, axis=0) * alpha[..., None]
            return segment_sum(msgs.reshape(-1, heads * hd), g.edge_dst,
                               g.num_dst, use_kernel=use_kernel)


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
