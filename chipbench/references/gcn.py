"""Plain float32 reference of full-graph GCN (Kipf and Welling 2017).

Each layer, over the graph's edges (self-loops included in the graph):

    h'[d] = sum_{e: dst_e = d} h[src_e] @ W / sqrt(out_deg[src_e] in_deg[d]) + b

with ReLU between layers and a masked mean negative log-likelihood over the
training nodes.  Degrees count the graph's own edges, self-loops included,
as ``GCNConv`` normalises ``A + I``.  The aggregation runs in edge chunks so
that it fits beside the program's leftovers.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.references import common

EDGE_CHUNK = 1 << 18


def init(cfg: dict, key):
    """Parameters in the program's layout: one ``{w, b}`` per layer,
    weights N(0, 1/fan_in), biases zero."""
    m = cfg["model"]
    dims = ([m["in_features"]] + [m["hidden"]] * (m["layers"] - 1)
            + [m["classes"]])
    return [{"w": common.normal_init(jax.random.fold_in(key, i),
                                     (dims[i], dims[i + 1]), dims[i]),
             "b": jnp.zeros((dims[i + 1],), jnp.float32)}
            for i in range(m["layers"])]


def loss(params, batch, precision: str):
    """``batch``: ``src``/``dst`` per edge, node features ``x``,
    ``labels`` and ``label_mask``."""
    src, dst, x = batch["src"], batch["dst"], batch["x"]
    n = x.shape[0]
    ones = jnp.ones(src.shape, jnp.float32)
    out_deg = jnp.maximum(jax.ops.segment_sum(ones, src, n), 1.0)
    in_deg = jnp.maximum(jax.ops.segment_sum(ones, dst, n), 1.0)
    coef = jax.lax.rsqrt(out_deg[src]) * jax.lax.rsqrt(in_deg[dst])
    h = x
    for i, p in enumerate(params):
        hw = common.mm(h, p["w"], precision)
        h = common.aggregate(hw, src, dst, coef, n, EDGE_CHUNK) + p["b"]
        if i + 1 < len(params):
            h = jax.nn.relu(h)
    return common.masked_nll(h, batch["labels"], batch["label_mask"])


def work(cfg: dict, counts: tuple) -> dict:
    """Work of one full-graph step; ``counts`` is ``(n_nodes, n_edges)``.
    Each layer's product ``h @ W`` runs forward, for the weight gradient
    and, past the first layer, for the input gradient; each layer's
    aggregation runs forward and backward, since its input ``h @ W``
    depends on a weight."""
    from chipbench import work as W
    m = cfg["model"]
    dims = ([m["in_features"]] + [m["hidden"]] * (m["layers"] - 1)
            + [m["classes"]])
    n_nodes, n_edges = counts
    dense = agg_flops = agg_bytes = 0.0
    for i in range(m["layers"]):
        dense += W.dense_flops(n_nodes, dims[i], dims[i + 1]) * (
            3 if i > 0 else 2)
        agg_flops += W.aggregation_flops(n_edges, dims[i + 1], backward=True)
        agg_bytes += W.aggregation_bytes(n_edges, dims[i + 1], n_nodes,
                                         n_nodes, backward=True)
    return {"model_flops": dense + agg_flops, "aggregation_flops": agg_flops,
            "aggregation_bytes": agg_bytes}
