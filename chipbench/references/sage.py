"""Plain float32 reference of GraphSAGE-mean over sampled blocks.

Layer ``l`` over block ``l`` (innermost first), for each destination ``d``
with in-block neighbours ``N(d)``:

    agg[d]  = sum_{s in N(d)} h[s] / max(|N(d)|, 1)
    h'[d]   = h[d] @ W_self + agg[d] @ W_nbr + b

with ReLU between layers and a masked mean negative log-likelihood on the
last layer's logits (Hamilton et al. 2017, the mean aggregator; no
normalisation of ``h'`` and no dropout, as the configuration states).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.references import common


def init(cfg: dict, key):
    """Parameters in the program's layout: one ``{w_self, w_nbr, b}`` per
    layer, weights N(0, 1/fan_in), biases zero."""
    m = cfg["model"]
    dims = ([m["in_features"]] + [m["hidden"]] * (m["layers"] - 1)
            + [m["classes"]])
    params = []
    for i in range(m["layers"]):
        k1, k2 = jax.random.split(jax.random.fold_in(key, i))
        params.append({
            "w_self": common.normal_init(k1, (dims[i], dims[i + 1]), dims[i]),
            "w_nbr": common.normal_init(k2, (dims[i], dims[i + 1]), dims[i]),
            "b": jnp.zeros((dims[i + 1],), jnp.float32)})
    return params


def loss(params, batch, precision: str):
    """``batch``: ``blocks`` (each ``src``, ``dst``, ``mask`` per edge and
    ``dst_rows``, a (num_dst,) array that fixes the block's width), the
    input rows ``x`` of block 0's sources, ``labels`` and ``label_mask``."""
    h = batch["x"]
    n_layers = len(params)
    for i, (p, b) in enumerate(zip(params, batch["blocks"])):
        n_dst = b["dst_rows"].shape[0]
        mask = b["mask"].astype(jnp.float32)
        agg = common.aggregate(h, b["src"], b["dst"], mask, n_dst,
                               chunk=b["src"].shape[0])
        deg = jax.ops.segment_sum(mask, b["dst"], n_dst)
        agg = agg / jnp.maximum(deg, 1.0)[:, None]
        h = (common.mm(h[:n_dst], p["w_self"], precision)
             + common.mm(agg, p["w_nbr"], precision) + p["b"])
        if i + 1 < n_layers:
            h = jax.nn.relu(h)
    return common.masked_nll(h, batch["labels"], batch["label_mask"])


def work(cfg: dict, counts: list) -> dict:
    """Work of one training step, counted over real rows and edges only
    (no padding, no one-hot products).  ``counts``: per layer ``(n_dst,
    n_src, n_edges)``, innermost first.  The first layer's aggregation
    needs no backward pass: its input rows carry no gradient."""
    from chipbench import work as W
    m = cfg["model"]
    dims = ([m["in_features"]] + [m["hidden"]] * (m["layers"] - 1)
            + [m["classes"]])
    dense = agg_flops = agg_bytes = 0.0
    for i, (n_dst, n_src, n_edges) in enumerate(counts):
        backward = i > 0
        # two products (self and neighbour): forward, weight gradient and,
        # past the first layer, input gradient
        dense += 2 * W.dense_flops(n_dst, dims[i], dims[i + 1]) * (
            3 if backward else 2)
        agg_flops += W.aggregation_flops(n_edges, dims[i], backward=backward)
        agg_bytes += W.aggregation_bytes(n_edges, dims[i], n_src, n_dst,
                                         backward=backward)
    return {"model_flops": dense + agg_flops, "aggregation_flops": agg_flops,
            "aggregation_bytes": agg_bytes}
