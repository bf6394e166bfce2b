"""Plain float32 reference of GAT over sampled blocks, as PyG's
``GATConv`` and its ogbn-products example build it.

Layer ``l`` over block ``l`` (innermost first), with ``H`` heads of width
``C``, sources ``s``, destinations ``d`` (the first ``n_dst`` sources) and
each destination's in-edges plus one self-loop (source row ``d``; an edge
of the graph from a node to itself is dropped first, as PyG's
``remove_self_loops`` then ``add_self_loops`` do):

    Wh[s]      = h[s] @ W                                   (H x C)
    z[e, k]    = leaky_relu(a_src[k] . Wh[src e, k] + a_dst[k] . Wh[dst e, k], 0.2)
    alpha[e,k] = exp(z[e, k]) / sum over the edges e' into dst e of exp(z[e', k])
    conv[d]    = concat_k (hidden layers) or mean_k (last layer) of
                 sum over the edges e into d of alpha[e, k] Wh[src e, k],  + b
    h'[d]      = conv[d] + h[d] @ W_skip + b_skip

with ELU between layers and a masked mean negative log-likelihood on the
last layer's logits (Velickovic et al. 2018; no dropout, as the
configuration states).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.references import common

EDGE_CHUNK = 1 << 18
# per edge and head: the logit (an add and the leaky slope) and the softmax
# (subtract the maximum, exponentiate, add to the denominator, divide)
EDGE_HEAD_FLOPS = 6
ID_BYTES = 2 * 4             # source and destination id of an edge


def _widths(cfg: dict) -> list:
    """``(din, heads, C, concat)`` of each layer."""
    m = cfg["model"]
    heads = m["heads"]
    out, din = [], m["in_features"]
    for i in range(m["layers"]):
        last = i + 1 == m["layers"]
        c = m["classes"] if last else m["hidden"] // heads
        out.append((din, heads, c, not last))
        din = heads * c
    return out


def init(cfg: dict, key):
    """Parameters in the program's layout: per layer ``w`` (din, H*C),
    ``a_src`` and ``a_dst`` (1, H, C), ``b``, ``w_skip`` (din, out) and
    ``b_skip``; weights N(0, 1/fan_in), attention vectors N(0, 1/C),
    biases zero."""
    params = []
    for i, (din, heads, c, concat) in enumerate(_widths(cfg)):
        dout = heads * c if concat else c
        k1, k2, k3, k4 = jax.random.split(jax.random.fold_in(key, i), 4)
        params.append({
            "w": common.normal_init(k1, (din, heads * c), din),
            "a_src": common.normal_init(k2, (1, heads, c), c),
            "a_dst": common.normal_init(k3, (1, heads, c), c),
            "b": jnp.zeros((dout,), jnp.float32),
            "w_skip": common.normal_init(k4, (din, dout), din),
            "b_skip": jnp.zeros((dout,), jnp.float32)})
    return params


def _layer(p, h, b, precision: str):
    n_dst = b["dst_rows"].shape[0]
    _, heads, c = p["a_src"].shape
    wh = common.mm(h, p["w"], precision)                    # (S, H*C)
    whr = wh.reshape(-1, heads, c)
    es = jnp.sum(whr * p["a_src"], axis=-1)                 # (S, H)
    ed = jnp.sum(whr[:n_dst] * p["a_dst"], axis=-1)         # (n_dst, H)
    loops = jnp.arange(n_dst, dtype=jnp.int32)
    src = jnp.concatenate([b["src"], loops])
    dst = jnp.concatenate([b["dst"], loops])
    valid = jnp.concatenate([b["mask"] & (b["src"] != b["dst"]),
                             jnp.ones((n_dst,), bool)])
    z = jax.nn.leaky_relu(es[src] + ed[dst], 0.2)
    z = jnp.where(valid[:, None], z, -jnp.inf)
    top = jax.ops.segment_max(z, dst, n_dst)     # finite: every d has a loop
    ex = jnp.where(valid[:, None], jnp.exp(z - top[dst]), 0.0)
    alpha = ex / jax.ops.segment_sum(ex, dst, n_dst)[dst]
    heads_out = [common.aggregate(wh[:, k * c:(k + 1) * c], src, dst,
                                  alpha[:, k], n_dst, EDGE_CHUNK)
                 for k in range(heads)]
    if p["b"].shape[0] == heads * c:
        conv = jnp.concatenate(heads_out, axis=1)
    else:
        conv = sum(heads_out) / heads
    return (conv + p["b"] + common.mm(h[:n_dst], p["w_skip"], precision)
            + p["b_skip"])


def loss(params, batch, precision: str):
    """``batch``: ``blocks`` (each ``src``, ``dst``, ``mask`` per edge and
    ``dst_rows``, a (num_dst,) array that fixes the block's width), the
    input rows ``x`` of block 0's sources, ``labels`` and ``label_mask``."""
    h = batch["x"]
    for i, (p, b) in enumerate(zip(params, batch["blocks"])):
        h = _layer(p, h, b, precision)
        if i + 1 < len(params):
            h = jax.nn.elu(h)
    return common.masked_nll(h, batch["labels"], batch["label_mask"])


def attention_work(n_dst: int, n_src: int, n_edges: int, heads: int,
                   c: int) -> tuple:
    """``(flops, bytes)`` of one layer's edge attention, forward and
    backward, over its ``n_edges`` sampled edges and one self-loop per
    destination.  Forward, per edge and head the logit and the softmax,
    and per edge the weighted sum (2·H·C) over one H·C source row read and
    the edge's ids; one H·C row written per destination.  Backward, the
    transpose: per edge the softmax's backward, the gradient of the weight
    (a dot of the source row with the destination's gradient row) and of
    the source row (2·H·C each), reading both rows and the ids; one H·C
    gradient row written per source.  Every layer needs the backward, the
    first too: its sources are ``x @ W``, which depends on a weight."""
    e = n_edges + n_dst
    hc = heads * c
    flops = e * heads * EDGE_HEAD_FLOPS + 2.0 * e * hc          # forward
    flops += e * heads * EDGE_HEAD_FLOPS + 4.0 * e * hc         # backward
    nbytes = e * (4 * hc + ID_BYTES) + 4 * n_dst * hc           # forward
    nbytes += e * (8 * hc + ID_BYTES) + 4 * n_src * hc          # backward
    return flops, float(nbytes)


def work(cfg: dict, counts: list) -> dict:
    """Work of one training step, counted over real rows and edges only.
    ``counts``: per layer ``(n_dst, n_src, n_edges)``, innermost first.
    Dense: the projection of every source, the attention halves of the
    sources and destinations and the skip product of the destinations,
    forward, for the weight gradient and, past the first layer, for the
    input gradient; plus the edge attention (:func:`attention_work`)."""
    from chipbench import work as W
    dense = att_flops = att_bytes = 0.0
    for i, ((din, heads, c, concat), (n_dst, n_src, n_edges)) in enumerate(
            zip(_widths(cfg), counts)):
        dout = heads * c if concat else c
        passes = 3 if i > 0 else 2
        dense += passes * (W.dense_flops(n_src, din, heads * c)
                           + W.dense_flops(n_dst, din, dout))
        dense += passes * 2.0 * (n_src + n_dst) * heads * c     # a . Wh
        f, b = attention_work(n_dst, n_src, n_edges, heads, c)
        att_flops += f
        att_bytes += b
    return {"model_flops": dense + att_flops, "attention_flops": att_flops,
            "attention_bytes": att_bytes}
