"""GNN models: stacks of abstraction-layer GNN layers, usable in
full-graph mode (one DeviceGraph) or mini-batch mode (list of Blocks).
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.abstraction import DeviceGraph, gather_scale_segment_sum
from repro.models.gnn.layers import LAYER_TYPES


@dataclasses.dataclass(frozen=True)
class GNNConfig:
    arch: str = "gcn"                 # gcn | sage | gat | gin | ggnn | appnp
    feat_dim: int = 64
    hidden: int = 128
    num_classes: int = 8
    num_layers: int = 2
    gat_heads: int = 4                # GAT: hidden = gat_heads * head width
    appnp_k: int = 4                  # APPNP propagation hops
    appnp_alpha: float = 0.1
    use_kernel: bool = False          # Pallas segment-sum for aggregation
    wire_codec: str = "fp32"          # comm-plane codec: fp32 | bf16 | int8


def init_gnn(cfg: GNNConfig, key) -> List[dict]:
    if cfg.arch == "appnp":
        # MLP head (feat -> hidden -> classes), then weightless propagation
        from repro.models.gnn.layers import _dense
        return [{"w": _dense(jax.random.fold_in(key, 0), cfg.feat_dim,
                             cfg.hidden)},
                {"w": _dense(jax.random.fold_in(key, 1), cfg.hidden,
                             cfg.num_classes)}]
    layer_cls = LAYER_TYPES[cfg.arch]
    dims = ([cfg.feat_dim] + [cfg.hidden] * (cfg.num_layers - 1)
            + [cfg.num_classes])
    params = []
    for i in range(cfg.num_layers):
        k = jax.random.fold_in(key, i)
        if cfg.arch == "gat":
            # hidden layers concatenate their heads, the last averages them
            params.append(layer_cls.init(k, dims[i], dims[i + 1],
                                         heads=cfg.gat_heads,
                                         concat=i + 1 < cfg.num_layers))
        else:
            params.append(layer_cls.init(k, dims[i], dims[i + 1]))
    return params


def _make_layer(cfg: GNNConfig):
    return LAYER_TYPES[cfg.arch]()


def _activation(cfg: GNNConfig):
    """The nonlinearity between layers: ELU for GAT, as published; ReLU
    for the rest."""
    return jax.nn.elu if cfg.arch == "gat" else jax.nn.relu


def forward_full(cfg: GNNConfig, params, g: DeviceGraph, x) -> jax.Array:
    """Full-graph forward (NeuGraph/ROC style, no sampling)."""
    if cfg.arch == "appnp":
        from repro.models.gnn.layers import APPNPLayer
        layer = APPNPLayer(cfg.appnp_alpha)
        with jax.named_scope("gnn.dense"):
            h = jax.nn.relu(x @ params[0]["w"]) @ params[1]["w"]
        h0 = h
        for _ in range(cfg.appnp_k):
            h = layer.propagate(g, h, h0, use_kernel=cfg.use_kernel)
        return h
    layer, act = _make_layer(cfg), _activation(cfg)
    h = x
    for i, p in enumerate(params):
        h = layer(p, g, h, use_kernel=cfg.use_kernel)
        if i + 1 < len(params):
            with jax.named_scope("gnn.dense"):
                h = act(h)
    return h


def forward_blocks(cfg: GNNConfig, params, blocks: Sequence[DeviceGraph],
                   x_input) -> jax.Array:
    """Mini-batch forward over sampled bipartite blocks (DistDGL style).
    ``x_input``: features of blocks[0].src_nodes."""
    layer, act = _make_layer(cfg), _activation(cfg)
    h = x_input
    for i, (p, g) in enumerate(zip(params, blocks)):
        h = layer(p, g, h, use_kernel=cfg.use_kernel)
        if i + 1 < len(params):
            with jax.named_scope("gnn.dense"):
                h = act(h)
    return h


def forward_stale(params, h_own, sg_local, ghosts, refresh, own_rows,
                  *, axis: str = "g", use_kernel: bool = False,
                  codec=None, residuals=None):
    """Staleness-bounded full-graph GCN forward (runs under ``shard_map``).

    The asynchronous counterpart of
    :func:`repro.core.propagation.gcn_forward_local`: layer ``i >= 1``
    aggregates *historical* activations for ghost sources (per-layer stale
    planes from a :class:`repro.core.halo.HaloExchange`) and fresh
    activations only for owned rows and the rows this step's refresh plan
    exchanges synchronously.  Layer 0 consumes the static input features,
    which never go stale.

    Args:
        params: per-layer GCN params ``[{"w", "b"}, ...]``.
        h_own: ``(n_local, F)`` this device's owned input features.
        sg_local: ``(es, ed, em, indeg_l, outdeg_all, n_local)`` — the
            per-device pull edge slices, local in-degree, replicated global
            out-degree, and owned-row count (``ShardedGraph`` layout; pad
            edges are masked out by ``em`` so pad rows never aggregate).
        ghosts: per-layer ``(N_pad, F_l)`` replicated stale activation
            planes, innermost first (layer ``l`` plane feeds layer ``l+1``).
        refresh: per-layer ``(N_pad,)`` bool — rows served *fresh* this
            step (this step's synchronous exchange).  All-True degrades
            exactly to the synchronous pull forward.
        own_rows: ``(N_pad,)`` bool — rows this device owns (always fresh).
        axis: mesh axis name (default ``"g"``).
        use_kernel: aggregate through the fused Pallas
            gather-scale-segment-sum kernel instead of XLA take +
            ``jax.ops.segment_sum``.
        codec: optional :class:`repro.core.comm.WireCodec`.  Under a
            lossy codec, a refreshed row's sender quantizes the plane on
            the wire (``codec.jax_qdq``; with error feedback iff
            ``codec.error_feedback``), so every device that does *not*
            own the row — refreshed or stale — reads the decoded wire
            value; the owner keeps its exact local activations.
            ``None`` or the identity fp32 codec compiles the exact
            pre-codec computation (bit-identical jaxpr).
        residuals: per-layer ``(N_pad, F_l)`` error-feedback residuals
            (required iff ``codec.error_feedback``, e.g. int8): the
            sender adds them before quantizing and the returned
            residuals carry ``pre - decoded`` for rows refreshed this
            step.  Codecs without feedback (bf16) quantize statelessly,
            matching the host :class:`~repro.core.comm.Transport`.

    Returns:
        ``(h, planes, residuals_out)`` — ``h`` is the ``(n_local,
        num_classes)`` output for owned rows; ``planes`` are the global
        layer outputs ``h_0 .. h_{L-2}`` *as they crossed the wire*
        (codec-decoded; exact under fp32) for the host to write back into
        the ghost buffers at the refreshed rows; ``residuals_out`` the
        updated error-feedback state (``()`` under an exact codec).

    Gradient semantics: stale rows enter as constants (no gradient flows
    into the buffers); refreshed rows participate in the synchronous
    all-gather and carry exact gradients — under a lossy codec via a
    straight-through estimator (the wire value enters the forward, the
    gradient of the unquantized activation flows back).  The S=0 fp32
    case is bitwise the synchronous step.
    """
    es, ed, em, indeg_l, outdeg_all, n_local = sg_local
    quantize = codec is not None and not codec.identity
    h = h_own
    planes = []
    res_out = []
    n_layers = len(params)
    for i, p in enumerate(params):
        h_all_fresh = jax.lax.all_gather(h, axis, tiled=True)  # (N_pad, F)
        if i == 0:
            h_all = h_all_fresh          # static inputs: never stale
        elif not quantize:
            planes.append(h_all_fresh)   # global layer-(i-1) output
            use_fresh = refresh[i - 1] | own_rows
            h_all = jnp.where(use_fresh[:, None], h_all_fresh,
                              ghosts[i - 1])
        else:
            mask = refresh[i - 1][:, None]
            if codec.error_feedback:
                # sender-side error feedback before quantizing the wire
                # plane; residuals advance only for rows sent this step
                res = residuals[i - 1]
                pre = h_all_fresh + jax.lax.stop_gradient(res)
                dec_raw = codec.jax_qdq(pre)
                res_out.append(jax.lax.stop_gradient(
                    jnp.where(mask, pre - dec_raw, res)))
            else:                        # stateless codec (bf16)
                dec_raw = codec.jax_qdq(h_all_fresh)
            # straight-through: forward sees the wire value, backward the
            # exact all-gather (refreshed rows keep exact gradients)
            dec = h_all_fresh + jax.lax.stop_gradient(dec_raw - h_all_fresh)
            planes.append(dec)           # wire view: what receivers store
            h_all = jnp.where(own_rows[:, None], h_all_fresh,
                              jnp.where(mask, dec, ghosts[i - 1]))
        with jax.named_scope("gnn.dense"):
            hw = h_all @ p["w"]
        with jax.named_scope("gnn.norm"):
            coef = (jax.lax.rsqrt(jnp.take(outdeg_all, es))
                    * jax.lax.rsqrt(jnp.take(indeg_l, ed)) * em)
        h = gather_scale_segment_sum(hw, es, ed, coef, n_local,
                                     use_kernel=use_kernel)
        with jax.named_scope("gnn.dense"):
            h = h + p["b"]
            if i + 1 < n_layers:
                h = jax.nn.relu(h)
    return h, planes, tuple(res_out)


def forward_blocks_cached(cfg: GNNConfig, params,
                          inner_blocks: Sequence[DeviceGraph],
                          outer_block: DeviceGraph, x_input,
                          cached_h, fresh_mask):
    """Serving forward with historical-embedding splice (GNNAutoScale).

    Computes the first ``L-1`` layers over the (possibly miss-restricted)
    inner blocks, then replaces rows of the final-layer input with cached
    historical embeddings where ``fresh_mask`` holds, and applies the last
    layer over ``outer_block``.  Returns ``(logits, h_fresh)`` where
    ``h_fresh`` is the pre-splice hidden state — the rows to write back for
    cache misses.  Shapes are static per (bucket, fanouts), so each bucket
    compiles once."""
    layer, act = _make_layer(cfg), _activation(cfg)
    h = x_input
    for i in range(len(params) - 1):
        h = layer(params[i], inner_blocks[i], h, use_kernel=cfg.use_kernel)
        h = act(h)
    h_fresh = h
    h = jnp.where(fresh_mask[:, None], cached_h, h_fresh)
    logits = layer(params[-1], outer_block, h, use_kernel=cfg.use_kernel)
    return logits, h_fresh


def nll_sum_count(logits, labels, mask):
    """Masked NLL as an (unnormalized sum, count) pair — the combinable
    form a distributed step psums across partitions before dividing, so
    the global mean is identical to the single-device mean regardless of
    how seeds were split."""
    with jax.named_scope("gnn.loss"):
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
        nll = logz - gold
        return jnp.sum(nll * mask), jnp.sum(mask)


def nll_loss(logits, labels, mask=None):
    if mask is None:
        mask = jnp.ones(labels.shape, logits.dtype)
    total, cnt = nll_sum_count(logits, labels, mask)
    with jax.named_scope("gnn.loss"):
        return total / jnp.maximum(cnt, 1.0)


def accuracy(logits, labels, mask=None):
    correct = (jnp.argmax(logits, -1) == labels).astype(jnp.float32)
    if mask is not None:
        return jnp.sum(correct * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(correct)


def make_fullgraph_train_step(cfg: GNNConfig, optimizer):
    def step(params, opt_state, g: DeviceGraph, x, labels, mask):
        def loss_fn(p):
            logits = forward_full(cfg, p, g, x)
            return nll_loss(logits, labels, mask)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        params, opt_state = optimizer.apply(params, grads, opt_state)
        return params, opt_state, loss

    return step


def make_minibatch_train_step(cfg: GNNConfig, optimizer):
    def step(params, opt_state, blocks, x_input, labels, mask):
        def loss_fn(p):
            logits = forward_blocks(cfg, p, blocks, x_input)
            return nll_loss(logits, labels, mask)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        params, opt_state = optimizer.apply(params, grads, opt_state)
        return params, opt_state, loss

    return step
