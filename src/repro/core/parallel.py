"""Parallelism strategies (survey §3.2.5 / §2.3.1, Tables 2 & 7).

GNN side:
* :func:`p3_layer1` + :func:`make_p3_train_step` — P³'s push-pull hybrid
  [Gandhi & Iyer, OSDI'21]: layer 1 runs *model-parallel over the feature
  dimension* (features never cross the network; only the (N, hidden)
  partial activations are reduce-scattered), deeper layers run data-parallel
  pull.  The survey singles this out (§3.2.5, §4.2).

Transformer side:
* :func:`moe_expert_parallel` — explicit shard_map expert parallelism:
  experts sharded over ``model``; activations replicated over ``model``
  (they already are, post attention), each shard computes only its local
  experts on the tokens routed to them (gather dispatch, real FLOPs only),
  and a single ``psum`` over ``model`` combines.  This is the beyond-
  baseline replacement for the GShard one-hot dispatch in
  ``models/transformer/moe.py`` (§Perf hillclimb #1).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.launch import sharding as shd

AXIS = "g"


# ===========================================================================
# P3 push-pull hybrid parallelism (GNN, full graph)
# ===========================================================================

def p3_layer1(x_fshard, w1_fshard, edge_src, edge_dst, edge_mask, coef,
              n_pad: int, n_local: int):
    """Runs inside shard_map over axis "g".

    x_fshard:  (N_pad, F/n) — every vertex, a slice of the feature dim
    w1_fshard: (F/n, H)     — matching input-dim slice of W1
    Aggregation is fully local (all vertices present); the partial
    (N_pad, H) activations are psum_scatter'd onto vertex owners.
    """
    feat = jnp.take(x_fshard, edge_src, axis=0)
    feat = feat * (coef * edge_mask)[:, None]
    agg = jax.ops.segment_sum(feat, edge_dst, n_pad)        # (N_pad, F/n)
    h_partial = agg @ w1_fshard                             # (N_pad, H)
    # Forward-pass sharding primitive; layer-1 grads stay UN-psummed on
    # purpose (see make_p3_train_step).
    # repro-lint: disable=RL001 -- psum_scatter transpose is all_gather, no double reduction
    return jax.lax.psum_scatter(h_partial, AXIS, scatter_dimension=0,
                                tiled=True)                 # (N_loc, H)


def make_p3_train_step(optimizer, n_dev: int, n_layers: int = 2):
    """Distributed GCN with P3 hybrid parallelism (2-layer reference).

    Inputs (see propagation.ShardedGraph):
      x_f:   (N_pad, F) sharded over the FEATURE dim (model parallel)
      edges: full edge list, replicated (global src, global dst)
      deeper layers: data-parallel pull over vertex shards.
    """
    devs = np.array(jax.devices()[:n_dev])
    mesh = Mesh(devs, (AXIS,))

    def step(params, opt_state, x_f, edge_src, edge_dst, edge_mask, coef,
             labels, lmask):
        n_pad = x_f.shape[0]
        n_local = n_pad // n_dev
        # psum the (parameter-free) count OUTSIDE the differentiated
        # function: under check_vma=False a psum inside loss_fn transposes
        # to a second psum, scaling every gradient by n_dev (the PR 2
        # double-psum class, masked by Adam scale-invariance — see
        # propagation.py; statically enforced by lint rule RL001)
        cnt = jax.lax.psum(jnp.sum(lmask), AXIS)

        def loss_fn(p):
            h = p3_layer1(x_f, p[0]["w"], edge_src, edge_dst, edge_mask,
                          coef, n_pad, n_local) + p[0]["b"]
            h = jax.nn.relu(h)
            for i in range(1, n_layers):
                h_all = jax.lax.all_gather(h @ p[i]["w"], AXIS, tiled=True)
                feat = jnp.take(h_all, edge_src, axis=0)
                feat = feat * (coef * edge_mask)[:, None]
                agg_full = jax.ops.segment_sum(feat, edge_dst, n_pad)
                idx = jax.lax.axis_index(AXIS)
                agg = jax.lax.dynamic_slice_in_dim(
                    agg_full, idx * n_local, n_local, axis=0)
                h = agg + p[i]["b"]
                if i + 1 < n_layers:
                    h = jax.nn.relu(h)
            logz = jax.nn.logsumexp(h, axis=-1)
            gold = jnp.take_along_axis(h, labels[:, None], axis=-1)[:, 0]
            local = jnp.sum((logz - gold) * lmask)
            return local / jnp.maximum(cnt, 1.0)

        local_loss, grads = jax.value_and_grad(loss_fn)(params)
        loss = jax.lax.psum(local_loss, AXIS)
        # replicated params: each device's grad is its local contribution
        # -> SUM across devices.  The feature-sharded layer-1 weight's
        # grad is already complete for its own shard (autodiff through
        # psum_scatter delivers the full cotangent) -> keep as is.
        summed = jax.tree.map(lambda g_: jax.lax.psum(g_, AXIS), grads)
        summed[0]["w"] = grads[0]["w"]
        params, opt_state = optimizer.apply(params, summed, opt_state)
        return params, opt_state, loss

    rep = P()
    pspec = [{"w": P(AXIS, None) if i == 0 else rep, "b": rep}
             for i in range(n_layers)]
    ospec = [{"w": P(AXIS, None) if i == 0 else rep, "b": rep}
             for i in range(n_layers)]
    opt_spec = {"m": pspec, "v": pspec, "step": rep}  # moments mirror params
    smapped = jax.shard_map(
        step, mesh=mesh,
        in_specs=(pspec, opt_spec, P(None, AXIS), rep, rep, rep, rep,
                  P(AXIS), P(AXIS)),
        out_specs=(ospec, opt_spec, rep),
        check_vma=False)
    return mesh, smapped


# ===========================================================================
# expert parallelism via shard_map (transformer MoE hillclimb)
# ===========================================================================

def _local_expert_compute(cfg, x_loc, router, w_gate, w_in, w_out,
                          capacity_factor: float):
    """Inside shard_map: x_loc (T_loc, D) replicated over model; expert
    weights are the LOCAL slice (E_loc, D, F).  Gather-dispatch (no one-hot
    einsums) + psum over 'model' by the caller."""
    E = cfg.num_experts
    k = cfg.experts_per_token
    m_idx = jax.lax.axis_index("model")
    E_loc = w_in.shape[0]
    T = x_loc.shape[0]
    C = max(1, int(np.ceil(T * k / E * capacity_factor)))

    logits = jnp.einsum("td,de->te", x_loc.astype(jnp.float32), router)
    gates = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(gates, k)
    w = w / jnp.sum(w, axis=-1, keepdims=True)

    flat_e = idx.reshape(T * k)
    local_e = flat_e - m_idx * E_loc
    is_local = (local_e >= 0) & (local_e < E_loc)

    # position within each local expert queue (cumsum over flat order)
    onehot = jax.nn.one_hot(jnp.where(is_local, local_e, E_loc), E_loc + 1,
                            dtype=jnp.int32)[:, :E_loc]       # (T*k, E_loc)
    pos = jnp.cumsum(onehot, axis=0) - onehot
    pos_of = jnp.sum(pos * onehot, axis=-1)
    keep = is_local & (pos_of < C)
    slot = jnp.where(keep, local_e * C + pos_of, E_loc * C)

    src = jnp.full((E_loc * C + 1,), T, jnp.int32)
    src = src.at[slot].set(jnp.arange(T * k, dtype=jnp.int32) // k)
    src = src[:E_loc * C]
    x_pad = jnp.concatenate([x_loc, jnp.zeros((1, x_loc.shape[1]),
                                              x_loc.dtype)])
    xe = jnp.take(x_pad, src, axis=0).reshape(E_loc, C, -1)

    h = jnp.einsum("ecd,edf->ecf", xe, w_in)
    hg = jnp.einsum("ecd,edf->ecf", xe, w_gate)
    act = jax.nn.silu if cfg.act == "silu" else (
        lambda v: jax.nn.gelu(v, approximate=True))
    ye = jnp.einsum("ecf,efd->ecd", act(hg) * h, w_out)

    ye_flat = jnp.concatenate([ye.reshape(E_loc * C, -1),
                               jnp.zeros((1, ye.shape[-1]), ye.dtype)])
    contrib = jnp.take(ye_flat, jnp.minimum(slot, E_loc * C), axis=0)
    wk = (w.reshape(T * k) * keep).astype(contrib.dtype)
    y = jnp.sum((contrib * wk[:, None]).reshape(T, k, -1), axis=1)
    return y  # partial: only local experts' contributions


def moe_expert_parallel(cfg, p, x, *, capacity_factor: float = 1.25):
    """Drop-in replacement for moe.moe_block using explicit shard_map EP.

    Requires active ShardingRules (shd context).  Falls back to the
    gathered single-device path when no rules are installed (smoke tests).
    """
    rules = shd._ACTIVE.get()
    if rules is None:
        from repro.models.transformer.moe import moe_block_gathered
        return moe_block_gathered(cfg, p, x,
                                  capacity_factor=capacity_factor)

    mesh = rules.mesh
    B, S, D = x.shape
    batch_ax = rules.batch_axis

    def inner(x_in, router, w_gate, w_in, w_out):
        T_loc = x_in.shape[0] * x_in.shape[1]
        y = _local_expert_compute(cfg, x_in.reshape(T_loc, D), router,
                                  w_gate, w_in, w_out, capacity_factor)
        y = jax.lax.psum(y, "model")
        return y.reshape(x_in.shape)

    xspec = P(batch_ax, None, None)
    out = jax.shard_map(
        inner, mesh=mesh,
        in_specs=(xspec, P(None, None), P("model", None, None),
                  P("model", None, None), P("model", None, None)),
        out_specs=xspec,
        check_vma=False,
    )(x, p["router"], p["w_gate"], p["w_in"], p["w_out"])

    if cfg.num_shared_experts:
        from repro.models.transformer import layers as L
        out = out + L.mlp(cfg, x, p["shared"])
    return out
