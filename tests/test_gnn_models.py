"""GNN model tests: abstraction equivalences, full-batch vs blocks,
learning on planted communities, kernel-path equality."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import sampling as S
from repro.core.abstraction import DeviceGraph, saga_layer, segment_softmax
from repro.models.gnn import model as GM
from repro.models.gnn.layers import LAYER_TYPES
from repro.models.gnn.model import GNNConfig
from repro.optim import AdamW


@pytest.fixture(scope="module")
def sbm_graph(graph):
    return graph("sbm", 240)


@pytest.mark.parametrize("arch", ["gcn", "sage", "gat", "gin", "ggnn",
                                  "appnp"])
def test_forward_shapes(sbm_graph, arch):
    cfg = GNNConfig(arch=arch, feat_dim=16, hidden=32,
                    num_classes=sbm_graph.num_classes)
    params = GM.init_gnn(cfg, jax.random.PRNGKey(0))
    dg = DeviceGraph.from_graph(sbm_graph)
    x = jnp.asarray(sbm_graph.features)
    logits = GM.forward_full(cfg, params, dg, x)
    assert logits.shape == (sbm_graph.num_nodes, 4)
    assert not np.any(np.isnan(np.asarray(logits)))


@pytest.mark.parametrize("arch", ["gcn", "sage", "gin", "gat"])
def test_kernel_path_matches_reference(sbm_graph, arch):
    cfg_ref = GNNConfig(arch=arch, feat_dim=16, hidden=32, num_classes=4)
    cfg_k = GNNConfig(arch=arch, feat_dim=16, hidden=32, num_classes=4,
                      use_kernel=True)
    params = GM.init_gnn(cfg_ref, jax.random.PRNGKey(0))
    dg = DeviceGraph.from_graph(sbm_graph)
    x = jnp.asarray(sbm_graph.features)
    a = GM.forward_full(cfg_ref, params, dg, x)
    b = GM.forward_full(cfg_k, params, dg, x)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-3,
                               rtol=1e-3)


def test_fullgraph_training_learns(sbm_graph):
    """End-to-end: GCN on planted communities reaches high train accuracy
    (the survey's node-classification task family, Table 9)."""
    cfg = GNNConfig(arch="gcn", feat_dim=16, hidden=32, num_classes=4)
    params = GM.init_gnn(cfg, jax.random.PRNGKey(0))
    opt = AdamW(lr=1e-2, weight_decay=0.0)
    ostate = opt.init(params)
    dg = DeviceGraph.from_graph(sbm_graph)
    x = jnp.asarray(sbm_graph.features)
    y = jnp.asarray(sbm_graph.labels)
    mask = jnp.ones_like(y, jnp.float32)
    step = jax.jit(GM.make_fullgraph_train_step(cfg, opt))
    losses = []
    for _ in range(60):
        params, ostate, loss = step(params, ostate, dg, x, y, mask)
        losses.append(float(loss))
    logits = GM.forward_full(cfg, params, dg, x)
    acc = float(GM.accuracy(logits, y))
    assert losses[-1] < losses[0] * 0.5
    assert acc > 0.9


def test_blocks_on_full_graph_match_fullbatch(sbm_graph):
    """A block covering the whole graph must reproduce full-batch output —
    ties the sampling path to the full-graph path."""
    cfg = GNNConfig(arch="sage", feat_dim=16, hidden=32, num_classes=4,
                    num_layers=2)
    params = GM.init_gnn(cfg, jax.random.PRNGKey(1))
    dg = DeviceGraph.from_graph(sbm_graph)
    x = jnp.asarray(sbm_graph.features)
    full = GM.forward_full(cfg, params, dg, x)
    blocks = [dg, dg]           # identity blocks: src == dst == all nodes
    via_blocks = GM.forward_blocks(cfg, params, blocks, x)
    np.testing.assert_allclose(np.asarray(full), np.asarray(via_blocks),
                               atol=1e-4, rtol=1e-4)


def test_minibatch_training_learns(sbm_graph):
    cfg = GNNConfig(arch="sage", feat_dim=16, hidden=32, num_classes=4)
    params = GM.init_gnn(cfg, jax.random.PRNGKey(2))
    opt = AdamW(lr=1e-2, weight_decay=0.0)
    ostate = opt.init(params)
    sampler = S.NeighborSampler(sbm_graph, [5, 5], seed=0)
    step = jax.jit(GM.make_minibatch_train_step(cfg, opt))
    rng = np.random.default_rng(0)
    first = last = None
    for it in range(40):
        seeds = rng.choice(sbm_graph.num_nodes, 32, replace=False)
        mb = sampler.sample(seeds)
        blocks = [DeviceGraph.from_block(b) for b in mb.blocks]
        x_in = jnp.asarray(
            sbm_graph.features[np.maximum(mb.blocks[0].src_nodes, 0)])
        y = jnp.asarray(sbm_graph.labels[seeds])
        mask = jnp.ones_like(y, jnp.float32)
        params, ostate, loss = step(params, ostate, blocks, x_in, y, mask)
        if it == 0:
            first = float(loss)
        last = float(loss)
    assert last < first


def test_neighbor_sampler_shapes_fixed_and_step_compiles_once(sbm_graph):
    """Block shapes depend only on (batch, fanouts), so ten batches share
    one compiled training step."""
    cfg = GNNConfig(arch="sage", feat_dim=16, hidden=32, num_classes=4)
    params = GM.init_gnn(cfg, jax.random.PRNGKey(0))
    opt = AdamW(lr=1e-2, weight_decay=0.0)
    ostate = opt.init(params)
    sampler = S.NeighborSampler(sbm_graph, [5, 5], seed=0)
    step = jax.jit(GM.make_minibatch_train_step(cfg, opt))
    rng = np.random.default_rng(0)
    shapes = set()
    for _ in range(10):
        seeds = rng.choice(sbm_graph.num_nodes, 32, replace=False)
        mb = sampler.sample(seeds)
        shapes.add(tuple((b.num_dst, b.num_src, len(b.edge_src))
                         for b in mb.blocks))
        blocks = [DeviceGraph.from_block(b) for b in mb.blocks]
        x_in = jnp.asarray(
            sbm_graph.features[np.maximum(mb.blocks[0].src_nodes, 0)])
        y = jnp.asarray(sbm_graph.labels[seeds])
        params, ostate, _ = step(params, ostate, blocks, x_in, y,
                                 jnp.ones_like(y, jnp.float32))
    assert shapes == {((32 * 6, 32 * 36, 32 * 30), (32, 32 * 6, 32 * 5))}
    assert step._cache_size() == 1


@pytest.mark.parametrize("arch", ["ggnn", "appnp"])
def test_new_archs_learn(sbm_graph, arch):
    cfg = GNNConfig(arch=arch, feat_dim=16, hidden=32, num_classes=4)
    params = GM.init_gnn(cfg, jax.random.PRNGKey(0))
    opt = AdamW(lr=1e-2, weight_decay=0.0)
    ostate = opt.init(params)
    dg = DeviceGraph.from_graph(sbm_graph)
    x = jnp.asarray(sbm_graph.features)
    y = jnp.asarray(sbm_graph.labels)
    mask = jnp.ones_like(y, jnp.float32)
    step = jax.jit(GM.make_fullgraph_train_step(cfg, opt))
    losses = []
    for _ in range(40):
        params, ostate, loss = step(params, ostate, dg, x, y, mask)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.6


def test_datasets_registry():
    from repro.graph.datasets import load
    ds = load("citeseer-like")
    g = ds.graph
    assert g.num_nodes == 3300 and g.num_classes == 6
    assert g.features.shape == (3300, 64)
    assert (ds.train_mask | ds.val_mask | ds.test_mask).all()
    assert not (ds.train_mask & ds.test_mask).any()
    rl = load("reddit-like", scale=0.005)
    deg = rl.graph.out_degree()
    assert deg.max() > 10 * deg.mean()   # heavy tail preserved


def test_saga_layer_manual_equivalence(sbm_graph):
    dg = DeviceGraph.from_graph(sbm_graph)
    x = jnp.asarray(sbm_graph.features)
    out = saga_layer(
        dg, x, x,
        apply_edge=lambda s, d, e: s,
        gather="sum",
        apply_vertex=lambda a, h: a)
    # manual: sum of in-neighbor features
    e = sbm_graph.edges()
    want = np.zeros_like(sbm_graph.features)
    np.add.at(want, e[:, 1], sbm_graph.features[e[:, 0]])
    np.testing.assert_allclose(np.asarray(out), want, atol=1e-4)


def test_segment_softmax_normalizes(sbm_graph):
    dg = DeviceGraph.from_graph(sbm_graph)
    logits = jnp.asarray(
        np.random.default_rng(0).normal(size=(dg.edge_src.shape[0], 2)),
        jnp.float32)
    alpha = segment_softmax(logits, dg.edge_dst, dg.num_dst, dg.edge_mask)
    sums = jax.ops.segment_sum(alpha, dg.edge_dst, dg.num_dst)
    has_edges = np.asarray(
        jax.ops.segment_sum(dg.edge_mask.astype(jnp.float32),
                            dg.edge_dst, dg.num_dst)) > 0
    np.testing.assert_allclose(np.asarray(sums)[has_edges], 1.0, atol=1e-4)
