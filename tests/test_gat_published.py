"""The program's GAT against the benchmark's plain reference
(``chipbench/references/gat.py``): PyG's ``GATConv`` with self-loops,
concatenated hidden heads, a head-mean last layer, a bias, a skip path
and ELU between layers, in every implementation of the edge attention,
over padded sampled blocks and over the full graph."""
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import sampling as S
from repro.core.abstraction import DeviceGraph
from repro.graph.structure import from_edges
from repro.kernels import gat_fused, ops as kops
from repro.models.gnn import model as GM
from repro.models.gnn.layers import GATLayer
from repro.models.gnn.model import GNNConfig

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from chipbench.references import gat as REF  # noqa: E402

IMPLS = ("jax_ops", "gat_fused", "gat_kernel_unfit")
# Both sides are float32 on the CPU, where every product is exact float32:
# they differ only in the order of their sums (segment sums over a few
# edges, the head mean, the reference's per-head aggregation), a few ulp
# of values of order 1.  A model without the self-loops, the skip path,
# the bias or the head mean misses by 1e-2 or more.
RTOL = 2e-5


def _cfg(use_kernel):
    return GNNConfig(arch="gat", feat_dim=16, hidden=32, num_classes=5,
                     num_layers=3, use_kernel=use_kernel)


REF_CFG = {"model": {"arch": "gat", "heads": 4, "in_features": 16,
                     "hidden": 32, "classes": 5, "layers": 3}}


@pytest.fixture(scope="module")
def graph(graph):
    return graph("sbm", 160)


@pytest.fixture
def impl(request, monkeypatch):
    """Route the attention to one implementation, and count the calls that
    reach the kernel.  ``gat_kernel_unfit`` sets ``use_kernel`` where the
    kernel's slabs do not fit VMEM: the layer then runs its ``jax.ops``
    attention."""
    calls = []
    if request.param == "gat_kernel_unfit":
        monkeypatch.setattr(gat_fused, "gat_fused_fits",
                            lambda *a, **k: False)
    real = kops._gat_fused_jit
    monkeypatch.setattr(kops, "_gat_fused_jit",
                        lambda *a, **k: calls.append(1) or real(*a, **k))
    return request.param, calls


def _params():
    return REF.init(REF_CFG, jax.random.PRNGKey(3))


def _batch(g, seeds, fanouts):
    """A padded sampled batch for the program and the same for the
    reference (the layout ``chipbench/paths/minibatch.py`` gives it)."""
    mb = S.NeighborSampler(g, fanouts, seed=5).sample(seeds)
    src = mb.blocks[0].src_nodes
    x = np.where((src >= 0)[:, None], g.features[np.maximum(src, 0)],
                 0.0).astype(np.float32)
    y = g.labels[seeds].astype(np.int32)
    w = np.ones(len(seeds), np.float32)
    w[-2:] = 0.0                                # two seeds left out
    ref = {"blocks": [{"src": b.edge_src.astype(np.int32),
                       "dst": b.edge_dst.astype(np.int32),
                       "mask": b.edge_mask.astype(bool),
                       "dst_rows": np.zeros(len(b.dst_nodes), np.int8)}
                      for b in mb.blocks],
           "x": x, "labels": y, "label_mask": w}
    return mb, ref


def _close(a, b):
    for (path, u), v in zip(jax.tree_util.tree_flatten_with_path(a)[0],
                            jax.tree.leaves(b)):
        u, v = np.asarray(u), np.asarray(v)
        scale = max(np.abs(v).max(), 1e-30)
        assert np.abs(u - v).max() <= RTOL * scale, (
            jax.tree_util.keystr(path), np.abs(u - v).max() / scale)


def test_init_has_the_reference_layout():
    prog = GM.init_gnn(_cfg(False), jax.random.PRNGKey(0))
    shapes = lambda t: jax.tree.map(jnp.shape, t)
    assert shapes(prog) == shapes(_params())
    # hidden layers concatenate 4 heads of 8; the last averages 4 of 5
    assert prog[0]["w"].shape == (16, 32) and prog[0]["b"].shape == (32,)
    assert prog[2]["w"].shape == (32, 20) and prog[2]["b"].shape == (5,)
    assert prog[2]["w_skip"].shape == (32, 5)


@pytest.mark.parametrize("impl", IMPLS, indirect=True)
def test_blocks_match_the_reference(graph, impl):
    name, calls = impl
    seeds = np.random.default_rng(0).choice(graph.num_nodes, 10,
                                            replace=False)
    mb, ref_batch = _batch(graph, seeds, [2, 3, 2])
    assert any((b.src_nodes < 0).any() for b in mb.blocks)     # padded
    cfg, params = _cfg(name != "jax_ops"), _params()
    blocks = [DeviceGraph.from_block(b) for b in mb.blocks]

    def prog_loss(p):
        logits = GM.forward_blocks(cfg, p, blocks, ref_batch["x"])
        return GM.nll_loss(logits, ref_batch["labels"],
                           ref_batch["label_mask"])

    with jax.default_matmul_precision("highest"):
        lp, gp = jax.value_and_grad(prog_loss)(params)
        lr, gr = jax.value_and_grad(REF.loss)(params, ref_batch, "highest")
    assert abs(float(lp) - float(lr)) <= RTOL * abs(float(lr))
    _close(gp, gr)
    # one kernel call a layer, forward; the kernel's backward is its own
    assert len(calls) == (3 if name == "gat_fused" else 0)


@pytest.mark.parametrize("impl", IMPLS, indirect=True)
def test_full_graph_matches_the_reference(graph, impl):
    """``forward_full`` over a graph with a few self-loops of its own: the
    layer drops them and adds one per node, as PyG does."""
    name, calls = impl
    e = graph.edges()
    loops = np.array([[0, 0], [7, 7], [9, 9]])
    g = from_edges(graph.num_nodes, np.concatenate([e, loops]),
                   features=graph.features, labels=graph.labels,
                   num_classes=graph.num_classes)
    dg = DeviceGraph.from_graph(g)
    ge = g.edges()
    n = g.num_nodes
    block = {"src": ge[:, 0].astype(np.int32),
             "dst": ge[:, 1].astype(np.int32),
             "mask": np.ones(len(ge), bool),
             "dst_rows": np.zeros(n, np.int8)}
    batch = {"blocks": [block] * 3, "x": g.features,
             "labels": g.labels.astype(np.int32),
             "label_mask": np.ones(n, np.float32)}
    cfg, params = _cfg(name != "jax_ops"), _params()

    def prog_loss(p):
        return GM.nll_loss(GM.forward_full(cfg, p, dg, g.features),
                           batch["labels"])

    with jax.default_matmul_precision("highest"):
        lp, gp = jax.value_and_grad(prog_loss)(params)
        lr, gr = jax.value_and_grad(REF.loss)(params, batch, "highest")
    assert abs(float(lp) - float(lr)) <= RTOL * abs(float(lr))
    _close(gp, gr)
    assert len(calls) == (3 if name == "gat_fused" else 0)


def test_unfit_kernel_lowers_as_jax_ops():
    """Where the kernel's slabs do not fit VMEM (ogbn-products' first
    layer: 681,472 sources, 4 heads of 128), ``use_kernel=True`` lowers
    the layer to the very program ``use_kernel=False`` does."""
    S, D, E, heads, c = 681_472, 61_952, 619_520, 4, 128
    assert not gat_fused.gat_fused_fits(S, D, heads, c)
    spec = jax.ShapeDtypeStruct
    g = DeviceGraph(spec((E,), jnp.int32), spec((E,), jnp.int32),
                    spec((E,), jnp.bool_), S, D, spec((D,), jnp.float32),
                    spec((S,), jnp.float32))
    p = jax.eval_shape(lambda k: GATLayer.init(k, 100, heads * c, heads),
                       jax.random.PRNGKey(0))
    x = spec((S, 100), jnp.float32)

    def text(use_kernel):
        f = functools.partial(GATLayer(), use_kernel=use_kernel)
        return jax.jit(f).lower(p, g, x).as_text()

    assert text(True) == text(False)


def test_each_published_part_is_in_the_function(graph):
    """The reference, with one part of the published layer taken out,
    misses the program by far more than the tolerance."""
    seeds = np.arange(10)
    mb, ref_batch = _batch(graph, seeds, [2, 3, 2])
    blocks = [DeviceGraph.from_block(b) for b in mb.blocks]
    params = _params()
    params = jax.tree.map(lambda a: a + 0.1, params)      # nonzero biases
    prog = float(GM.nll_loss(GM.forward_blocks(_cfg(False), params, blocks,
                                               ref_batch["x"]),
                             ref_batch["labels"], ref_batch["label_mask"]))
    no_skip = [dict(p, w_skip=p["w_skip"] * 0, b_skip=p["b_skip"] * 0)
               for p in params]
    no_bias = [dict(p, b=p["b"] * 0) for p in params]
    for changed in (no_skip, no_bias):
        other = float(REF.loss(changed, ref_batch, "highest"))
        assert abs(other - prog) > 1e3 * RTOL * abs(prog)
