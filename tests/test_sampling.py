"""Sampling invariants (§3.2.2 / Table 4): fanout bounds, block structure,
neighborhood-explosion containment; the array-built block against the
dict-built one it replaced; the layer-wide neighbour draw (exact fanout,
determinism, thread safety, uniformity)."""
import copy
import sys
import threading

import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core import sampling as S
from repro.graph.structure import from_edges


@pytest.fixture(scope="module")
def graph(graph):
    return graph("er", 300)


def _check_block_invariants(b: S.Block):
    valid_src = b.src_nodes[b.src_nodes >= 0]
    valid_dst = b.dst_nodes[b.dst_nodes >= 0]
    # dst nodes are a prefix of src nodes
    np.testing.assert_array_equal(b.src_nodes[:len(valid_dst)], valid_dst)
    # masked edges index inside the valid ranges
    es = b.edge_src[b.edge_mask]
    ed = b.edge_dst[b.edge_mask]
    assert (es < len(b.src_nodes)).all()
    assert (ed < len(valid_dst)).all()


def test_neighbor_sampler_fanout_bound(graph):
    fanouts = [4, 4]
    s = S.NeighborSampler(graph, fanouts, seed=0)
    seeds = np.arange(16)
    mb = s.sample(seeds)
    assert len(mb.blocks) == 2
    for b, f in zip(mb.blocks, reversed(fanouts)):
        _check_block_invariants(b)
    # neighborhood must not explode beyond seeds * prod(fanouts+1)
    assert mb.blocks[0].num_src <= 16 * (1 + 4) * (1 + 4)
    np.testing.assert_array_equal(mb.blocks[-1].dst_nodes, seeds)


def test_importance_sampler(graph):
    s = S.ImportanceSampler(graph, [3, 3], seed=0)
    mb = s.sample(np.arange(8))
    for b in mb.blocks:
        _check_block_invariants(b)


@pytest.mark.parametrize("dependent", [False, True])
def test_layerwise_samplers(graph, dependent):
    s = S.LayerWiseSampler(graph, [32, 32], dependent=dependent, seed=0)
    mb = s.sample(np.arange(8))
    for b in mb.blocks:
        _check_block_invariants(b)
        # layer budget respected
        assert b.num_src <= 8 + 32 + b.num_dst


def test_cluster_sampler_covers_all_nodes(graph):
    cs = S.ClusterSampler(graph, n_clusters=8, clusters_per_batch=2, seed=0)
    assert (cs.assign >= 0).all() and (cs.assign < 8).all()
    nodes, sub = cs.sample_subgraph()
    assert sub.num_nodes == len(nodes)
    assert sub.num_classes == graph.num_classes


def test_saint_rw_sampler(graph):
    s = S.SaintRWSampler(graph, n_roots=10, walk_len=4, seed=0)
    nodes, sub = s.sample_subgraph()
    assert 10 <= sub.num_nodes <= 10 * 5
    assert sub.features.shape[0] == sub.num_nodes


def test_neighborhood_explosion_motivation(graph):
    """Survey §3.2.2: unsampled k-hop neighborhoods explode; sampled ones
    stay bounded."""
    sizes = S.neighborhood_growth(graph, np.arange(4), hops=3)
    s = S.NeighborSampler(graph, [4, 4, 4], seed=0)
    mb = s.sample(np.arange(4))
    sampled_input = int((mb.blocks[0].src_nodes >= 0).sum())
    assert sizes[-1] > sampled_input  # sampling contains the explosion


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 100), batch=st.integers(1, 12))
def test_property_blocks_are_consistent(graph, seed, batch):
    s = S.NeighborSampler(graph, [3, 3], seed=seed)
    rng = np.random.default_rng(seed)
    seeds = rng.choice(graph.num_nodes, batch, replace=False)
    mb = s.sample(seeds)
    # features flow: every block's dst appears in next block's src prefix
    np.testing.assert_array_equal(mb.blocks[-1].dst_nodes, seeds)
    for b in mb.blocks:
        _check_block_invariants(b)


# ---------------------------------------------------------------------------
# the array-built block against the dict-built one
# ---------------------------------------------------------------------------

def _dict_build_block(g, dst, src_extra, edges, src_cap, edge_cap):
    """The dict-and-loop ``_build_block`` that the array-built one
    replaced, kept verbatim as the reference."""
    src = np.concatenate([dst, np.setdiff1d(src_extra, dst)])
    src = src[:src_cap]
    lookup_src = {v: i for i, v in enumerate(src)}
    lookup_dst = {v: i for i, v in enumerate(dst)}
    es, ed, keep = [], [], []
    for s, d in edges:
        si = lookup_src.get(s)
        di = lookup_dst.get(d)
        if si is not None and di is not None:
            es.append(si)
            ed.append(di)
    es = np.asarray(es[:edge_cap], np.int32)
    ed = np.asarray(ed[:edge_cap], np.int32)
    mask = np.zeros(edge_cap, bool)
    mask[:len(es)] = True
    return S.Block(
        src_nodes=S._pad_to(src.astype(np.int64), src_cap, -1),
        dst_nodes=dst.astype(np.int64),
        edge_src=S._pad_to(es, edge_cap, 0),
        edge_dst=S._pad_to(ed, edge_cap, 0),
        edge_mask=mask,
    )


def _recorded_build_calls(monkeypatch, run) -> list:
    """The arguments of every ``_build_block`` call that ``run()`` makes."""
    calls, build = [], S._build_block

    def recording(*args):
        calls.append(copy.deepcopy(args))
        return build(*args)

    monkeypatch.setattr(S, "_build_block", recording)
    run()
    monkeypatch.setattr(S, "_build_block", build)
    return calls


MADE = ("padded_dst", "empty_extra", "truncated", "outside_endpoints")
SAMPLED = ("neighbor", "importance", "layerwise", "fastgcn", "per_node_padded")


def _made_inputs(case):
    """``(dst, src_extra, edges, src_cap, edge_cap)`` lists written out."""
    rng = np.random.default_rng(3)
    dst = np.asarray([5, -1, 9, 2, -1, 40], np.int64)
    extra = np.unique(rng.integers(0, 60, 30))
    edges = np.stack([rng.choice(extra, 50), rng.choice(dst[dst >= 0], 50)],
                     axis=1)
    if case == "padded_dst":
        return [(dst, extra, edges, len(dst) * 8, 60)]
    if case == "empty_extra":
        return [(dst, np.zeros(0, np.int64), edges[:, [1, 1]], 12, 60),
                (dst, np.zeros(0, np.int64), np.zeros((0, 2), np.int64),
                 12, 4)]
    if case == "truncated":
        # sources cut inside the extras and inside the dst prefix; edges
        # cut at the cap
        return [(dst, extra, edges, len(dst) + 7, 20),
                (dst, extra, edges, 4, 8)]
    if case == "outside_endpoints":
        stray = np.asarray([[61, 5], [5, 61], [70, 70], [9, -1], [-1, 2],
                            [2, 2]], np.int64)
        return [(dst, extra, np.concatenate([stray, edges, stray]),
                 len(dst) * 8, 80)]
    raise ValueError(case)


def _sampler_inputs(case, graph, monkeypatch):
    if case == "neighbor":
        s = S.NeighborSampler(graph, [3, 5], seed=1)
        run = lambda: s.sample(np.arange(0, 40, 3))
    elif case == "importance":
        s = S.ImportanceSampler(graph, [3, 3], seed=1)
        run = lambda: s.sample(np.arange(8))
    elif case in ("layerwise", "fastgcn"):
        s = S.LayerWiseSampler(graph, [32, 16], seed=1,
                               dependent=case == "layerwise")
        run = lambda: s.sample(np.arange(8))
    elif case == "per_node_padded":
        gr = graph.reverse()
        run = lambda: S.sample_block_padded(
            graph, gr, np.asarray([4, -1, 17, 8, -1]), 3,
            lambda n: np.random.default_rng(n),
            expand=np.asarray([True, True, False, True, True]))
    else:
        raise ValueError(case)
    return [args[1:] for args in _recorded_build_calls(monkeypatch, run)]


@pytest.mark.parametrize("case", MADE + SAMPLED)
def test_build_block_matches_dict_reference(graph, monkeypatch, case):
    """Bitwise the block the dict-built reference gives, for inputs written
    out and for those each sampler hands ``_build_block``; every array owned
    and writable."""
    inputs = (_made_inputs(case) if case in MADE
              else _sampler_inputs(case, graph, monkeypatch))
    assert inputs
    for dst, extra, edges, src_cap, edge_cap in inputs:
        got = S._build_block(graph, dst, extra, edges, src_cap, edge_cap)
        want = _dict_build_block(graph, dst, extra, edges, src_cap, edge_cap)
        for field in ("src_nodes", "dst_nodes", "edge_src", "edge_dst",
                      "edge_mask"):
            a, b = getattr(got, field), getattr(want, field)
            assert a.dtype == b.dtype and a.shape == b.shape, field
            assert np.array_equal(a, b), field
            assert a.flags.writeable and a.flags.owndata, field


# ---------------------------------------------------------------------------
# the layer-wide neighbour draw
# ---------------------------------------------------------------------------

def _ladder_graph():
    """60 nodes; node v has in-degree v % 31 (0 to 30), from distinct
    random sources: degree-0 nodes and nodes below, at and above any
    fanout up to 30."""
    rng = np.random.default_rng(0)
    edges = [(u, v) for v in range(60)
             for u in rng.choice(60, v % 31, replace=False)]
    return from_edges(60, np.asarray(edges, np.int64))


def _blocks_equal(a: S.MiniBatch, b: S.MiniBatch) -> bool:
    return all(np.array_equal(getattr(x, f), getattr(y, f))
               for x, y in zip(a.blocks, b.blocks)
               for f in ("src_nodes", "dst_nodes", "edge_src", "edge_dst",
                         "edge_mask"))


def _exact_fanout(g):
    """Every real destination gets exactly min(in-degree, f) distinct
    in-neighbours, and every edge is an edge of the graph (as the
    benchmark's ``block_faults`` reads a block)."""
    fanouts = [4, 10]
    in_deg = g.in_degree()
    graph_edges = set(map(tuple, g.edges().tolist()))
    for seed in range(4):
        seeds = np.random.default_rng(seed).choice(60, 12, replace=False)
        mb = S.NeighborSampler(g, fanouts, seed=seed).sample(seeds)
        np.testing.assert_array_equal(mb.blocks[-1].dst_nodes, seeds)
        for b, f in zip(mb.blocks, fanouts):
            _check_block_invariants(b)
            m = b.edge_mask
            s, d = b.src_nodes[b.edge_src[m]], b.dst_nodes[b.edge_dst[m]]
            assert (s >= 0).all() and (d >= 0).all()
            assert all((int(u), int(v)) in graph_edges for u, v in zip(s, d))
            assert len(set(zip(s.tolist(), d.tolist()))) == len(s)
            got = np.bincount(b.edge_dst[m], minlength=b.num_dst)
            valid = b.dst_nodes >= 0
            want = np.minimum(in_deg[b.dst_nodes[valid]], f)
            np.testing.assert_array_equal(got[valid], want)
            assert not got[~valid].any()
            # edges run destination-major, as the aggregation expects
            assert (np.diff(b.edge_dst[m]) >= 0).all()


def _same_seed(g):
    seeds = np.arange(0, 60, 5)
    a = S.NeighborSampler(g, [4, 10], seed=7).sample(seeds)
    b = S.NeighborSampler(g, [4, 10], seed=7).sample(seeds)
    c = S.NeighborSampler(g, [4, 10], seed=8).sample(seeds)
    assert _blocks_equal(a, b)
    assert not _blocks_equal(a, c)


def _threads(g):
    """Copies of one sampler with generators of their own, as the
    benchmark's loader workers hold them, give the same batches whether
    they run in turn or in two threads at once."""
    base = S.NeighborSampler(g, [4, 10], seed=0)
    batches = [np.random.default_rng(i).choice(60, 12, replace=False)
               for i in range(40)]

    def workers():
        out = []
        for w in range(2):
            s = copy.copy(base)
            s.rng = np.random.default_rng([5, w])
            out.append(s)
        return out

    def run(s, results, w):
        results[w] = [s.sample(x) for x in batches]

    in_turn = [None, None]
    for w, s in enumerate(workers()):
        run(s, in_turn, w)
    at_once = [None, None]
    threads = [threading.Thread(target=run, args=(s, at_once, w))
               for w, s in enumerate(workers())]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)          # switch threads as often as it can
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    for w in range(2):
        assert all(_blocks_equal(a, b)
                   for a, b in zip(in_turn[w], at_once[w]))


def _uniform(g):
    """Over 3,000 draws of one destination with in-degree 30 at fanout 10,
    each neighbour appears about a third of the time (chi-square over 30
    cells, 29 degrees of freedom: mean 29, and 80 lies past p = 1e-6)."""
    v, f, n = 30, 10, 3000
    s = S.NeighborSampler(g, [f], seed=123)
    nbr = s.gr.neighbors(v)
    assert len(nbr) == 30
    counts = dict.fromkeys(nbr.tolist(), 0)
    for _ in range(n):
        edges, capped = s.draw(np.asarray([v, -1]), f)
        assert capped == 1
        picked = edges[:, 0].tolist()
        assert len(set(picked)) == f and (edges[:, 1] == v).all()
        for u in picked:
            counts[u] += 1
    expected = n * f / len(nbr)
    chi2 = sum((c - expected) ** 2 / expected for c in counts.values())
    assert chi2 < 80, chi2


@pytest.mark.parametrize("case", ["exact_fanout", "same_seed", "threads",
                                  "uniform"])
def test_neighbor_sampler_layer_draw(case):
    {"exact_fanout": _exact_fanout, "same_seed": _same_seed,
     "threads": _threads, "uniform": _uniform}[case](_ladder_graph())


@pytest.mark.parametrize("sampler", ["neighbor", "distributed"])
def test_destinations_are_the_first_sources(graph, sampler):
    """What GAT's self-loops and skip path rely on: in every block, source
    slot ``i`` holds destination ``i`` for each of the ``n_dst`` slots,
    padded slots included, so ``x_src[:n_dst]`` are the destinations."""
    seeds = np.random.default_rng(4).choice(graph.num_nodes, 24,
                                            replace=False)
    if sampler == "neighbor":
        blocks = S.NeighborSampler(graph, [3, 2, 4], seed=1).sample(
            seeds).blocks
    else:
        from repro.distributed import DistributedMinibatchSampler
        ds = DistributedMinibatchSampler(graph, 3, [3, 2, 4], 16,
                                         cache_capacity=30)
        owned = ds.layout.owned[1]
        blocks = ds.sample_partition(1, owned[:11]).blocks   # 5 pad seeds
    assert any((b.dst_nodes < 0).any() for b in blocks)
    for b in blocks:
        np.testing.assert_array_equal(b.src_nodes[:b.num_dst], b.dst_nodes)
