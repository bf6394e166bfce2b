"""The program's spans and named scopes (``repro.core.telemetry`` and the
GNN step).

* a span records its thread's CPU seconds and, while a profiler trace is
  recorded, writes itself into that trace as ``repro.<name>`` with its
  attributes; with telemetry off and no trace it records nothing;
* ``loader.get`` says whether a batch was waiting; ``sampler.sample``
  counts the edges it sampled and the destinations whose in-degree
  exceeded the fanout; ``store.fetch_masked`` counts slots, pad slots,
  bytes and the parts gathered on the pool;
* the compiled mini-batch and full-graph steps name their aggregation,
  forward and backward, their dense work, normalisation, loss and
  optimizer in their HLO ``op_name`` metadata.
"""
import glob
import re
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import telemetry as T


@pytest.fixture()
def reg():
    r = T.get_registry()
    prev = T.set_enabled(True)
    r.reset()
    try:
        yield r
    finally:
        r.reset()
        T.set_enabled(prev)


def _profile_options():
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    return options


def _recorded_spans(trace_dir) -> list:
    """``(name, stats)`` of every ``repro.`` event in the trace."""
    from jax.profiler import ProfileData
    path = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("repro."):
                    out.append((e.name, dict(e.stats)))
    return out


def _busy(seconds: float):
    t = time.thread_time()
    while time.thread_time() - t < seconds:
        pass


@pytest.mark.parametrize("enabled", [False, True])
def test_spans_enter_a_recorded_trace_with_cpu(tmp_path, enabled):
    r = T.get_registry()
    prev = T.set_enabled(enabled)
    r.reset()
    try:
        with T.span("before.trace"):
            pass
        jax.profiler.start_trace(str(tmp_path),
                                 profiler_options=_profile_options())
        try:
            with T.span("work", rows=3) as attrs:
                attrs["queue"] = "empty"
                _busy(0.02)
            with T.span("virtual", clock=lambda: 0.0):
                pass
        finally:
            jax.profiler.stop_trace()
        with T.span("after.trace") as attrs:
            assert (attrs is not None) == enabled
        names = {n for n, _ in _recorded_spans(tmp_path)}
        assert names == {"repro.work"}
        (_, stats), = _recorded_spans(tmp_path)
        assert stats["rows"] == 3 and stats["queue"] == "empty"
        assert 0.015 <= stats["cpu"] <= 1.0
        events = {e["name"]: e for e in r.tracer.events}
        if enabled:
            assert set(events) == {"before.trace", "work", "virtual",
                                   "after.trace"}
            work = events["work"]
            assert work["attrs"] == {"rows": 3, "queue": "empty"}
            assert 0.015 <= work["cpu"] <= work["dur"] + 1e-3
        else:
            assert events == {}
    finally:
        r.reset()
        T.set_enabled(prev)


def test_a_disabled_span_yields_none_and_records_nothing():
    r = T.get_registry()
    prev = T.set_enabled(False)
    r.reset()
    try:
        with T.span("off") as attrs:
            assert attrs is None
        assert r.tracer.events == []
    finally:
        T.set_enabled(prev)


def test_span_cpu_in_jsonl(reg, tmp_path):
    with T.span("busy"):
        _busy(0.01)
    path = str(tmp_path / "trace.jsonl")
    reg.tracer.export_jsonl(path)
    assert T.validate_trace_jsonl(path) == 1
    (ev,) = reg.tracer.events
    assert ev["cpu"] >= 0.005


def test_spans_from_threads_keep_seq_dense(reg, tmp_path):
    """Loader threads and the consumer record at once: every event keeps
    a seq of its own, in the order of the event list."""
    def work():
        for _ in range(500):
            with T.span("t"):
                pass

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    path = str(tmp_path / "trace.jsonl")
    assert reg.tracer.export_jsonl(path) == 4000
    assert T.validate_trace_jsonl(path) == 4000


@pytest.mark.parametrize("ready", [False, True])
def test_loader_get_says_whether_a_batch_was_waiting(reg, ready):
    from repro.core.scheduling import PipelinedLoader

    go = threading.Event()
    if ready:
        go.set()

    def sample():
        go.wait()
        return 1

    loader = PipelinedLoader(sample, depth=2, n_workers=1)
    try:
        if ready:
            deadline = time.time() + 30
            while not loader.q.full() and time.time() < deadline:
                time.sleep(0.01)
            assert loader.q.full()
        else:
            threading.Timer(0.05, go.set).start()
        assert next(loader) == 1
    finally:
        go.set()
        loader.close()
    gets = [e for e in reg.tracer.events if e["name"] == "loader.get"]
    assert [e["attrs"]["queue"] for e in gets] == (
        ["ready"] if ready else ["empty"])
    if not ready:
        assert gets[0]["dur"] >= 0.03


def test_sampler_counts_edges_and_capped_destinations(reg):
    from repro.core.sampling import NeighborSampler
    from repro.graph import generators as G

    g = G.sbm(200, 4, p_in=0.2, p_out=0.01, seed=1)
    fanouts = [2, 3]
    s = NeighborSampler(g, fanouts, seed=0)
    mb = s.sample(np.arange(16))
    (ev,) = [e for e in reg.tracer.events if e["name"] == "sampler.sample"]
    in_deg = g.in_degree()
    capped = sum(int(np.sum(in_deg[b.dst_nodes[b.dst_nodes >= 0]] > f))
                 for b, f in zip(mb.blocks, fanouts))
    assert capped > 0
    assert ev["attrs"] == {
        "edges": sum(int(b.edge_mask.sum()) for b in mb.blocks),
        "capped": capped}


def test_fetch_masked_counts_rows_pads_and_bytes(reg):
    from repro.core.caching import FeatureStore
    from repro.graph import generators as G

    g = G.featurize(G.sbm(60, 3, p_in=0.3, p_out=0.02, seed=0), 8, seed=0)
    store = FeatureStore(g, np.arange(10))
    ids = np.array([5, -1, 12, 40, -1, -1, 3, 59])
    needed = ids >= 0
    needed[3] = False                       # a real id whose row is not needed
    out = store.fetch_masked(ids, needed)
    (ev,) = [e for e in reg.tracer.events if e["name"] == "store.fetch_masked"]
    assert ev["attrs"] == {"rows": 8, "pad_rows": 4, "bytes": out.nbytes,
                           "parts": 0}
    assert out.nbytes == 8 * 8 * 4
    assert np.array_equal(out[~needed], np.zeros((4, 8), np.float32))


def test_from_block_degrees_match_the_block():
    from repro.core.abstraction import DeviceGraph
    from repro.core.sampling import NeighborSampler
    from repro.graph import generators as G

    g = G.sbm(200, 4, p_in=0.2, p_out=0.01, seed=1)
    mb = NeighborSampler(g, [3, 4], seed=0).sample(np.arange(16))
    for b in mb.blocks:
        dg = DeviceGraph.from_block(b)
        m = np.asarray(b.edge_mask, bool)
        indeg = np.bincount(np.asarray(b.edge_dst)[m], minlength=b.num_dst)
        outdeg = np.bincount(np.asarray(b.edge_src)[m], minlength=b.num_src)
        assert np.array_equal(np.asarray(dg.in_deg), np.maximum(indeg, 1))
        assert np.array_equal(np.asarray(dg.out_deg), np.maximum(outdeg, 1))


def _op_names(compiled) -> set:
    return set(re.findall(r'op_name="([^"]*)"', compiled.as_text()))


STEPS = {
    # (step maker, arch, use_kernel, the implementation scope expected)
    "minibatch-sage-kernel": ("make_minibatch_train_step", "sage", True,
                              "pallas_"),
    "fullgraph-gcn": ("make_fullgraph_train_step", "gcn", False, "jax_ops"),
}


@pytest.mark.parametrize("case", sorted(STEPS))
def test_compiled_steps_carry_program_scopes(case):
    from repro.core.abstraction import DeviceGraph
    from repro.models.gnn import model as GM
    from repro.models.gnn.model import GNNConfig
    from repro.optim import AdamW

    maker, arch, use_kernel, impl = STEPS[case]
    cfg = GNNConfig(arch=arch, feat_dim=16, hidden=32, num_classes=4,
                    num_layers=2, use_kernel=use_kernel)
    opt = AdamW(lr=1e-2, weight_decay=0.0)
    params = GM.init_gnn(cfg, jax.random.PRNGKey(0))
    ostate = opt.init(params)

    def graph(n_dst, n_src, e):
        src = jnp.arange(e, dtype=jnp.int32) % n_src
        dst = jnp.arange(e, dtype=jnp.int32) % n_dst
        return DeviceGraph(src, dst, jnp.ones((e,), bool), n_src, n_dst,
                           jnp.ones((n_dst,)), jnp.ones((n_src,)))

    if maker == "make_minibatch_train_step":
        graphs = [graph(24, 96, 72), graph(8, 24, 16)]
        n_in, n_out = 96, 8
    else:
        graphs = graph(64, 64, 256)
        n_in = n_out = 64
    args = (graphs, jnp.ones((n_in, 16)), jnp.zeros((n_out,), jnp.int32),
            jnp.ones((n_out,)))
    step = getattr(GM, maker)(cfg, opt)
    names = _op_names(jax.jit(step).lower(params, ostate, *args).compile())
    text = "\n".join(names)
    assert "/jvp(gnn.aggregate)/" + impl in text
    assert "/transpose(jvp(gnn.aggregate))/" + impl in text
    for scope in ("jvp(gnn.dense)", "transpose(jvp(gnn.dense))",
                  "jvp(gnn.norm)", "jvp(gnn.loss)", "/optimizer/"):
        assert scope in text, scope
