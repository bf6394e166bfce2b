"""``FeatureStore.fetch_masked`` builds the input-row matrix in one pass.

Every case compares the result bit for bit with
``np.where(needed[:, None], features[max(ids, 0)], 0)``, on the calling
thread and on the gather pool (forced by shrinking the module's size
constants).  The table holds ``NaN`` and ``-0.0`` rows, and the
unneeded slots point at them, so a masked multiply or a missed slot
shows as a wrong bit pattern.
"""
import os
import sys
import threading

import numpy as np
import pytest

from repro.core import caching as C
from repro.core import telemetry as T
from repro.core.comm import HEADER_BYTES, Transport
from repro.distributed.sampler import PartitionFeatureStore
from repro.graph import generators as G

N, F = 64, 8


def _graph():
    g = G.featurize(G.sbm(N, 4, p_in=0.3, p_out=0.02, seed=0), F, seed=0)
    g.features[3] = np.nan
    g.features[4] = -0.0
    return g


def _want(g, ids, needed):
    needed = needed & (ids >= 0)
    return np.where(needed[:, None], g.features[np.maximum(ids, 0)], 0)


def _bits(a):
    return np.ascontiguousarray(a).view(np.uint32)


def _layouts():
    rng = np.random.default_rng(1)
    real = rng.integers(0, N, 40)
    real[:4] = [3, 4, 3, 4]                 # NaN and -0.0 rows
    mid_tail = np.concatenate([real[:9], np.full(6, -1), real[9:30],
                               np.full(11, -1)])
    dropped = rng.integers(0, N, 200)
    dropped[::5] = 3                        # dropped slots read NaN rows
    return {
        "pad_runs_mid_and_tail": (mid_tail, mid_tail >= 0),
        "all_pads": (np.full(30, -1), np.ones(30, bool)),
        "no_pads": (real, np.ones(len(real), bool)),
        # fragmented: many short runs, and needed pads are still
        # returned as zero rows
        "needed_drops_real_ids": (dropped, rng.random(200) < 0.5),
    }


LAYOUTS = _layouts()


@pytest.fixture(params=["serial", "pool"])
def path(request, monkeypatch):
    if request.param == "pool":
        monkeypatch.setattr(C, "_POOL_MIN_BYTES", 0)
        monkeypatch.setattr(C, "_PART_BYTES", 3 * F * 4)   # 3-slot parts
    return request.param


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


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_rows_bit_for_bit(path, layout):
    g = _graph()
    ids, needed = LAYOUTS[layout]
    store = C.FeatureStore(g, np.arange(0, N, 3))
    out = store.fetch_masked(ids, needed)
    assert out.dtype == np.float32 and out.shape == (len(ids), F)
    assert out.flags.c_contiguous and out.flags.writeable
    assert np.array_equal(_bits(out), _bits(_want(g, ids, needed)))


@pytest.mark.parametrize("codec", ["fp32", "bf16", "int8"])
def test_codec_miss_rows_and_bytes(path, codec):
    g = _graph()
    ids, needed = LAYOUTS["pad_runs_mid_and_tail"]
    cached = np.arange(0, N, 3)
    store = C.FeatureStore(g, cached, codec=codec)
    out = store.fetch_masked(ids, needed)
    want = _want(g, ids, needed)
    miss = needed & (ids >= 0) & ~np.isin(ids, cached)
    # the decoded rows a fresh channel of the same codec hands back
    ref = Transport(codec, n_rows=N, path="test.reference")
    want[miss] = ref.send(want[miss], row_ids=ids[miss])
    assert np.array_equal(_bits(out), _bits(want))
    assert store.misses == int(miss.sum())
    assert store.hits == int(np.sum(needed & (ids >= 0))) - store.misses
    assert store.requests == 1
    assert store.transferred_bytes == (int(miss.sum()) * store.bytes_per_row
                                       + HEADER_BYTES)


@pytest.mark.parametrize("mix", ["all_hits", "all_misses", "half_cached"])
def test_int8_hits_exact_misses_decoded_pads_zero(path, mix):
    """The int8 codec touches only the rows that cross the wire: cache
    hits come back bit for bit, misses as the codec decodes them, and
    slots not needed as +0.0 rows."""
    g = _graph()
    ids, needed = LAYOUTS["pad_runs_mid_and_tail"]
    real = np.unique(ids[ids >= 0])
    cached = {"all_hits": real, "all_misses": np.zeros(0, np.int64),
              "half_cached": real[::2]}[mix]
    store = C.FeatureStore(g, cached, codec="int8")
    out = store.fetch_masked(ids, needed)
    want = _want(g, ids, needed)
    slot = needed & (ids >= 0)
    hit = slot & np.isin(ids, cached)
    miss = slot & ~hit
    assert hit.any() == (mix != "all_misses")
    assert miss.any() == (mix != "all_hits")
    assert np.array_equal(_bits(out[~slot]), _bits(np.zeros_like(out[~slot])))
    assert np.array_equal(_bits(out[hit]), _bits(want[hit]))
    ref = Transport("int8", n_rows=N, path="test.reference")
    assert np.array_equal(_bits(out[miss]),
                          _bits(ref.send(want[miss], row_ids=ids[miss])))
    assert (store.hits, store.misses) == (int(hit.sum()), int(miss.sum()))
    assert store.requests == int(miss.any())


def test_identity_codec_accounts_misses_without_a_send(path, monkeypatch):
    g = _graph()
    ids, needed = LAYOUTS["no_pads"]
    store = C.FeatureStore(g, np.zeros(0, np.int64))

    def refused(*a, **k):
        raise AssertionError("fp32 miss rows went through a send")

    monkeypatch.setattr(store, "_pull_remote", refused)
    out = store.fetch_masked(ids, needed)
    assert np.array_equal(_bits(out), _bits(_want(g, ids, needed)))
    assert store.transferred_bytes == len(ids) * F * 4 + HEADER_BYTES


def test_float64_table_rounds_through_the_fp32_wire(path):
    g = _graph()
    g.features = g.features.astype(np.float64) + 1e-12
    ids, needed = LAYOUTS["no_pads"]
    out = C.FeatureStore(g, np.zeros(0, np.int64)).fetch_masked(ids, needed)
    assert out.dtype == np.float64
    assert np.array_equal(out, g.features[ids].astype(np.float32),
                          equal_nan=True)


def test_partition_store_owned_rows_are_free(path):
    g = _graph()
    ids, needed = LAYOUTS["pad_runs_mid_and_tail"]
    owned = np.arange(N // 2)
    store = PartitionFeatureStore(g, owned, np.arange(N // 2, N, 4))
    out = store.fetch_masked(ids, needed)
    assert np.array_equal(_bits(out), _bits(_want(g, ids, needed)))
    real = needed & (ids >= 0)
    local = real & (ids < N // 2)
    miss = real & ~local & ((ids - N // 2) % 4 != 0)
    assert store.local_rows == int(local.sum())
    assert (store.hits, store.misses) == (int((real & ~local).sum())
                                          - int(miss.sum()), int(miss.sum()))
    assert store.transferred_bytes == int(miss.sum()) * F * 4 + HEADER_BYTES


def test_each_call_returns_its_own_writable_matrix(path):
    g = _graph()
    table = g.features.copy()
    ids, needed = LAYOUTS["pad_runs_mid_and_tail"]
    store = C.FeatureStore(g, np.arange(0, N, 3))
    a = store.fetch_masked(ids, needed)
    b = store.fetch_masked(ids, needed)
    assert not np.shares_memory(a, b)
    assert not np.shares_memory(a, g.features)
    want = _want(g, ids, needed)
    slot = int(np.flatnonzero(needed & ~np.isnan(want).any(1))[0])
    a[slot, 0] += 1.0                       # as the benchmark's row fault
    assert np.any(a != want)
    assert np.array_equal(_bits(b), _bits(want))
    assert np.array_equal(_bits(g.features), _bits(table))


def test_concurrent_partition_stores(path):
    """More callers than cores share the pool, each on its own store, as
    the loader workers do; every matrix still holds its own batch."""
    g = _graph()
    rng = np.random.default_rng(2)
    n = (os.cpu_count() or 1) + 4
    owner = rng.integers(0, n, N)
    stores = [PartitionFeatureStore(g, np.flatnonzero(owner == p),
                                    np.arange(p % 5, N, 5)) for p in range(n)]
    batches = [(rng.integers(-1, N, 500), rng.random(500) < 0.8)
               for _ in range(4)]
    results, errors = {}, []

    def work(p):
        try:
            for k in range(10):
                ids, needed = batches[(p + k) % 4]
                results[p, k] = stores[p].fetch_masked(ids, needed)
        except Exception as e:              # surfaced below
            errors.append(e)

    threads = [threading.Thread(target=work, args=(p,)) for p in range(n)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert not errors
    assert len(results) == n * 10
    for (p, k), out in results.items():
        ids, needed = batches[(p + k) % 4]
        assert np.array_equal(_bits(out), _bits(_want(g, ids, needed)))


def test_span_counts_parts(reg, monkeypatch):
    monkeypatch.setattr(C, "_POOL_MIN_BYTES", 0)
    monkeypatch.setattr(C, "_PART_BYTES", 4 * F * 4)   # 4-slot parts
    g = _graph()
    ids, needed = LAYOUTS["pad_runs_mid_and_tail"]
    out = C.FeatureStore(g, np.arange(0, N, 3)).fetch_masked(ids, needed)
    (ev,) = [e for e in reg.tracer.events if e["name"] == "store.fetch_masked"]
    assert ev["attrs"] == {"rows": len(ids),
                           "pad_rows": int(np.sum(~needed | (ids < 0))),
                           "bytes": out.nbytes,
                           "parts": -(-len(ids) // 4)}
