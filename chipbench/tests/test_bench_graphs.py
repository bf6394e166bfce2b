"""The benchmark's graph generator at a small scale: exact counts, CSR form,
degree skew, reproducibility from ``graph_seed``, and the on-disk cache."""
import numpy as np
import pytest

from chipbench import graphs

SPEC = {"nodes": 3000, "edges": 3000 * 30, "classes": 7, "features": 16,
        "self_loops": False, "graph_seed": 5, "degree_tail": 1.0,
        "max_expected_degree": 900, "homophily": 0.7, "class_sep": 0.1,
        "train_nodes": 1200}


def _spec(**kw):
    return dict(SPEC, **kw)


@pytest.fixture(scope="module", params=[False, True], ids=["plain", "loops"])
def built(request):
    spec = _spec(self_loops=request.param)
    return spec, graphs.generate(spec)


def test_counts_and_csr_form(built):
    spec, a = built
    n = spec["nodes"]
    row_ptr, col = a["row_ptr"], a["col_idx"]
    loops = n if spec["self_loops"] else 0
    assert len(row_ptr) == n + 1 and row_ptr[0] == 0
    assert row_ptr[-1] == len(col) == spec["edges"] + loops
    assert np.all(np.diff(row_ptr) >= 0)
    src = np.repeat(np.arange(n), np.diff(row_ptr))
    keys = src.astype(np.int64) * n + col
    assert np.all(np.diff(keys) > 0)            # sorted rows, no duplicates
    assert np.sum(src == col) == loops
    rev = np.sort(col.astype(np.int64) * n + src)
    assert np.array_equal(rev, keys)            # symmetric
    assert a["features"].shape == (n, spec["features"])
    assert a["features"].dtype == np.float32
    assert set(np.unique(a["labels"])) == set(range(spec["classes"]))
    assert a["train_mask"].sum() == spec["train_nodes"]
    assert np.array_equal(graphs.directed_keys(a), keys)


def test_degrees_are_skewed_and_classes_cluster(built):
    spec, a = built
    deg = np.diff(a["row_ptr"])
    assert deg.max() >= 5 * deg.mean()
    top = np.sort(deg)[::-1][:len(deg) // 100]
    assert top.sum() >= 0.04 * deg.sum()
    src = np.repeat(np.arange(spec["nodes"]), deg)
    same = np.mean(a["labels"][src] == a["labels"][a["col_idx"]])
    assert 0.4 <= same <= spec["homophily"] + 0.05


def test_reproducible_from_graph_seed():
    a, b = graphs.generate(SPEC), graphs.generate(SPEC)
    for k in graphs.ARRAYS:
        assert np.array_equal(a[k], b[k]), k
    c = graphs.generate(_spec(graph_seed=6))
    assert not np.array_equal(a["col_idx"], c["col_idx"])


def test_cache_writes_once_then_loads(tmp_path):
    spec = _spec(nodes=400, edges=400 * 10, max_expected_degree=100,
                 train_nodes=100)
    a, generated = graphs.load_arrays("g", spec, str(tmp_path))
    b, again = graphs.load_arrays("g", spec, str(tmp_path))
    assert generated and not again
    for k in graphs.ARRAYS:
        assert np.array_equal(a[k], b[k]), k
    other = graphs.cache_key("g", _spec(class_sep=0.2))
    assert other != graphs.cache_key("g", spec)


def test_odd_edge_count_is_refused():
    with pytest.raises(ValueError):
        graphs.generate(_spec(edges=101))
