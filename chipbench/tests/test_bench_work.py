"""Work counts against hand counts, the reference's building blocks
against numpy, and the readers' arithmetic."""
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import work as W
from chipbench.references import common, gcn, sage


def test_aggregation_work_of_one_tiny_block():
    # 3 edges, width 2, 3 source rows, 2 destination rows
    assert W.aggregation_flops(3, 2, backward=False) == 12
    assert W.aggregation_flops(3, 2, backward=True) == 24
    fwd = 3 * 2 * 4 + 3 * 12 + 2 * 2 * 4        # rows per edge, ids+coef, out
    bwd = 3 * 2 * 4 + 3 * 12 + 3 * 2 * 4        # grad rows, ids+coef, d-src
    assert W.aggregation_bytes(3, 2, 3, 2, backward=False) == fwd
    assert W.aggregation_bytes(3, 2, 3, 2, backward=True) == fwd + bwd
    assert W.dense_flops(2, 3, 4) == 48


def test_roofline_share_names_its_bound():
    peak = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert W.roofline_share(100.0, 10.0, 2.0, peak) == (50.0, "bytes")
    assert W.roofline_share(400.0, 10.0, 8.0, peak) == (50.0, "flops")


def test_sage_work_by_hand():
    cfg = {"model": {"in_features": 3, "hidden": 4, "classes": 2,
                     "layers": 2}}
    # layer 0: 2 dst, 5 src, 6 edges at width 3; layer 1: 1 dst, 2 src,
    # 2 edges at width 4
    w = sage.work(cfg, [(2, 5, 6), (1, 2, 2)])
    dense = 2 * (2 * 2 * 3 * 4) * 2 + 2 * (2 * 1 * 4 * 2) * 3
    agg = 2 * 6 * 3 + 2 * 2 * 4 * 2
    assert w["model_flops"] == dense + agg
    assert w["aggregation_flops"] == agg
    assert w["aggregation_bytes"] == (
        W.aggregation_bytes(6, 3, 5, 2, backward=False)
        + W.aggregation_bytes(2, 4, 2, 1, backward=True))


def test_gcn_work_by_hand():
    cfg = {"model": {"in_features": 3, "hidden": 4, "classes": 2,
                     "layers": 2}}
    w = gcn.work(cfg, (5, 7))
    dense = 2 * 5 * 3 * 4 * 2 + 2 * 5 * 4 * 2 * 3
    agg = 2 * 7 * 4 * 2 + 2 * 7 * 2 * 2
    assert w["model_flops"] == dense + agg


def test_reference_products_and_aggregation():
    rng = np.random.default_rng(0)
    a = rng.standard_normal((32, 48)).astype(np.float32)
    b = rng.standard_normal((48, 16)).astype(np.float32)
    exact = a.astype(np.float64) @ b.astype(np.float64)
    hi = np.asarray(common.mm(jnp.asarray(a), jnp.asarray(b), "highest"))
    lo = np.asarray(common.mm(jnp.asarray(a), jnp.asarray(b), "high"))
    scale = np.abs(exact).max()
    assert np.abs(hi - exact).max() < 1e-5 * scale
    # three bfloat16 passes lose what one pass of the tails would add
    assert 1e-7 * scale < np.abs(lo - exact).max() < 1e-3 * scale
    with pytest.raises(ValueError):
        common.mm(a, b, "bfloat16")
    src = np.array([0, 2, 2, 1], np.int32)
    dst = np.array([1, 0, 1, 1], np.int32)
    coef = np.array([1.0, 2.0, 0.5, 0.0], np.float32)
    h = rng.standard_normal((3, 5)).astype(np.float32)
    want = np.zeros((2, 5), np.float32)
    for s, d, c in zip(src, dst, coef):
        want[d] += c * h[s]
    got = common.aggregate(jnp.asarray(h), src, dst, coef, 2, chunk=3)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6)


def test_reference_adamw_matches_the_closed_form_first_step():
    opt = {"lr": 0.01, "b1": 0.9, "b2": 0.999, "eps": 1e-8,
           "weight_decay": 0.0, "clip_norm": 0.0}
    p = {"w": jnp.ones((2, 2))}
    g = {"w": jnp.array([[1.0, -2.0], [3.0, -4.0]])}
    new, state = common.adamw(p, g, common.adamw_init(p), opt)
    # step 1 of Adam moves each weight by lr * sign(g)
    np.testing.assert_allclose(np.asarray(new["w"]),
                               1.0 - 0.01 * np.sign(np.asarray(g["w"])),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(state["m"]["w"]) / 0.1,
                               np.asarray(g["w"]), rtol=1e-6)
