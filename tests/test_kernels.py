"""Per-kernel shape/dtype sweeps asserting allclose vs the ref.py oracles
(interpret=True executes the kernel bodies on CPU), gradient tests for
the custom VJPs, hypothesis properties over random shapes, and the
kernel-vs-reference training-equivalence subprocess matrix."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.gat_fused import gat_fused_attention_pallas
from repro.kernels.segment_sum import (gather_scale_segment_sum_pallas,
                                       gather_scale_segment_sum_q_pallas,
                                       segment_sum_pallas)
from repro.kernels.ssd_chunk import ssd_chunk_state_pallas

RNG = np.random.default_rng(42)
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 else \
        dict(atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("E,F,N", [(64, 32, 16), (300, 70, 45),
                                   (1000, 128, 128), (17, 5, 3)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_segment_sum(E, F, N, dtype):
    msgs = jnp.asarray(RNG.normal(size=(E, F)), dtype)
    ids = jnp.asarray(RNG.integers(0, N, E), jnp.int32)
    got = segment_sum_pallas(msgs, ids, N)
    # the kernel accumulates in fp32 scratch; compare against the fp32
    # ground truth with dtype-appropriate tolerance
    want = ref.segment_sum(msgs.astype(jnp.float32), ids, N)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def test_segment_sum_empty_segments():
    msgs = jnp.ones((8, 4), jnp.float32)
    ids = jnp.zeros((8,), jnp.int32)          # everything into segment 0
    got = segment_sum_pallas(msgs, ids, 5)
    assert float(got[0, 0]) == 8.0
    assert float(jnp.abs(got[1:]).sum()) == 0.0


def test_segment_sum_no_edges():
    """E=0 degenerates to a single all-pad tile: zeros out, zeros grad."""
    msgs = jnp.zeros((0, 6), jnp.float32)
    ids = jnp.zeros((0,), jnp.int32)
    got = segment_sum_pallas(msgs, ids, 7)
    assert got.shape == (7, 6)
    assert float(jnp.abs(got).sum()) == 0.0
    grad = jax.grad(lambda m: jnp.sum(segment_sum_pallas(m, ids, 7)))(msgs)
    assert grad.shape == (0, 6)


# ---------------------------------------------------------------------------
# custom-VJP gradients: kernel vs jax.ops autodiff
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("E,F,N", [(64, 32, 16), (300, 70, 45),
                                   (17, 5, 3), (129, 130, 129)])
def test_segment_sum_grad_matches_reference(E, F, N):
    """d/d(msgs) of a weighted sum through the kernel == through
    jax.ops.segment_sum (the backward gather kernel vs XLA's VJP)."""
    msgs = jnp.asarray(RNG.normal(size=(E, F)), jnp.float32)
    ids = jnp.asarray(RNG.integers(0, N, E), jnp.int32)
    w = jnp.asarray(RNG.normal(size=(N, F)), jnp.float32)

    def loss(seg_fn):
        return lambda m: jnp.sum(seg_fn(m, ids, N) * w)

    gk = jax.grad(loss(lambda m, i, n: segment_sum_pallas(m, i, n)))(msgs)
    gr = jax.grad(loss(jax.ops.segment_sum))(msgs)
    np.testing.assert_allclose(np.asarray(gk), np.asarray(gr),
                               atol=2e-5, rtol=2e-5)


def _fused_ref(h, src, dst, coef, num_dst):
    msgs = jnp.take(h, src, axis=0) * coef[:, None]
    return jax.ops.segment_sum(msgs, dst, num_dst)


@pytest.mark.parametrize("S,E,F,N", [(50, 200, 33, 40), (16, 64, 128, 16),
                                     (130, 300, 5, 71)])
def test_fused_forward_matches_reference(S, E, F, N):
    h = jnp.asarray(RNG.normal(size=(S, F)), jnp.float32)
    src = jnp.asarray(RNG.integers(0, S, E), jnp.int32)
    dst = jnp.asarray(RNG.integers(0, N, E), jnp.int32)
    coef = jnp.asarray(RNG.normal(size=(E,)), jnp.float32)
    got = gather_scale_segment_sum_pallas(h, src, dst, coef, N)
    want = _fused_ref(h, src, dst, coef, N)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("S,E,F,N", [(50, 200, 33, 40), (130, 300, 5, 71)])
def test_fused_grads_match_reference(S, E, F, N):
    """dh (fused kernel with src/dst swapped) and dcoef (edge-dot
    kernel) both match the XLA VJP of the unfused expression."""
    h = jnp.asarray(RNG.normal(size=(S, F)), jnp.float32)
    src = jnp.asarray(RNG.integers(0, S, E), jnp.int32)
    dst = jnp.asarray(RNG.integers(0, N, E), jnp.int32)
    coef = jnp.asarray(RNG.normal(size=(E,)), jnp.float32)
    w = jnp.asarray(RNG.normal(size=(N, F)), jnp.float32)

    def loss(fn):
        return lambda h_, c_: jnp.sum(fn(h_, src, dst, c_, N) * w)

    gk = jax.grad(loss(gather_scale_segment_sum_pallas),
                  argnums=(0, 1))(h, coef)
    gr = jax.grad(loss(_fused_ref), argnums=(0, 1))(h, coef)
    np.testing.assert_allclose(np.asarray(gk[0]), np.asarray(gr[0]),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(gk[1]), np.asarray(gr[1]),
                               atol=2e-4, rtol=2e-4)


def test_fused_all_masked_edges():
    """coef carries the edge mask: all-masked input aggregates (and
    back-propagates) exactly zero."""
    S, E, F, N = 20, 40, 12, 10
    h = jnp.asarray(RNG.normal(size=(S, F)), jnp.float32)
    src = jnp.asarray(RNG.integers(0, S, E), jnp.int32)
    dst = jnp.asarray(RNG.integers(0, N, E), jnp.int32)
    coef = jnp.zeros((E,), jnp.float32)
    out = gather_scale_segment_sum_pallas(h, src, dst, coef, N)
    assert float(jnp.abs(out).sum()) == 0.0
    dh = jax.grad(lambda h_: jnp.sum(
        gather_scale_segment_sum_pallas(h_, src, dst, coef, N)))(h)
    assert float(jnp.abs(dh).sum()) == 0.0


def test_fused_capacity_fallback():
    """Above the fused kernel's VMEM capacity, the ops-layer dispatch
    falls back to the unfused blocked kernel (row-count independent)
    instead of tripping the budget assert — use_kernel=True must work
    on large single-device graphs."""
    from repro.kernels import ops as kops
    from repro.kernels.segment_sum import fused_fits

    S = N = 5000
    E, F = 300, 128
    assert not fused_fits(S, N, F)
    h = jnp.asarray(RNG.normal(size=(S, F)), jnp.float32)
    src = jnp.asarray(RNG.integers(0, S, E), jnp.int32)
    dst = jnp.asarray(RNG.integers(0, N, E), jnp.int32)
    coef = jnp.asarray(RNG.normal(size=(E,)), jnp.float32)
    got = kops.gather_scale_segment_sum(h, src, dst, coef, N)
    want = _fused_ref(h, src, dst, coef, N)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    # gradients flow through the fallback path too
    gk = jax.grad(lambda h_: jnp.sum(kops.gather_scale_segment_sum(
        h_, src, dst, coef, N)))(h)
    gr = jax.grad(lambda h_: jnp.sum(_fused_ref(h_, src, dst, coef, N)))(h)
    np.testing.assert_allclose(np.asarray(gk), np.asarray(gr),
                               atol=2e-5, rtol=2e-5)


def test_fused_no_edges():
    h = jnp.asarray(RNG.normal(size=(9, 6)), jnp.float32)
    e = jnp.zeros((0,), jnp.int32)
    out = gather_scale_segment_sum_pallas(h, e, e,
                                          jnp.zeros((0,), jnp.float32), 5)
    assert out.shape == (5, 6)
    assert float(jnp.abs(out).sum()) == 0.0


# ---------------------------------------------------------------------------
# one-pass fused GAT attention (online softmax; logits/alphas never in HBM)
# ---------------------------------------------------------------------------

def _gat_ref(hs, es, ed, src, dst, maskf, N, heads):
    """Multi-pass XLA reference: the attention GATLayer's ``jax_ops``
    path runs over its edges (leaky-relu logits, per-destination softmax
    with the same 1e-9 denominator, weighted segment sum, heads
    concatenated; the layer averages them in its last layer)."""
    hd = hs.shape[1] // heads
    logits = jax.nn.leaky_relu(
        jnp.take(es, src, axis=0) + jnp.take(ed, dst, axis=0), 0.2)
    logits = jnp.where(maskf[:, None] > 0, logits, -1e30)
    mx = jax.ops.segment_max(logits, dst, N)
    mx = jnp.where(jnp.isfinite(mx), mx, 0.0)
    ex = jnp.exp(logits - mx[dst]) * maskf[:, None]
    den = jax.ops.segment_sum(ex, dst, N)
    alpha = ex / (jnp.take(den, dst, axis=0) + 1e-9)
    msgs = jnp.take(hs.reshape(-1, heads, hd), src, axis=0) \
        * alpha[..., None]
    return jax.ops.segment_sum(msgs.reshape(-1, heads * hd), dst, N)


def _gat_case(S, E, N, heads, hd, seed=0, mask_frac=0.0, loops=False):
    """Random attention inputs; with ``loops``, the edge set GATLayer
    hands the kernel: ``E`` sampled edges and then one self-loop per
    destination (source row ``i`` is destination ``i``)."""
    rng = np.random.default_rng(seed)
    hs = jnp.asarray(rng.normal(size=(S, heads * hd)), jnp.float32)
    es = jnp.asarray(rng.normal(size=(S, heads)), jnp.float32) * 0.3
    ed = jnp.asarray(rng.normal(size=(N, heads)), jnp.float32) * 0.3
    src = rng.integers(0, S, E)
    dst = rng.integers(0, N, E)
    mask = rng.random(E) >= mask_frac
    if loops:
        src = np.concatenate([src, np.arange(N)])
        dst = np.concatenate([dst, np.arange(N)])
        mask = np.concatenate([mask, np.ones(N, bool)])
    return (hs, es, ed, jnp.asarray(src, jnp.int32),
            jnp.asarray(dst, jnp.int32), jnp.asarray(mask))


@pytest.mark.parametrize("S,E,N,heads,hd", [
    (40, 150, 40, 4, 16), (25, 90, 17, 2, 8), (64, 300, 64, 1, 32),
    (30, 100, 12, 4, 4),       # bipartite N < S, tiny heads
])
def test_gat_fused_forward_matches_reference(S, E, N, heads, hd):
    hs, es, ed, src, dst, mask = _gat_case(S, E, N, heads, hd,
                                           mask_frac=0.2, loops=True)
    got = gat_fused_attention_pallas(hs, es, ed, src, dst, mask, N,
                                     heads=heads)
    want = _gat_ref(hs, es, ed, src, dst, mask.astype(jnp.float32), N,
                    heads)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("S,E,N,heads,hd", [(40, 150, 40, 4, 16),
                                            (25, 90, 17, 2, 8)])
def test_gat_fused_grads_match_reference(S, E, N, heads, hd):
    """The composed VJP (flash-style alpha recompute + swapped fused
    kernels + closed-form softmax backward) matches XLA autodiff through
    the multi-pass expression on every differentiable input."""
    hs, es, ed, src, dst, mask = _gat_case(S, E, N, heads, hd, seed=1,
                                           mask_frac=0.2, loops=True)
    maskf = mask.astype(jnp.float32)
    w = jnp.asarray(np.random.default_rng(9).normal(
        size=(N, heads * hd)), jnp.float32)

    def loss(fn):
        return lambda a, b, c: jnp.sum(fn(a, b, c) * w)

    k = loss(lambda a, b, c: gat_fused_attention_pallas(
        a, b, c, src, dst, mask, N, heads=heads))
    r = loss(lambda a, b, c: _gat_ref(a, b, c, src, dst, maskf, N, heads))
    gk = jax.grad(k, argnums=(0, 1, 2))(hs, es, ed)
    gr = jax.grad(r, argnums=(0, 1, 2))(hs, es, ed)
    for got, want, name in zip(gk, gr, ("dhs", "des", "ded")):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=1e-5, err_msg=name)


def test_gat_fused_no_edges():
    hs, es, ed, _, _, _ = _gat_case(9, 10, 7, 2, 8)
    z = jnp.zeros((0,), jnp.int32)
    out = gat_fused_attention_pallas(hs, es, ed, z, z,
                                     jnp.zeros((0,), bool), 7, heads=2)
    assert out.shape == (7, 16)
    assert float(jnp.abs(out).sum()) == 0.0
    dhs = jax.grad(lambda a: jnp.sum(gat_fused_attention_pallas(
        a, es, ed, z, z, jnp.zeros((0,), bool), 7, heads=2)))(hs)
    assert float(jnp.abs(dhs).sum()) == 0.0


def test_gat_fused_all_masked():
    """Every edge masked: softmax has no support anywhere -> exact
    zeros out (no NaNs from exp around the -1e30 sentinel)."""
    hs, es, ed, src, dst, _ = _gat_case(20, 60, 15, 4, 8, seed=2)
    mask = jnp.zeros((60,), bool)
    out = gat_fused_attention_pallas(hs, es, ed, src, dst, mask, 15,
                                     heads=4)
    assert not bool(jnp.any(jnp.isnan(out)))
    assert float(jnp.abs(out).sum()) == 0.0


def test_gat_fused_single_neighbor_copies_source_row():
    """One valid in-edge per destination -> alpha = 1 exactly, so the
    output is the source hs row verbatim; untouched dsts stay zero."""
    heads, hd = 2, 8
    hs, es, ed, _, _, _ = _gat_case(6, 4, 5, heads, hd, seed=3)
    src = jnp.asarray([4, 1, 0], jnp.int32)
    dst = jnp.asarray([0, 2, 3], jnp.int32)
    mask = jnp.ones((3,), bool)
    out = np.asarray(gat_fused_attention_pallas(
        hs, es, ed, src, dst, mask, 5, heads=heads))
    np.testing.assert_allclose(out[0], np.asarray(hs)[4], atol=1e-5)
    np.testing.assert_allclose(out[2], np.asarray(hs)[1], atol=1e-5)
    np.testing.assert_allclose(out[3], np.asarray(hs)[0], atol=1e-5)
    assert np.abs(out[[1, 4]]).sum() == 0.0


# ---------------------------------------------------------------------------
# int8-in / fp32-accumulate aggregation
# ---------------------------------------------------------------------------

def _quantize_rows(h):
    mn = h.min(axis=1, keepdims=True)
    scale = np.maximum((h.max(axis=1, keepdims=True) - mn) / 255.0, 1e-12)
    q = np.rint((h - mn) / scale).astype(np.uint8)
    return q, mn.astype(np.float32), scale.astype(np.float32)


@pytest.mark.parametrize("S,E,F,N", [(50, 200, 33, 40), (16, 64, 128, 16),
                                     (130, 300, 5, 71)])
def test_int8_in_matches_decode_then_fp32(S, E, F, N):
    """The quantized kernel dequantizes per source slab in VMEM — it
    must agree with decode-to-fp32-then-aggregate to fp32 roundoff
    (same affine, same accumulation order)."""
    rng = np.random.default_rng(7)
    h = rng.normal(size=(S, F)).astype(np.float32)
    q, mn, scale = _quantize_rows(h)
    src = jnp.asarray(rng.integers(0, S, E), jnp.int32)
    dst = jnp.asarray(rng.integers(0, N, E), jnp.int32)
    coef = jnp.asarray(rng.normal(size=(E,)), jnp.float32)
    got = gather_scale_segment_sum_q_pallas(
        jnp.asarray(q), jnp.asarray(mn), jnp.asarray(scale), src, dst,
        coef, N)
    decoded = mn + q.astype(np.float32) * scale
    want = gather_scale_segment_sum_pallas(jnp.asarray(decoded), src,
                                           dst, coef, N)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_int8_in_error_bound_vs_fp32_truth():
    """Against the TRUE fp32 aggregation, the int8-in result is bounded
    by the codec's per-row quantization error: |err| <= sum over
    contributing edges of |coef_e| * scale_src[e] / 2, row-feature-wise."""
    rng = np.random.default_rng(11)
    S, E, F, N = 40, 160, 24, 30
    h = rng.normal(size=(S, F)).astype(np.float32)
    q, mn, scale = _quantize_rows(h)
    src = rng.integers(0, S, E)
    dst = rng.integers(0, N, E)
    coef = rng.normal(size=(E,)).astype(np.float32)
    got = np.asarray(gather_scale_segment_sum_q_pallas(
        jnp.asarray(q), jnp.asarray(mn), jnp.asarray(scale),
        jnp.asarray(src, jnp.int32), jnp.asarray(dst, jnp.int32),
        jnp.asarray(coef), N))
    truth = np.zeros((N, F), np.float64)
    np.add.at(truth, dst, h[src].astype(np.float64) * coef[:, None])
    bound = np.zeros((N,), np.float64)
    np.add.at(bound, dst,
              np.abs(coef) * (scale[src, 0] / 2.0 + 1e-7))
    err = np.abs(got - truth).max(axis=1)
    assert (err <= bound + 1e-5).all(), (err - bound).max()


def test_int8_in_no_edges():
    q = jnp.zeros((9, 6), jnp.uint8)
    mn = jnp.zeros((9, 1), jnp.float32)
    sc = jnp.ones((9, 1), jnp.float32)
    z = jnp.zeros((0,), jnp.int32)
    out = gather_scale_segment_sum_q_pallas(
        q, mn, sc, z, z, jnp.zeros((0,), jnp.float32), 5)
    assert out.shape == (5, 6)
    assert float(jnp.abs(out).sum()) == 0.0


# ---------------------------------------------------------------------------
# hypothesis properties over random (E, F, num_segments)
# ---------------------------------------------------------------------------

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                     # pragma: no cover
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    @settings(max_examples=20, deadline=None)
    @given(E=st.integers(0, 260), F=st.integers(1, 140),
           N=st.integers(1, 150), seed=st.integers(0, 2**31 - 1))
    def test_property_segment_sum_fwd_bwd(E, F, N, seed):
        """Forward and VJP match jax.ops for arbitrary shapes, including
        E=0 and non-multiples of every tile size."""
        rng = np.random.default_rng(seed)
        msgs = jnp.asarray(rng.normal(size=(E, F)), jnp.float32)
        ids = jnp.asarray(rng.integers(0, N, E), jnp.int32)
        got = segment_sum_pallas(msgs, ids, N)
        want = jax.ops.segment_sum(msgs, ids, N)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=3e-5, rtol=3e-5)
        w = jnp.asarray(rng.normal(size=(N, F)), jnp.float32)
        gk = jax.grad(lambda m: jnp.sum(
            segment_sum_pallas(m, ids, N) * w))(msgs)
        gr = jax.grad(lambda m: jnp.sum(
            jax.ops.segment_sum(m, ids, N) * w))(msgs)
        np.testing.assert_allclose(np.asarray(gk), np.asarray(gr),
                                   atol=3e-5, rtol=3e-5)

    @settings(max_examples=15, deadline=None)
    @given(S=st.integers(1, 120), E=st.integers(0, 200),
           F=st.integers(1, 140), N=st.integers(1, 90),
           mask_all=st.booleans(), seed=st.integers(0, 2**31 - 1))
    def test_property_fused_fwd_bwd(S, E, F, N, mask_all, seed):
        """Fused kernel (fwd + dh) matches the unfused XLA expression,
        including all-masked edge sets (coef == 0 everywhere)."""
        rng = np.random.default_rng(seed)
        h = jnp.asarray(rng.normal(size=(S, F)), jnp.float32)
        src = jnp.asarray(rng.integers(0, S, E), jnp.int32)
        dst = jnp.asarray(rng.integers(0, N, E), jnp.int32)
        coef = jnp.zeros((E,), jnp.float32) if mask_all else \
            jnp.asarray(rng.normal(size=(E,)), jnp.float32)
        got = gather_scale_segment_sum_pallas(h, src, dst, coef, N)
        want = _fused_ref(h, src, dst, coef, N)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=3e-5, rtol=3e-5)
        w = jnp.asarray(rng.normal(size=(N, F)), jnp.float32)
        gk = jax.grad(lambda h_: jnp.sum(gather_scale_segment_sum_pallas(
            h_, src, dst, coef, N) * w))(h)
        gr = jax.grad(lambda h_: jnp.sum(
            _fused_ref(h_, src, dst, coef, N) * w))(h)
        np.testing.assert_allclose(np.asarray(gk), np.asarray(gr),
                                   atol=3e-5, rtol=3e-5)

    @settings(max_examples=15, deadline=None)
    @given(S=st.integers(1, 60), E=st.integers(0, 150),
           N=st.integers(1, 50), heads=st.sampled_from([1, 2, 4]),
           hd=st.sampled_from([4, 8, 16]),
           seed=st.integers(0, 2**31 - 1))
    def test_property_gat_alphas_sum_to_one(S, E, N, heads, hd, seed):
        """The alpha-sum softmax property, observed through the fused
        kernel: with every source's hs row set to the same constant
        vector c, out[d] = c * (sum of d's alphas) — exactly c wherever
        d has a valid in-edge, exactly 0 elsewhere (pad/masked edges
        contribute nothing)."""
        rng = np.random.default_rng(seed)
        c = rng.normal(size=(1, heads * hd)).astype(np.float32)
        hs = jnp.asarray(np.repeat(c, S, axis=0))
        es = jnp.asarray(rng.normal(size=(S, heads)), jnp.float32)
        ed = jnp.asarray(rng.normal(size=(N, heads)), jnp.float32)
        src = jnp.asarray(rng.integers(0, S, E), jnp.int32)
        dst = jnp.asarray(rng.integers(0, N, E), jnp.int32)
        mask = jnp.asarray(rng.random(E) < 0.7)
        out = np.asarray(gat_fused_attention_pallas(
            hs, es, ed, src, dst, mask, N, heads=heads))
        has_edge = np.zeros(N, bool)
        np.add.at(has_edge, np.asarray(dst), np.asarray(mask))
        np.testing.assert_allclose(out[has_edge],
                                   np.repeat(c, has_edge.sum(), axis=0),
                                   atol=3e-5, rtol=3e-5)
        assert np.abs(out[~has_edge]).sum() == 0.0


# ---------------------------------------------------------------------------
# training equivalence: jax.grad through use_kernel=True over a device
# matrix (subprocess so the forced host-device topology can be set)
# ---------------------------------------------------------------------------

@pytest.mark.distributed
@pytest.mark.parametrize("n_dev", [1, 2, 4])
def test_kernel_training_equivalence(n_dev):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable,
         os.path.join(ROOT, "tests", "kernel_train_check.py"),
         str(n_dev), "hash"],
        capture_output=True, text=True, timeout=600, env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "PASS kernel-equivalence" in r.stdout, r.stdout


@pytest.mark.distributed
@pytest.mark.parametrize("n_dev", [1, 2])
def test_gat_fused_training_equivalence(n_dev):
    """Full GAT training through the fused one-pass kernel vs the XLA
    reference from the same init: every parameter within 1e-5 after 10
    steps, single-device and under a forced 2-device pmap."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable,
         os.path.join(ROOT, "tests", "gat_train_check.py"), str(n_dev)],
        capture_output=True, text=True, timeout=600, env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "PASS gat-fused-equivalence" in r.stdout, r.stdout


@pytest.mark.parametrize("B,H,K,Sq,Skv,hd", [
    (1, 2, 2, 32, 32, 16),
    (2, 4, 2, 64, 64, 32),     # GQA G=2
    (1, 8, 1, 48, 96, 64),     # MQA, decode-ish Sq<Skv, non-multiple of 32
])
@pytest.mark.parametrize("window", [0, 16])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(B, H, K, Sq, Skv, hd, window, dtype):
    q = jnp.asarray(RNG.normal(size=(B, H, Sq, hd)), dtype)
    k = jnp.asarray(RNG.normal(size=(B, K, Skv, hd)), dtype)
    v = jnp.asarray(RNG.normal(size=(B, K, Skv, hd)), dtype)
    got = flash_attention_pallas(q, k, v, causal=True, window=window,
                                 bq=32, bk=32)
    want = ref.flash_attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def test_flash_attention_non_causal():
    q = jnp.asarray(RNG.normal(size=(1, 2, 32, 16)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(1, 2, 32, 16)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(1, 2, 32, 16)), jnp.float32)
    got = flash_attention_pallas(q, k, v, causal=False, bq=16, bk=16)
    want = ref.flash_attention(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=2e-5)


@pytest.mark.parametrize("B,L,H,P,G,N", [
    (1, 16, 4, 8, 1, 16), (2, 32, 8, 16, 1, 24), (1, 64, 8, 32, 2, 64),
])
def test_ssd_chunk_state(B, L, H, P, G, N):
    x = jnp.asarray(RNG.normal(size=(B, L, H, P)), jnp.float32)
    dt = jnp.asarray(RNG.random((B, L, H)), jnp.float32)
    A = -jnp.asarray(RNG.random(H) + 0.1, jnp.float32)
    Bm = jnp.asarray(RNG.normal(size=(B, L, G, N)), jnp.float32)
    got = ssd_chunk_state_pallas(x, dt, A, Bm, bh=min(4, H))
    want = ref.ssd_chunk_state(x, dt, A, Bm)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4,
                               rtol=1e-4)
