"""Communication-plane invariants (repro.core.comm): codec round-trips,
framing/byte accounting, error feedback, and the single canonical
HEADER_BYTES shared by every transfer path."""
import numpy as np
import pytest

from repro.core.comm import (CODECS, HEADER_BYTES, INT8_ROW_META_BYTES,
                             Transport, resolve_codec)

try:
    from hypothesis import example, given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                      # unit tests still run without it
    HAVE_HYPOTHESIS = False

    def given(*a, **k):                  # noqa: D103 - stub decorator
        return lambda f: pytest.mark.skip("hypothesis not installed")(f)

    settings = example = given

    class st:                            # noqa: D101 - stub strategies
        floats = integers = lists = staticmethod(lambda *a, **k: None)


# ---------------------------------------------------------------------------
# one canonical HEADER_BYTES (the dedup satellite)
# ---------------------------------------------------------------------------

def test_header_bytes_is_canonical_everywhere():
    """`core.caching` and `core.halo` must account the SAME per-RPC
    envelope object the comm plane defines — no more per-subsystem
    copies."""
    from repro.core import caching, halo
    assert caching.HEADER_BYTES is HEADER_BYTES
    assert halo.HEADER_BYTES is HEADER_BYTES


def test_resolve_codec():
    assert resolve_codec(None).name == "fp32"
    assert resolve_codec("int8") is CODECS["int8"]
    assert resolve_codec(CODECS["bf16"]) is CODECS["bf16"]
    with pytest.raises(KeyError):
        resolve_codec("fp16")


# ---------------------------------------------------------------------------
# codec round-trips
# ---------------------------------------------------------------------------

def _rows(n=7, dim=19, seed=0, scale=3.0):
    rng = np.random.default_rng(seed)
    return (rng.normal(size=(n, dim)) * scale).astype(np.float32)


def test_fp32_roundtrip_bit_exact():
    x = _rows()
    c = CODECS["fp32"]
    p = c.encode(x)
    assert p.nbytes == x.shape[0] * 4 * x.shape[1]
    np.testing.assert_array_equal(c.decode(p), x)
    assert c.identity and not c.error_feedback


def test_bf16_roundtrip_error_bound():
    """bf16 keeps 8 mantissa bits: relative error <= 2**-8 per element."""
    x = _rows(scale=100.0)
    c = CODECS["bf16"]
    p = c.encode(x)
    assert p.nbytes == x.shape[0] * 2 * x.shape[1]
    d = c.decode(p)
    assert (np.abs(d - x) <= np.abs(x) * 2.0 ** -8 + 1e-30).all()
    # exactly-representable values survive untouched
    e = np.asarray([[0.0, 1.0, -2.5, 1024.0]], np.float32)
    np.testing.assert_array_equal(c.qdq(e), e)


def test_int8_roundtrip_error_bound_and_wire_size():
    x = _rows(n=5, dim=64)
    c = CODECS["int8"]
    p = c.encode(x)
    assert p.nbytes == 5 * (64 + INT8_ROW_META_BYTES)
    # the ~4x claim: at hidden=64 (the bench width) the 8-byte row
    # metadata is amortized below the 30% acceptance line
    assert p.nbytes <= 5 * 64 * 4 * 0.30
    d = c.decode(p)
    scale = p.data[2]                            # (n, 1) per-row step
    assert (np.abs(d - x) <= scale * 0.5 + 1e-12).all()


def test_int8_constant_row_is_exact():
    x = np.full((2, 9), 3.25, np.float32)
    np.testing.assert_array_equal(CODECS["int8"].qdq(x), x)


@pytest.mark.parametrize("qdq", ["host", "jax"])
def test_int8_range_below_normal_steps_keeps_a_nonzero_step(qdq):
    """A row whose range is under 255 normal float32 steps quantizes with
    the smallest normal float32 as its step, neither 0 nor a subnormal
    (which the chip flushes), so every element stays within half a
    step."""
    import jax.numpy as jnp
    x = np.asarray([[0.0, 8.688e-44, 1e-40, 3e-39]], np.float32)
    c = CODECS["int8"]
    tiny = float(np.finfo(np.float32).tiny)
    if qdq == "host":
        p = c.encode(x)
        assert float(p.data[2][0, 0]) == tiny
        d = c.decode(p)
    else:
        d = np.asarray(c.jax_qdq(jnp.asarray(x)))
        assert c.jax_qdq(jnp.asarray(x, jnp.bfloat16)).dtype == jnp.bfloat16
    assert d.dtype == np.float32
    assert (np.abs(d.astype(np.float64) - x) <= tiny / 2).all()


@pytest.mark.parametrize("codec", ["fp32", "bf16", "int8"])
def test_jax_qdq_matches_host_qdq(codec):
    """The in-step quantizer (`jax_qdq`, used by forward_stale) and the
    host transport must agree on the wire loss to float tolerance."""
    import jax.numpy as jnp
    c = CODECS[codec]
    x = _rows(n=6, dim=24, seed=3)
    host = c.qdq(x)
    dev = np.asarray(c.jax_qdq(jnp.asarray(x)))
    scale = (x.max(1, keepdims=True) - x.min(1, keepdims=True)) / 255.0
    tol = 0.0 if codec != "int8" else scale      # rounding-direction ties
    assert (np.abs(dev - host) <= tol + 1e-6).all()


# ---------------------------------------------------------------------------
# transport framing + accounting
# ---------------------------------------------------------------------------

def test_transport_zero_row_send_is_free():
    t = Transport("int8", path="test/zero-row")
    out = t.send(np.zeros((0, 8), np.float32))
    assert out.shape == (0, 8)
    assert t.total_bytes == 0 and t.requests == 0


def test_transport_charges_payload_plus_one_header_per_send():
    t = Transport("int8", path="test/framing")
    t.send(_rows(n=4, dim=16))
    t.send(_rows(n=2, dim=16))
    c = CODECS["int8"]
    assert t.payload_bytes == 6 * c.wire_bytes_per_row(16)
    assert t.header_bytes == 2 * HEADER_BYTES
    assert t.rows_sent == 6 and t.requests == 2
    st = t.stats()
    assert st["total_bytes"] == t.payload_bytes + t.header_bytes
    t.reset_counters()
    assert t.total_bytes == 0 and t.rows_sent == 0


def test_residual_store_values_grow_with_touched_rows():
    """Error-feedback VALUE rows grow with the rows actually sent, not
    with the id space (the id→slot map is a cheap dense int32 vector) —
    never-sent ids read back zeros."""
    from repro.core.comm import ResidualStore
    rs = ResidualStore(n_rows=200_000, dim=4)
    rs.scatter(np.asarray([100_000, 7]), np.ones((2, 4)) * 2.5)
    assert rs._used == 2
    assert len(rs._buf) < 100                    # values, not id space
    got = rs.gather(np.asarray([7, 42, 100_000]))
    np.testing.assert_array_equal(got[0], np.full(4, 2.5, np.float32))
    np.testing.assert_array_equal(got[1], np.zeros(4, np.float32))
    np.testing.assert_array_equal(got[2], np.full(4, 2.5, np.float32))
    # growth past the initial capacity keeps earlier rows intact
    ids = np.arange(40)
    rs.scatter(ids, np.tile(np.arange(40, dtype=np.float32)[:, None],
                            (1, 4)))
    assert float(rs.gather(np.asarray([39]))[0, 0]) == 39.0
    assert float(rs.gather(np.asarray([100_000]))[0, 0]) == 2.5


def test_transport_fp32_send_is_identity():
    t = Transport("fp32", path="test/fp32-identity")
    x = _rows()
    np.testing.assert_array_equal(t.send(x), x)
    assert t.total_bytes == x.shape[0] * 4 * x.shape[1] + HEADER_BYTES


def test_featurestore_all_false_fetch_masked_is_free_under_compression():
    """The dedup-satellite regression, on the compressed path: an
    all-False mask must add 0 bytes even when an int8 transport (with
    residual state) is attached."""
    from repro.core.caching import FeatureStore
    from repro.graph import generators as G
    g = G.featurize(G.sbm(64, 4, p_in=0.9, p_out=0.02, seed=0), 8, seed=0)
    store = FeatureStore(g, np.zeros(0, np.int64), codec="int8")
    out = store.fetch_masked(np.asarray([1, 2, -1]), np.zeros(3, bool))
    assert store.transferred_bytes == 0
    assert (store.hits, store.misses, store.requests) == (0, 0, 0)
    assert not out.any()
    # a real miss pays compressed rows + one header and returns the
    # DECODED value (bounded error, not the raw row)
    got = store.fetch_masked(np.asarray([1, 2, -1]),
                             np.asarray([True, False, False]))
    assert store.transferred_bytes == store.bytes_per_row + HEADER_BYTES
    assert store.bytes_per_row == 8 + INT8_ROW_META_BYTES
    scale = (g.features[1].max() - g.features[1].min()) / 255.0
    assert np.abs(got[0] - g.features[1]).max() <= scale * 0.5 + 1e-12


# ---------------------------------------------------------------------------
# 2-device int8 training subprocess (tier-2 / run_tests.sh comm)
# ---------------------------------------------------------------------------

@pytest.mark.distributed
@pytest.mark.parametrize("codec", ["bf16", "int8"])
def test_comm_train_check_subprocess(codec):
    """int8/bf16 full-graph training on 2 forced host devices: finite
    losses, compressed bytes/step (see tests/comm_train_check.py)."""
    import os
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(root, "src")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, os.path.join(root, "tests",
                                      "comm_train_check.py"), "2", codec],
        capture_output=True, text=True, timeout=600, env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "PASS comm-train" in r.stdout, r.stdout


# ---------------------------------------------------------------------------
# hypothesis properties
# ---------------------------------------------------------------------------

# hypothesis refuses width=32 bounds that float32 cannot represent exactly
F32_MAX = float(np.finfo(np.float32).max)
finite_f32 = st.floats(min_value=-F32_MAX, max_value=F32_MAX,
                       allow_nan=False, allow_infinity=False, width=32)


@settings(deadline=None, max_examples=60)
@given(st.lists(finite_f32, min_size=2, max_size=24))
@example([0.0, 8.688e-44])       # range a subnormal: its step underflowed
def test_int8_error_at_most_half_scale_any_finite_row(row):
    """Property (a): per-element int8 encode/decode error <= scale/2 for
    arbitrary finite float32 rows (plus float32 representation spacing —
    when the row range is below the ulp of its magnitude, the codec
    cannot beat the format itself)."""
    x = np.asarray([row], np.float32)
    c = CODECS["int8"]
    p = c.encode(x)
    d = c.decode(p)
    scale = float(p.data[2][0, 0])
    slack = np.spacing(np.maximum(np.abs(x), np.float32(scale)))
    assert (np.abs(d - x).astype(np.float64)
            <= 0.5 * scale + 2.0 * slack).all()


@settings(deadline=None, max_examples=40)
@given(st.lists(st.floats(min_value=-1e4, max_value=1e4,
                          allow_nan=False, width=32),
                min_size=2, max_size=16),
       st.integers(min_value=2, max_value=12))
def test_error_feedback_mean_converges_to_truth(row, sends):
    """Property (b): with sender-side error feedback, the running mean of
    decoded sends of one fixed row converges to the true row — the
    accumulated bias after T sends is the (bounded) residual / T."""
    x = np.asarray([row], np.float32)
    t = Transport("int8", n_rows=4, path="test/error-feedback")
    ids = np.asarray([2])
    acc = np.zeros_like(x, np.float64)
    max_scale = 0.0
    for _ in range(sends):
        p = CODECS["int8"].encode(x.astype(np.float64)
                                  + (t.residuals.gather(ids)
                                     if t.residuals is not None else 0.0))
        max_scale = max(max_scale, float(p.data[2].max()))
        acc += t.send(x, row_ids=ids)
    err = np.abs(acc / sends - x).max()
    # slack: float32 decode rounding + float32 residual storage rounding
    slack = float(np.spacing(np.float32(np.abs(x).max() + max_scale)))
    assert err <= (0.5 * max_scale) / sends + 4.0 * slack + 1e-12
    # and the channel accounted every send
    assert t.requests == sends
    assert t.payload_bytes == sends * CODECS["int8"].wire_bytes_per_row(
        x.shape[1])
