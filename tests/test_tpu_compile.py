"""Ahead-of-time compiles of the GNN Pallas kernels for a described TPU
v5e chip (no chip attached).

Interpret mode accepts layouts, casts and precisions that the chip's
compiler refuses; these tests hand each kernel to that compiler with
``interpret=False``, forward and backward, at the shapes the one-chip
smoke run (``chip_smoke.py``: GraphSAGE at Reddit's widths, batch 1024,
fanouts [5, 5]) calls it with.  A kernel that run does not call is
compiled at the largest fanout-5 block its VMEM capacity predicate
accepts.  Each test asserts that the kernel reached the compiled program
as a ``tpu_custom_call``.

The topology is described inside a module fixture, never while a module
is imported: only one process may load the TPU library, and test workers
import every test file.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import gat_fused as GF
from repro.kernels import segment_sum as SS

BATCH, FANOUT = 1024, 5          # chip_smoke.py's batch and fanouts
FEAT, HIDDEN, HEADS = 602, 256, 4
# (E, F, num_dst) of the two segment sums the smoke run's SAGE blocks
# dispatch to: the inner block aggregates raw features, the outer block
# the hidden layer; neither block's source slab fits the fused kernel
INNER = (BATCH * (1 + FANOUT) * FANOUT, FEAT, BATCH * (1 + FANOUT))
OUTER = (BATCH * FANOUT, HIDDEN, BATCH)


def _largest_block(fits) -> int:
    """Largest 8-aligned destination count ``d`` of a fanout-5 block
    (``6 d`` source rows) that the capacity predicate ``fits`` accepts."""
    d = 8
    while fits(6 * (d + 8), d + 8):
        d += 8
    assert fits(6 * d, d)
    return d


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:           # noqa: BLE001 — any failure means skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def compile_for_chip(topo):
    """``compile_for_chip(fn, *shapes)`` lowers and compiles ``fn`` for
    one described v5e chip and returns the compiled program's text.  The
    persistent compilation cache stays off meanwhile: an entry written
    for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache

    one_chip = SingleDeviceSharding(topo.devices[0])
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    def run(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in shapes]
        return jax.jit(fn).lower(*args).compile().as_text()

    yield run
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sum_grads(f, argnums):
    """Forward plus the VJP of ``sum(f(...))`` w.r.t. ``argnums``."""
    def fwd_bwd(*args):
        out, vjp = jax.vjp(lambda *a: f(*a), *args)
        grads = vjp(jnp.ones_like(out))
        return out, tuple(grads[i] for i in argnums)
    return fwd_bwd


@pytest.mark.parametrize("E,F,N", [INNER, OUTER], ids=["inner", "outer"])
def test_segment_sum_compiles(compile_for_chip, E, F, N):
    seg = functools.partial(SS.segment_sum_pallas, num_segments=N,
                            interpret=False)
    text = compile_for_chip(_sum_grads(seg, (0,)),
                            ((E, F), jnp.float32), ((E,), jnp.int32))
    assert "tpu_custom_call" in text


def test_fused_gather_scale_segment_sum_compiles(compile_for_chip):
    D = _largest_block(lambda s, d: SS.fused_fits(s, d, HIDDEN))
    S, E = 6 * D, FANOUT * D

    def f(h, src, dst, coef):
        return SS.gather_scale_segment_sum_pallas(h, src, dst, coef, D,
                                                  interpret=False)

    text = compile_for_chip(_sum_grads(f, (0, 3)),
                            ((S, HIDDEN), jnp.float32), ((E,), jnp.int32),
                            ((E,), jnp.int32), ((E,), jnp.float32))
    assert "tpu_custom_call" in text


def test_int8_in_fused_compiles(compile_for_chip):
    D = _largest_block(lambda s, d: SS.fused_fits(s, d, FEAT))
    S, E = 6 * D, FANOUT * D

    def f(q, mn, scale, src, dst, coef):
        return SS.gather_scale_segment_sum_q_pallas(
            q, mn, scale, src, dst, coef, D, interpret=False)

    text = compile_for_chip(f, ((S, FEAT), jnp.uint8),
                            ((S, 1), jnp.float32), ((S, 1), jnp.float32),
                            ((E,), jnp.int32), ((E,), jnp.int32),
                            ((E,), jnp.float32))
    assert "tpu_custom_call" in text


def test_gat_fused_attention_compiles(compile_for_chip):
    hd = HIDDEN // HEADS
    D = _largest_block(lambda s, d: GF.gat_fused_fits(s, d, HEADS, hd))
    S, E = 6 * D, FANOUT * D

    def f(hs, es, ed, src, dst, mask):
        return GF.gat_fused_attention_pallas(hs, es, ed, src, dst, mask, D,
                                             heads=HEADS, interpret=False)

    text = compile_for_chip(_sum_grads(f, (0, 1, 2)),
                            ((S, HIDDEN), jnp.float32),
                            ((S, HEADS), jnp.float32),
                            ((D, HEADS), jnp.float32), ((E,), jnp.int32),
                            ((E,), jnp.int32), ((E,), jnp.float32))
    assert "tpu_custom_call" in text
