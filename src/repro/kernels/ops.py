"""jit'd public wrappers for the Pallas kernels.

The kernels target the TPU.  They run in Pallas interpret mode on the
CPU backend only (the test suite's platform); every other backend
compiles them, so a chip run never silently interprets.

The backend is resolved *per call* in a plain-Python wrapper and passed
into the jit as a static argument.  (The previous design read
``jax.default_backend()`` at first trace inside an ``@jax.jit`` body;
the jit cache never revisits a traced constant, so a process that traced
once on CPU — e.g. an import-time warmup before TPU init — silently
pinned interpret mode for its whole lifetime.)  A caller that embeds
these wrappers inside its own ``jit`` still resolves the backend at its
own trace time, which is the earliest point a backend exists for it.
"""
from __future__ import annotations

import functools
import warnings

import jax
import jax.numpy as jnp

from repro.core import telemetry
from repro.kernels import flash_attention as _fa
from repro.kernels import gat_fused as _gat
from repro.kernels import segment_sum as _ss
from repro.kernels import ssd_chunk as _ssd


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


# VMEM-residency / tile-density of the most recently recorded edge
# ordering (host-side: the launchers call record_tile_density;
# edge ids are tracers inside jit, so the wrappers cannot)
_m_tile_active = telemetry.gauge(
    "kernel_tile_density", "blocked-kernel tile locality of the current "
    "edge ordering", metric="active_tile_frac")
_m_tile_rows = telemetry.gauge(
    "kernel_tile_density", metric="src_rows_per_edge_tile")


def record_tile_density(edge_src, edge_dst, num_dst: int) -> dict:
    """Compute and publish the tile-density metrics of an edge ordering
    (``--reorder`` moves these; the blocked kernels sweep every tile,
    so active_tile_frac is the share of that sweep that meets an edge).
    Host-side numpy — call outside jit."""
    d = _ss.edge_tile_density(edge_src, edge_dst, num_dst)
    _m_tile_active.set(d["active_tile_frac"])
    _m_tile_rows.set(d["src_rows_per_edge_tile"])
    return d


@functools.partial(jax.jit, static_argnames=("num_segments", "interpret"))
def _segment_sum_jit(msgs, seg_ids, num_segments: int, interpret: bool):
    return _ss.segment_sum_pallas(msgs, seg_ids, num_segments,
                                  interpret=interpret)


def segment_sum(msgs, seg_ids, num_segments: int):
    """Differentiable blocked segment-sum (scatter-add); the VJP is a
    blocked gather kernel.  See :mod:`repro.kernels.segment_sum`."""
    with jax.named_scope("pallas_unfused"):
        return _segment_sum_jit(msgs, seg_ids, num_segments,
                                interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("num_dst", "interpret"))
def _gss_jit(h, edge_src, edge_dst, coef, num_dst: int, interpret: bool):
    return _ss.gather_scale_segment_sum_pallas(h, edge_src, edge_dst,
                                               coef, num_dst,
                                               interpret=interpret)


@functools.partial(jax.jit, static_argnames=("num_dst", "interpret"))
def _gss_unfused_jit(h, edge_src, edge_dst, coef, num_dst: int,
                     interpret: bool):
    msgs = jnp.take(h, edge_src, axis=0) * coef[:, None]
    return _ss.segment_sum_pallas(msgs, edge_dst, num_dst,
                                  interpret=interpret)


_fallback_warned: set = set()


def gather_scale_segment_sum(h, edge_src, edge_dst, coef, num_dst: int):
    """Fused differentiable gather -> per-edge scale -> segment-sum:
    ``out[d] = sum_{e: edge_dst[e]=d} coef[e] * h[edge_src[e]]`` without
    materializing the (E, F) message tensor in HBM.  Fold the edge mask
    into ``coef``.

    Capacity dispatch: the fused kernel keeps an (S, BF) source slab
    VMEM-resident, which stops fitting somewhere in the thousands of
    rows (exact bound depends on F).  When
    :func:`repro.kernels.segment_sum.fused_fits` says no — e.g. a large
    single-device full graph, where the distributed layouts would have
    sharded the rows — this falls back to XLA gather+scale feeding the
    blocked scatter kernel, whose working set is row-count independent,
    so ``use_kernel=True`` never hits the VMEM assert from this path.
    """
    S, F = h.shape
    interpret = _interpret()
    if not _ss.fused_fits(S, num_dst, F):
        key = (S, num_dst, F)
        if key not in _fallback_warned:      # surface the dispatch once
            _fallback_warned.add(key)
            warnings.warn(
                f"gather_scale_segment_sum: fused-kernel VMEM slab for "
                f"num_src={S}, num_dst={num_dst}, F={F} exceeds the "
                f"budget; dispatching to the unfused blocked kernel "
                f"(the (E, F) message tensor WILL cross HBM)")
        with jax.named_scope("pallas_unfused"):
            return _gss_unfused_jit(h, edge_src, edge_dst, coef, num_dst,
                                    interpret=interpret)
    with jax.named_scope("pallas_fused"):
        return _gss_jit(h, edge_src, edge_dst, coef, num_dst,
                        interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("num_dst", "heads", "interpret"))
def _gat_fused_jit(hs, es, ed, edge_src, edge_dst, mask, num_dst: int,
                   heads: int, interpret: bool):
    return _gat.gat_fused_attention_pallas(hs, es, ed, edge_src,
                                           edge_dst, mask, num_dst,
                                           heads=heads,
                                           interpret=interpret)


def gat_attention(hs, es, ed, edge_src, edge_dst, mask, num_dst: int, *,
                  heads: int):
    """One-pass fused GAT attention aggregation (differentiable).

    ``hs``: (num_src, heads·hd) projected source features; ``es``/``ed``:
    per-head logit halves; returns (num_dst, heads·hd) — per-destination
    softmax over ``leaky_relu(es[src] + ed[dst], 0.2)`` weighting a
    segment-sum of ``hs[src]``, computed in a single grid pass with an
    online softmax so edge logits/alphas never reach HBM (see
    :mod:`repro.kernels.gat_fused`).

    Only for shapes where :func:`repro.kernels.gat_fused.gat_fused_fits`
    holds: ``GATLayer`` runs its ``jax.ops`` attention elsewhere, and the
    kernel asserts its VMEM budget."""
    with jax.named_scope("gat_fused"):
        return _gat_fused_jit(hs, es, ed, edge_src, edge_dst, mask,
                              num_dst, heads, interpret=_interpret())


@functools.partial(jax.jit,
                   static_argnames=("causal", "window", "interpret"))
def _flash_attention_jit(q, k, v, causal: bool, window: int,
                         interpret: bool):
    return _fa.flash_attention_pallas(q, k, v, causal=causal,
                                      window=window, interpret=interpret)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0):
    return _flash_attention_jit(q, k, v, causal, window,
                                interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("interpret",))
def _ssd_chunk_state_jit(x, dt, A, Bm, interpret: bool):
    return _ssd.ssd_chunk_state_pallas(x, dt, A, Bm, interpret=interpret)


def ssd_chunk_state(x, dt, A, Bm):
    return _ssd_chunk_state_jit(x, dt, A, Bm, interpret=_interpret())
