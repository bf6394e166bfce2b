"""Pallas TPU kernels: blocked segment-sum and the fused
gather-scale-segment-sum (the GNN aggregation hot-spot), both
differentiable via custom VJPs whose backward passes are themselves
blocked Pallas kernels.

The survey's Gather phase is a sparse scatter-add on GPUs.  TPUs have no
efficient scatter, so we re-express the reduction as a *blocked one-hot
matmul* (MXU-friendly; the NeuGraph/GridGraph 2D-grid idea as BlockSpec
tiling):

    out[nb, fb] += onehot(seg_ids[eb] - nb0).T @ msgs[eb, fb]

Grid = (N/BN, F/BF, E/BE) with the edge dimension innermost, so each
(node-tile, feature-tile) output block stays resident in VMEM while all
edge tiles accumulate into it.

**VJP.**  The transpose of a scatter-add is a gather:
``grad_msgs = grad_out[seg_ids]``.  That gather is the same one-hot
trick with the roles of the matmul operands swapped,

    grad_msgs[eb, fb] += onehot(seg_ids[eb] - nb0) @ grad_out[nb, fb]

on grid (E/BE, F/BF, N/BN) with the *node* dimension innermost (each
edge id lands in exactly one node tile, so the accumulation over node
tiles reconstructs the gathered row exactly).

**Fusion.**  :func:`gather_scale_segment_sum_pallas` runs the whole
Scatter -> ApplyEdge (scale) -> Gather pipeline inside one kernel: the
source-feature matrix is kept VMEM-resident one feature-tile at a time
(grid (F/BF, N/BN, E/BE), feature dimension *outermost*, so the block is
DMA'd from HBM once per feature tile, not once per edge tile), rows are
gathered by a one-hot matmul, scaled by the per-edge coefficient, and
accumulated straight into destination tiles — the ``(E, F)`` message
tensor never exists in HBM.  Its VJP reuses the fused kernel with source
and destination swapped (``dh``) plus a per-edge dot-product kernel
(``dcoef``).

**Tiles.**  The feature tile ``bf`` adapts to F (:func:`_pick_bf`): wide
inputs get lane-aligned multiples of 128, narrow inputs (GAT per-head
logits, F of a few) get a sublane-aligned sliver instead of burning a
full 128-lane MXU tile on padding.  Every entry point asserts the VMEM
working set fits (:func:`_assert_vmem`).

VMEM working set per step of the scatter kernel: BE*BF (msgs) + BE*BN
(one-hot) + BN*BF (acc) = 128*128*3 floats ~= 192 KiB with the default
tiles — comfortably inside the ~16 MiB budget, all matmul dims
128-aligned for the MXU.  The fused kernel additionally keeps an
(S_pad, BF) source-feature slab resident, so it only engages while the
gathered source matrix fits VMEM (a few thousand rows at F=128 —
mini-batch blocks always, full graphs up to moderate size; note the
distributed pull path hands it the *all-gathered* (N_pad, F) matrix,
not a per-device shard).  :func:`fused_fits` is the capacity predicate;
the :mod:`repro.kernels.ops` dispatch falls back to the unfused blocked
kernel (row-count-independent working set) with a one-time warning, and
the budget asserts catch direct callers that overshoot.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BE = 128   # edge tile
DEFAULT_BN = 128   # segment (node) tile
DEFAULT_BF = 128   # feature tile (upper bound; _pick_bf adapts to F)

LANE = 128         # TPU lane width (last-dim tiling granularity)
SUBLANE = 8        # float32 sublane granularity
MAX_BF = 512
VMEM_BUDGET = 8 * 2**20    # bytes; half the ~16 MiB/core so double
                           # buffering of input blocks still fits


def _pick_bf(F: int) -> int:
    """Adaptive feature tile: the smallest aligned width covering ``F``.

    Wide inputs get lane-aligned (multiples of 128, capped at MAX_BF so
    the VMEM slab stays bounded); narrow inputs — GAT per-head logits
    are F=heads, a handful — get a sublane-aligned sliver, so F=4 costs
    an 8-wide tile instead of the 32x padding waste of a hardcoded 128.
    """
    if F >= LANE:
        return min(-(-F // LANE) * LANE, MAX_BF)
    return max(SUBLANE, -(-F // SUBLANE) * SUBLANE)


def _assert_vmem(n_floats: int, *, what: str) -> None:
    """Fail loudly (at trace time) if a kernel's per-step VMEM working
    set exceeds the budget — mis-sized tiles must not silently spill."""
    bytes_ = 4 * n_floats
    assert bytes_ <= VMEM_BUDGET, (
        f"{what}: VMEM working set {bytes_ / 2**20:.1f} MiB exceeds the "
        f"{VMEM_BUDGET / 2**20:.0f} MiB budget — shrink the tile sizes "
        f"or shard the source dimension")


def _pad_edges(E: int, be: int) -> int:
    """Edge count padded to a whole tile; E=0 still gets one (all-pad)
    tile so the grid is never empty and the kernel always emits."""
    return max(-(-E // be) * be, be)


def _edge_column(v, Ep: int, fill, dtype) -> jax.Array:
    """Per-edge operand (ids, coefficients, masks) as an ``(Ep, 1)``
    column padded with ``fill``.  Kernels read it in ``(be, 1)`` blocks:
    the TPU compiler refuses 1-D ``(be,)`` blocks of a 1-D array, whose
    XLA layout tiles by 1024 where Mosaic tiles by 128."""
    return jnp.full((Ep, 1), fill, dtype).at[:v.shape[0], 0].set(
        v.astype(dtype))


def _dot(a, b):
    """f32 matmul at full precision.  The one-hot gathers and scatters
    must be exact; at the TPU's default precision the data operand would
    round to bfloat16."""
    return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# forward scatter-add kernel
# ---------------------------------------------------------------------------

def _scatter_kernel(ids_ref, msgs_ref, out_ref, acc_ref, *, bn: int):
    n_i = pl.program_id(0)
    e_i = pl.program_id(2)
    ne = pl.num_programs(2)

    local = ids_ref[:] - n_i * bn                      # (BE, 1)
    onehot = (local == jax.lax.broadcasted_iota(
        jnp.int32, (1, bn), 1)).astype(jnp.float32)    # (BE, BN)
    msgs = msgs_ref[:].astype(jnp.float32)             # (BE, BF)
    contrib = _dot(onehot.T, msgs)                     # (BN, BF)

    @pl.when(e_i == 0)
    def _init():
        acc_ref[:] = contrib

    @pl.when(e_i != 0)
    def _acc():
        acc_ref[:] = acc_ref[:] + contrib

    @pl.when(e_i == ne - 1)
    def _emit():
        out_ref[:] = acc_ref[:].astype(out_ref.dtype)


def _scatter_add(msgs, seg_ids, num_segments, be, bn, bf, interpret):
    """Raw forward: blocked one-hot-matmul scatter-add (no VJP)."""
    E, F = msgs.shape
    Ep = _pad_edges(E, be)
    Fp = -(-F // bf) * bf
    # one sacrificial segment row absorbs padded edges
    pad_seg = num_segments
    Np = -(-(num_segments + 1) // bn) * bn

    msgs_p = jnp.zeros((Ep, Fp), msgs.dtype).at[:E, :F].set(msgs)
    ids_p = _edge_column(seg_ids, Ep, pad_seg, jnp.int32)

    grid = (Np // bn, Fp // bf, Ep // be)
    out = pl.pallas_call(
        functools.partial(_scatter_kernel, bn=bn),
        grid=grid,
        in_specs=[
            pl.BlockSpec((be, 1), lambda n, f, e: (e, 0)),
            pl.BlockSpec((be, bf), lambda n, f, e: (e, f)),
        ],
        out_specs=pl.BlockSpec((bn, bf), lambda n, f, e: (n, f)),
        out_shape=jax.ShapeDtypeStruct((Np, Fp), msgs.dtype),
        scratch_shapes=[pltpu.VMEM((bn, bf), jnp.float32)],
        interpret=interpret,
    )(ids_p, msgs_p)
    return out[:num_segments, :F]


# ---------------------------------------------------------------------------
# backward gather kernel (the transpose of scatter-add)
# ---------------------------------------------------------------------------

def _gather_kernel(ids_ref, gout_ref, out_ref, acc_ref, *, bn: int):
    n_i = pl.program_id(2)
    nn = pl.num_programs(2)

    local = ids_ref[:] - n_i * bn                      # (BE, 1)
    onehot = (local == jax.lax.broadcasted_iota(
        jnp.int32, (1, bn), 1)).astype(jnp.float32)    # (BE, BN)
    gout = gout_ref[:].astype(jnp.float32)             # (BN, BF)
    contrib = _dot(onehot, gout)                       # (BE, BF)

    @pl.when(n_i == 0)
    def _init():
        acc_ref[:] = contrib

    @pl.when(n_i != 0)
    def _acc():
        acc_ref[:] = acc_ref[:] + contrib

    @pl.when(n_i == nn - 1)
    def _emit():
        out_ref[:] = acc_ref[:].astype(out_ref.dtype)


def gather_rows_pallas(grad_out, seg_ids, E, *, be=DEFAULT_BE,
                       bn=DEFAULT_BN, bf=None, interpret=True):
    """Blocked row gather ``grad_out[seg_ids]`` — the scatter-add VJP.

    ``grad_out``: (N, F); ``seg_ids``: (E,) int32 with values in
    [0, N] (row N — the sacrificial pad segment — gathers zeros).
    Returns (E, F).  Each edge id lives in exactly one node tile, so
    accumulating one-hot-gathered contributions over the (innermost)
    node-tile axis reconstructs the gathered row exactly.
    """
    N, F = grad_out.shape
    bf = _pick_bf(F) if bf is None else bf
    # 2x (bn, bf) double-buffered input blocks + (be, bf) out + acc
    # + (be, bn) one-hot + ids
    _assert_vmem(2 * be * bf + be * bn + 2 * bn * bf + be,
                 what="gather_rows_pallas")
    Ep = _pad_edges(E, be)
    Fp = -(-F // bf) * bf
    Np = -(-(N + 1) // bn) * bn        # +1: pad ids may point at row N

    gout_p = jnp.zeros((Np, Fp), grad_out.dtype).at[:N, :F].set(grad_out)
    ids_p = _edge_column(seg_ids, Ep, N, jnp.int32)

    grid = (Ep // be, Fp // bf, Np // bn)
    out = pl.pallas_call(
        functools.partial(_gather_kernel, bn=bn),
        grid=grid,
        in_specs=[
            pl.BlockSpec((be, 1), lambda e, f, n: (e, 0)),
            pl.BlockSpec((bn, bf), lambda e, f, n: (n, f)),
        ],
        out_specs=pl.BlockSpec((be, bf), lambda e, f, n: (e, f)),
        out_shape=jax.ShapeDtypeStruct((Ep, Fp), grad_out.dtype),
        scratch_shapes=[pltpu.VMEM((be, bf), jnp.float32)],
        interpret=interpret,
    )(ids_p, gout_p)
    return out[:E, :F]


# ---------------------------------------------------------------------------
# differentiable segment_sum
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6))
def _segment_sum(msgs, seg_ids, num_segments, be, bn, bf, interpret):
    return _scatter_add(msgs, seg_ids, num_segments, be, bn, bf, interpret)


def _segment_sum_fwd(msgs, seg_ids, num_segments, be, bn, bf, interpret):
    out = _scatter_add(msgs, seg_ids, num_segments, be, bn, bf, interpret)
    return out, seg_ids                   # linear in msgs: ids suffice


def _segment_sum_bwd(num_segments, be, bn, bf, interpret, seg_ids, g):
    E = seg_ids.shape[0]
    grad_msgs = gather_rows_pallas(g, seg_ids, E, be=be, bn=bn, bf=bf,
                                   interpret=interpret)
    return grad_msgs, np.zeros(seg_ids.shape, jax.dtypes.float0)


_segment_sum.defvjp(_segment_sum_fwd, _segment_sum_bwd)


def segment_sum_pallas(msgs: jax.Array, seg_ids: jax.Array,
                       num_segments: int, *,
                       be: int = DEFAULT_BE, bn: int = DEFAULT_BN,
                       bf: int | None = None,
                       interpret: bool = True) -> jax.Array:
    """Differentiable blocked segment-sum.

    ``msgs``: (E, F); ``seg_ids``: (E,) int32.  E, F, num_segments are
    padded to tile multiples internally (padded edges point at one
    sacrificial segment row that is dropped on return; E=0 degenerates
    to a single all-pad tile and returns zeros).  ``bf=None`` picks the
    feature tile adaptively from F (:func:`_pick_bf`).  The VJP gathers
    ``grad_out[seg_ids]`` with :func:`gather_rows_pallas`.
    """
    E, F = msgs.shape
    bf = _pick_bf(F) if bf is None else bf
    # covers forward (scatter) AND its VJP (gather): both hold the same
    # working set — one-hot + 2x double-buffered (·, bf) inputs + out/acc
    _assert_vmem(2 * be * bf + be * bn + 2 * bn * bf + be,
                 what="segment_sum_pallas")
    return _segment_sum(msgs, seg_ids, num_segments, be, bn, bf, interpret)


# ---------------------------------------------------------------------------
# fused gather -> scale -> segment-sum
# ---------------------------------------------------------------------------

def _fused_kernel(src_ref, dst_ref, coef_ref, h_ref, out_ref, acc_ref, *,
                  bn: int, sp: int):
    n_i = pl.program_id(1)
    e_i = pl.program_id(2)
    ne = pl.num_programs(2)

    onehot_s = (src_ref[:] == jax.lax.broadcasted_iota(
        jnp.int32, (1, sp), 1)).astype(jnp.float32)    # (BE, Sp)
    h = h_ref[:].astype(jnp.float32)                   # (Sp, BF) resident
    msgs = _dot(onehot_s, h)                           # (BE, BF) VMEM-only
    msgs = msgs * coef_ref[:].astype(jnp.float32)      # (BE, 1) broadcast

    local = dst_ref[:] - n_i * bn                      # (BE, 1)
    onehot_d = (local == jax.lax.broadcasted_iota(
        jnp.int32, (1, bn), 1)).astype(jnp.float32)    # (BE, BN)
    contrib = _dot(onehot_d.T, msgs)                   # (BN, BF)

    @pl.when(e_i == 0)
    def _init():
        acc_ref[:] = contrib

    @pl.when(e_i != 0)
    def _acc():
        acc_ref[:] = acc_ref[:] + contrib

    @pl.when(e_i == ne - 1)
    def _emit():
        out_ref[:] = acc_ref[:].astype(out_ref.dtype)


def _fused_impl(h, edge_src, edge_dst, coef, num_dst, be, bn, bf,
                interpret):
    """Raw fused forward (no VJP): out[d] = sum_{e: dst_e=d} coef_e *
    h[src_e].  The (E, F) message tensor lives only tile-by-tile in
    VMEM, never in HBM."""
    S, F = h.shape
    E = edge_src.shape[0]
    Ep = _pad_edges(E, be)
    Fp = -(-F // bf) * bf
    Sp = -(-S // SUBLANE) * SUBLANE
    pad_seg = num_dst
    Np = -(-(num_dst + 1) // bn) * bn

    h_p = jnp.zeros((Sp, Fp), h.dtype).at[:S, :F].set(h)
    src_p = _edge_column(edge_src, Ep, 0, jnp.int32)
    dst_p = _edge_column(edge_dst, Ep, pad_seg, jnp.int32)
    coef_p = _edge_column(coef, Ep, 0, coef.dtype)

    # feature dimension OUTERMOST: the (Sp, bf) source slab's block index
    # is constant over the whole inner (n, e) sweep, so it is fetched
    # from HBM once per feature tile (Pallas skips the DMA when the
    # block index does not change between steps)
    grid = (Fp // bf, Np // bn, Ep // be)
    out = pl.pallas_call(
        functools.partial(_fused_kernel, bn=bn, sp=Sp),
        grid=grid,
        in_specs=[
            pl.BlockSpec((be, 1), lambda f, n, e: (e, 0)),
            pl.BlockSpec((be, 1), lambda f, n, e: (e, 0)),
            pl.BlockSpec((be, 1), lambda f, n, e: (e, 0)),
            pl.BlockSpec((Sp, bf), lambda f, n, e: (0, f)),
        ],
        out_specs=pl.BlockSpec((bn, bf), lambda f, n, e: (n, f)),
        out_shape=jax.ShapeDtypeStruct((Np, Fp), h.dtype),
        scratch_shapes=[pltpu.VMEM((bn, bf), jnp.float32)],
        interpret=interpret,
    )(src_p, dst_p, coef_p, h_p)
    return out[:num_dst, :F]


def _edge_dot_kernel(src_ref, dst_ref, h_ref, gout_ref, out_ref, acc_ref,
                     *, sp: int, npd: int):
    f_i = pl.program_id(1)
    nf = pl.num_programs(1)

    onehot_s = (src_ref[:] == jax.lax.broadcasted_iota(
        jnp.int32, (1, sp), 1)).astype(jnp.float32)      # (BE, Sp)
    onehot_d = (dst_ref[:] == jax.lax.broadcasted_iota(
        jnp.int32, (1, npd), 1)).astype(jnp.float32)     # (BE, Npd)
    hs = _dot(onehot_s, h_ref[:].astype(jnp.float32))    # (BE, BF)
    gd = _dot(onehot_d, gout_ref[:].astype(jnp.float32))  # (BE, BF)
    part = jnp.sum(hs * gd, axis=1, keepdims=True)       # (BE, 1)

    @pl.when(f_i == 0)
    def _init():
        acc_ref[:] = part

    @pl.when(f_i != 0)
    def _acc():
        acc_ref[:] = acc_ref[:] + part

    @pl.when(f_i == nf - 1)
    def _emit():
        out_ref[:] = acc_ref[:].astype(out_ref.dtype)


def _edge_dot(h, gout, edge_src, edge_dst, be, bf, interpret):
    """Per-edge feature dot <h[src_e], gout[dst_e]> — the coefficient
    cotangent of the fused kernel."""
    S, F = h.shape
    Nd = gout.shape[0]
    E = edge_src.shape[0]
    Ep = _pad_edges(E, be)
    Fp = -(-F // bf) * bf
    Sp = -(-S // SUBLANE) * SUBLANE
    Npd = -(-Nd // SUBLANE) * SUBLANE

    h_p = jnp.zeros((Sp, Fp), h.dtype).at[:S, :F].set(h)
    g_p = jnp.zeros((Npd, Fp), gout.dtype).at[:Nd, :F].set(gout)
    # pad-edge rows of the output are trimmed below, so pad ids only
    # need to be in range
    src_p = _edge_column(edge_src, Ep, 0, jnp.int32)
    dst_p = _edge_column(edge_dst, Ep, 0, jnp.int32)

    grid = (Ep // be, Fp // bf)
    out = pl.pallas_call(
        functools.partial(_edge_dot_kernel, sp=Sp, npd=Npd),
        grid=grid,
        in_specs=[
            pl.BlockSpec((be, 1), lambda e, f: (e, 0)),
            pl.BlockSpec((be, 1), lambda e, f: (e, 0)),
            pl.BlockSpec((Sp, bf), lambda e, f: (0, f)),
            pl.BlockSpec((Npd, bf), lambda e, f: (0, f)),
        ],
        out_specs=pl.BlockSpec((be, 1), lambda e, f: (e, 0)),
        out_shape=jax.ShapeDtypeStruct((Ep, 1), h.dtype),
        scratch_shapes=[pltpu.VMEM((be, 1), jnp.float32)],
        interpret=interpret,
    )(src_p, dst_p, h_p, g_p)
    return out[:E, 0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _fused(h, edge_src, edge_dst, coef, num_dst, be, bn, bf, interpret):
    return _fused_impl(h, edge_src, edge_dst, coef, num_dst, be, bn, bf,
                       interpret)


def _fused_fwd(h, edge_src, edge_dst, coef, num_dst, be, bn, bf,
               interpret):
    out = _fused_impl(h, edge_src, edge_dst, coef, num_dst, be, bn, bf,
                      interpret)
    return out, (h, edge_src, edge_dst, coef)


def _fused_bwd(num_dst, be, bn, bf, interpret, res, g):
    h, edge_src, edge_dst, coef = res
    num_src = h.shape[0]
    # transpose of "gather src, scale, scatter to dst" is the same fused
    # op with src and dst swapped: dh[s] = sum_{e: src_e=s} coef_e * g[dst_e]
    dh = _fused_impl(g, edge_dst, edge_src, coef, num_src, be, bn, bf,
                     interpret)
    dcoef = _edge_dot(h, g, edge_src, edge_dst, be, bf, interpret)
    zero_ids = np.zeros(edge_src.shape, jax.dtypes.float0)
    return dh, zero_ids, zero_ids, dcoef.astype(coef.dtype)


_fused.defvjp(_fused_fwd, _fused_bwd)


def gather_scale_segment_sum_pallas(h: jax.Array, edge_src: jax.Array,
                                    edge_dst: jax.Array, coef: jax.Array,
                                    num_dst: int, *,
                                    be: int = DEFAULT_BE,
                                    bn: int = DEFAULT_BN,
                                    bf: int | None = None,
                                    interpret: bool = True) -> jax.Array:
    """Fused differentiable Scatter–ApplyEdge–Gather:
    ``out[d] = sum_{e: edge_dst[e]=d} coef[e] * h[edge_src[e]]``.

    ``h``: (num_src, F) source features; ``edge_src``/``edge_dst``: (E,)
    int32; ``coef``: (E,) per-edge coefficient (fold the edge validity
    mask into it — padded/masked edges must carry coef 0).  Returns
    (num_dst, F).

    One kernel reads source rows (one-hot matmul against a VMEM-resident
    (S_pad, BF) feature slab), scales by ``coef``, and accumulates into
    destination tiles — the (E, F) message tensor never reaches HBM.
    The VJP reuses the same kernel with src/dst swapped for ``dh`` and a
    per-edge dot kernel for ``dcoef``; edge indices get zero (float0)
    cotangents.
    """
    S, F = h.shape
    bf = _pick_bf(F) if bf is None else bf
    _assert_vmem(fused_vmem_floats(S, num_dst, F, be=be, bn=bn, bf=bf),
                 what="gather_scale_segment_sum_pallas (fwd+vjp)")
    return _fused(h, edge_src, edge_dst, coef, num_dst, be, bn, bf,
                  interpret)


def edge_tile_density(edge_src, edge_dst, num_dst: int, *,
                      be: int = DEFAULT_BE, bn: int = DEFAULT_BN) -> dict:
    """Pure-numpy VMEM-residency / tile-density metrics of the blocked
    kernels for a given edge ordering (what ``--reorder`` improves).

    Returns a dict:

    * ``active_tile_frac`` — fraction of (dst-tile, edge-tile) grid
      cells holding at least one real edge.  The blocked scatter sweeps
      the full ``n_tiles x e_tiles`` grid regardless, so a low fraction
      is both wasted work today and the headroom a tile-skipping kernel
      would reclaim; locality reordering concentrates edges into few
      cells.
    * ``src_rows_per_edge_tile`` — mean distinct source rows gathered
      per edge tile, normalized by the tile's edge count (1.0 = every
      edge hits a different row, lower = gathers reuse VMEM-resident
      rows within the tile).
    """
    src = np.asarray(edge_src, np.int64)
    dst = np.asarray(edge_dst, np.int64)
    E = len(src)
    if E == 0:
        return {"active_tile_frac": 0.0, "src_rows_per_edge_tile": 0.0}
    e_tiles = -(-E // be)
    n_tiles = -(-(num_dst + 1) // bn)
    e_idx = np.arange(E) // be
    cells = np.unique(e_idx * n_tiles + dst // bn)
    rows = []
    for t in range(e_tiles):
        chunk = src[t * be:(t + 1) * be]
        rows.append(len(np.unique(chunk)) / len(chunk))
    return {
        "active_tile_frac": len(cells) / (n_tiles * e_tiles),
        "src_rows_per_edge_tile": float(np.mean(rows)),
    }


def fused_vmem_floats(num_src: int, num_dst: int, F: int, *,
                      be: int = DEFAULT_BE, bn: int = DEFAULT_BN,
                      bf: int | None = None) -> int:
    """Per-step VMEM working set (floats) of the fused kernel AND its
    VJP — the largest of: the forward (source slab resident), the
    swapped backward (grad slab of ``num_dst`` rows resident), and the
    edge-dot kernel (both slabs + both one-hots resident).  Dispatch
    layers use :func:`fused_fits` to fall back to the unfused blocked
    kernel (whose working set is row-count independent) when the slab
    would not fit."""
    bf = _pick_bf(F) if bf is None else bf
    Sp = -(-num_src // SUBLANE) * SUBLANE
    Gp = -(-num_dst // SUBLANE) * SUBLANE

    def fused_set(sp):
        # resident slab + src one-hot + msgs + dst one-hot + out/acc + ids
        return sp * bf + be * sp + be * bf + be * bn + 2 * bn * bf + 3 * be

    edge_dot_set = ((Sp + Gp) * bf + be * (Sp + Gp) + 2 * be * bf
                    + 4 * be)
    return max(fused_set(Sp), fused_set(Gp), edge_dot_set)


def fused_fits(num_src: int, num_dst: int, F: int, *,
               be: int = DEFAULT_BE, bn: int = DEFAULT_BN,
               bf: int | None = None) -> bool:
    """True iff the fused kernel (fwd + VJP) fits the VMEM budget for
    these row counts — the capacity predicate behind the automatic
    fused/unfused dispatch in :mod:`repro.kernels.ops`."""
    return 4 * fused_vmem_floats(num_src, num_dst, F, be=be, bn=bn,
                                 bf=bf) <= VMEM_BUDGET
