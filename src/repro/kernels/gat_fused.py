"""Pallas TPU kernel: one-pass fused GAT attention aggregation.

The ``jax.ops`` GAT attention (``segment_softmax`` + weighted
``segment_sum``) makes three HBM round-trips per layer: edge logits are
materialized, re-read for the per-destination max/denominator, and the
(E, heads) alpha tensor plus the (E, heads·hd) message tensor cross HBM
again for the weighted reduction.  This kernel is the flash-attention treatment of
that pipeline (``kernels/flash_attention.py`` is the in-repo exemplar):

    grid = (D/BN, E/BE), edge tiles innermost.  Per destination tile,
    a running max ``m``, denominator ``l`` and weighted accumulator
    ``acc`` live in VMEM scratch across edge tiles; each edge tile
    gathers its source logit halves and per-head source features by
    one-hot matmuls against VMEM-resident slabs, forms the leaky-relu
    logits, and folds them into the online softmax —

        m' = max(m, tile_max)        l' = e^{m-m'} l + Σ e^{z-m'}
        acc' = e^{m-m'} acc + Σ e^{z-m'} · hs[src]

    — so edge logits and alphas NEVER reach HBM.  The final emit divides
    ``acc / (l + 1e-9)``, matching the reference denominator exactly.

Masked / padded edges carry ``mask = 0`` and contribute nothing (their
``p`` is forced to 0 before it can touch ``l`` or ``acc``); destinations
with no valid incoming edge emit exact zeros, like the reference.

**VJP.**  ``jax.custom_vjp`` with the flash-attention recompute strategy:
the backward recomputes the (E, heads) alphas once (heads is small — 4
floats per edge, not heads·hd), then routes every feature-dimension-heavy
cotangent through the existing fused Pallas kernels —

* ``dhs``  = per-head fused gather-scale-segment-sum with src/dst swapped,
* ``dalpha`` = per-head edge-dot kernel ``<hs[src], g[dst]>``,

followed by the closed-form softmax backward and two light (E, heads)
segment sums for ``des`` / ``ded``.  The (E, heads·hd) message tensor
exists in neither pass.  Gradients match the ``segment_softmax``
reference to ≤1e-5/param (asserted by ``tests/gat_train_check.py`` over
{1, 2} devices).

:func:`gat_fused_fits` is the VMEM capacity predicate:
``models/gnn/layers.py`` (``GATLayer``) takes this kernel where it holds
and its ``jax.ops`` attention where the source slabs would not fit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.segment_sum import (DEFAULT_BE, DEFAULT_BN, LANE,
                                       SUBLANE, VMEM_BUDGET, _assert_vmem,
                                       _dot, _edge_column, _edge_dot,
                                       _fused_impl, _pad_edges, _pick_bf,
                                       fused_vmem_floats)

NEG_INF = -1e30
LEAKY_SLOPE = 0.2


def _lanes(n: int) -> int:
    """``n`` rounded up to whole 128-lane rows, as VMEM holds a block's
    last dimension."""
    return -(-n // LANE) * LANE


def _pad8(n: int) -> int:
    return max(SUBLANE, -(-n // SUBLANE) * SUBLANE)


def _gat_kernel(src_ref, dst_ref, mask_ref, hs_ref, es_ref, ed_ref, o_ref,
                m_scr, l_scr, acc_scr, *, bn: int, sp: int, heads: int,
                hdp: int):
    n_i = pl.program_id(0)
    e_i = pl.program_id(1)
    ne = pl.num_programs(1)

    @pl.when(e_i == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    onehot_s = (src_ref[:] == jax.lax.broadcasted_iota(
        jnp.int32, (1, sp), 1)).astype(jnp.float32)     # (BE, Sp)
    es_e = _dot(onehot_s, es_ref[:].astype(jnp.float32))  # (BE, Hp)

    local = dst_ref[:] - n_i * bn                       # (BE, 1)
    onehot_d = (local == jax.lax.broadcasted_iota(
        jnp.int32, (1, bn), 1)).astype(jnp.float32)     # (BE, BN)
    ed_e = _dot(onehot_d, ed_ref[:].astype(jnp.float32))  # (BE, Hp)

    pre = es_e + ed_e
    logits = jnp.where(pre >= 0, pre, LEAKY_SLOPE * pre)   # (BE, Hp)

    # edges outside this destination tile have an all-zero one-hot row;
    # fold that into the validity so they cannot touch max/denominator
    intile = jnp.sum(onehot_d, axis=1, keepdims=True)      # (BE, 1)
    veff = mask_ref[:] * intile                            # (BE, 1)

    hs = hs_ref[:].astype(jnp.float32)                     # (Sp, H*hdp)
    for h in range(heads):                                 # static unroll
        sl = slice(h * hdp, (h + 1) * hdp)
        lh = logits[:, h:h + 1]                            # (BE, 1)
        cond = (onehot_d > 0.5) & (veff > 0.5)             # (BE, BN)
        tile_max = jnp.max(jnp.where(cond, lh, NEG_INF),
                           axis=0, keepdims=True)          # (1, BN)
        m_prev = m_scr[:, h:h + 1]                         # (BN, 1)
        m_new = jnp.maximum(m_prev, tile_max.T)
        m_e = _dot(onehot_d, m_new)                        # (BE, 1)
        # guard: an invalid edge may see m_e = 0 or -inf; never exp it
        p = jnp.where(veff > 0.5, jnp.exp(lh - m_e), 0.0)  # (BE, 1)
        corr = jnp.exp(m_prev - m_new)                     # (BN, 1)
        l_scr[:, h:h + 1] = (corr * l_scr[:, h:h + 1]
                             + _dot(onehot_d.T, p))
        msgs = _dot(onehot_s, hs[:, sl])                   # (BE, hdp)
        contrib = _dot(onehot_d.T, p * msgs)               # (BN, hdp)
        acc_scr[:, sl] = corr * acc_scr[:, sl] + contrib
        m_scr[:, h:h + 1] = m_new

    @pl.when(e_i == ne - 1)
    def _finish():
        for h in range(heads):
            sl = slice(h * hdp, (h + 1) * hdp)
            den = l_scr[:, h:h + 1] + 1e-9        # reference denominator
            o_ref[:, sl] = (acc_scr[:, sl] / den).astype(o_ref.dtype)


def _gat_impl(hs, es, ed, edge_src, edge_dst, maskf, num_dst, heads, be,
              bn, interpret):
    """Raw one-pass forward (no VJP).  ``hs``: (S, heads*hd) projected
    source features; ``es``: (S, heads) / ``ed``: (num_dst, heads) logit
    halves; ``maskf``: (E,) float validity.  Returns (num_dst, heads*hd)."""
    S = hs.shape[0]
    hd = hs.shape[1] // heads
    E = edge_src.shape[0]
    hdp = _pick_bf(hd)
    hp = _pad8(heads)
    Sp = _pad8(S)
    Ep = _pad_edges(E, be)
    pad_seg = num_dst
    Np = -(-(num_dst + 1) // bn) * bn

    hs_p = jnp.zeros((Sp, heads * hdp), hs.dtype)
    for h in range(heads):
        hs_p = hs_p.at[:S, h * hdp:h * hdp + hd].set(
            hs[:, h * hd:(h + 1) * hd])
    es_p = jnp.zeros((Sp, hp), es.dtype).at[:S, :heads].set(es)
    ed_p = jnp.zeros((Np, hp), ed.dtype).at[:num_dst, :heads].set(ed)
    src_p = _edge_column(edge_src, Ep, 0, jnp.int32)
    dst_p = _edge_column(edge_dst, Ep, pad_seg, jnp.int32)
    mask_p = _edge_column(maskf, Ep, 0, jnp.float32)

    # hs/es slabs have a constant block index over the whole grid sweep,
    # so they cross HBM once; the ed block follows the destination tile
    grid = (Np // bn, Ep // be)
    out = pl.pallas_call(
        functools.partial(_gat_kernel, bn=bn, sp=Sp, heads=heads, hdp=hdp),
        grid=grid,
        in_specs=[
            pl.BlockSpec((be, 1), lambda n, e: (e, 0)),
            pl.BlockSpec((be, 1), lambda n, e: (e, 0)),
            pl.BlockSpec((be, 1), lambda n, e: (e, 0)),
            pl.BlockSpec((Sp, heads * hdp), lambda n, e: (0, 0)),
            pl.BlockSpec((Sp, hp), lambda n, e: (0, 0)),
            pl.BlockSpec((bn, hp), lambda n, e: (n, 0)),
        ],
        out_specs=pl.BlockSpec((bn, heads * hdp), lambda n, e: (n, 0)),
        out_shape=jax.ShapeDtypeStruct((Np, heads * hdp), hs.dtype),
        scratch_shapes=[
            pltpu.VMEM((bn, hp), jnp.float32),           # running max
            pltpu.VMEM((bn, hp), jnp.float32),           # running denom
            pltpu.VMEM((bn, heads * hdp), jnp.float32),  # weighted acc
        ],
        interpret=interpret,
    )(src_p, dst_p, mask_p, hs_p, es_p, ed_p)
    if hdp == hd:
        return out[:num_dst]
    out = out[:num_dst].reshape(num_dst, heads, hdp)[:, :, :hd]
    return out.reshape(num_dst, heads * hd)


def _reference_alphas(es, ed, edge_src, edge_dst, maskf, num_dst):
    """(E, heads) attention weights in XLA ops, as the ``jax.ops``
    attention forms them (the flash-style backward recomputes these
    instead of saving them; scope ``edge_softmax``)."""
    with jax.named_scope("edge_softmax"):
        pre = (jnp.take(es, edge_src, axis=0)
               + jnp.take(ed, edge_dst, axis=0))           # (E, H)
        z = jnp.where(pre >= 0, pre, LEAKY_SLOPE * pre)
        zm = jnp.where(maskf[:, None] > 0, z, NEG_INF)
        mx = jax.ops.segment_max(zm, edge_dst, num_dst)
        mx = jnp.where(jnp.isfinite(mx), mx, 0.0)          # empty segments
        ex = jnp.exp(zm - mx[edge_dst]) * maskf[:, None]
        den = jax.ops.segment_sum(ex, edge_dst, num_dst)
        return ex / (den[edge_dst] + 1e-9), pre


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10))
def _gat(hs, es, ed, edge_src, edge_dst, maskf, num_dst, heads, be, bn,
         interpret):
    return _gat_impl(hs, es, ed, edge_src, edge_dst, maskf, num_dst,
                     heads, be, bn, interpret)


def _gat_fwd(hs, es, ed, edge_src, edge_dst, maskf, num_dst, heads, be,
             bn, interpret):
    out = _gat_impl(hs, es, ed, edge_src, edge_dst, maskf, num_dst,
                    heads, be, bn, interpret)
    return out, (hs, es, ed, edge_src, edge_dst, maskf)


def _gat_bwd(num_dst, heads, be, bn, interpret, res, g):
    hs, es, ed, edge_src, edge_dst, maskf = res
    S = hs.shape[0]
    hd = hs.shape[1] // heads
    bf = _pick_bf(hd)
    alpha, pre = _reference_alphas(es, ed, edge_src, edge_dst, maskf,
                                   num_dst)                # (E, H) recompute
    dhs_cols = []
    dalpha_cols = []
    for h in range(heads):                                 # static unroll
        g_h = g[:, h * hd:(h + 1) * hd]
        hs_h = hs[:, h * hd:(h + 1) * hd]
        a_h = alpha[:, h]
        # transpose of "gather src, weight by alpha, scatter to dst":
        # the fused kernel with src and dst swapped
        dhs_cols.append(_fused_impl(g_h, edge_dst, edge_src, a_h, S, be,
                                    bn, bf, interpret))
        dalpha_cols.append(_edge_dot(hs_h, g_h, edge_src, edge_dst, be,
                                     bf, interpret))
    dhs = jnp.concatenate(dhs_cols, axis=1)                # (S, H*hd)
    dalpha = jnp.stack(dalpha_cols, axis=1)                # (E, H)
    # closed-form softmax backward: dz = alpha * (dalpha - sum_dst)
    s = jax.ops.segment_sum(alpha * dalpha, edge_dst, num_dst)
    dz = alpha * (dalpha - s[edge_dst])                    # (E, H)
    dpre = dz * jnp.where(pre >= 0, 1.0, LEAKY_SLOPE)
    des = jax.ops.segment_sum(dpre, edge_src, S)
    ded = jax.ops.segment_sum(dpre, edge_dst, num_dst)
    zero_ids = np.zeros(edge_src.shape, jax.dtypes.float0)
    return (dhs, des.astype(es.dtype), ded.astype(ed.dtype), zero_ids,
            zero_ids, jnp.zeros_like(maskf))


_gat.defvjp(_gat_fwd, _gat_bwd)


def gat_fused_attention_pallas(hs: jax.Array, es: jax.Array, ed: jax.Array,
                               edge_src: jax.Array, edge_dst: jax.Array,
                               mask: jax.Array, num_dst: int, *,
                               heads: int, be: int = DEFAULT_BE,
                               bn: int = DEFAULT_BN,
                               interpret: bool = True) -> jax.Array:
    """Differentiable one-pass fused GAT aggregation.

    ``out[d, h] = Σ_e softmax_d(leaky_relu(es[src_e] + ed[d]))_e ·
    hs[src_e, h]`` for edges with ``edge_dst[e] = d`` and ``mask[e]``
    set.  ``hs``: (num_src, heads·hd); ``es``: (num_src, heads);
    ``ed``: (num_dst, heads); ``mask``: (E,) bool/float validity.
    Returns (num_dst, heads·hd); destinations with no valid incoming
    edge emit zeros, matching the ``segment_softmax`` reference.
    """
    maskf = mask.astype(jnp.float32)
    hd = hs.shape[1] // heads
    _assert_vmem(
        gat_fused_vmem_floats(hs.shape[0], num_dst, heads, hd, be=be,
                              bn=bn),
        what="gat_fused_attention_pallas (fwd+vjp)")
    return _gat(hs, es, ed, edge_src, edge_dst, maskf, num_dst, heads,
                be, bn, interpret)


def gat_fused_vmem_floats(num_src: int, num_dst: int, heads: int, hd: int,
                          *, be: int = DEFAULT_BE,
                          bn: int = DEFAULT_BN) -> int:
    """Per-step VMEM working set (floats) of the one-pass forward AND
    its backward's per-head fused/edge-dot kernels (whichever is
    largest).  Dispatch layers use :func:`gat_fused_fits`."""
    hdp = _pick_bf(hd)
    hp = _pad8(heads)
    sp = _pad8(num_src)
    # VMEM lays every block out in whole 128-lane rows: an (Sp, 8) logit
    # slab takes as much room as an (Sp, 128) one
    hw, hl = _lanes(heads * hdp), _lanes(hp)
    fwd = (sp * hw + sp * hl                   # hs + es slabs resident
           + bn * hl                           # ed tile
           + be * _lanes(sp) + be * _lanes(bn)  # both one-hots
           + 3 * be * hl                       # es_e/ed_e/logits
           + be * _lanes(hdp) + bn * _lanes(hdp) + be * _lanes(bn)
           + bn * (2 * hl + 2 * hw)            # m/l/acc/out
           + 3 * be * LANE)                    # ids + mask columns
    bwd = fused_vmem_floats(max(num_src, num_dst),
                            max(num_src, num_dst), hd, be=be, bn=bn)
    return max(fwd, bwd)


def gat_fused_fits(num_src: int, num_dst: int, heads: int, hd: int, *,
                   be: int = DEFAULT_BE, bn: int = DEFAULT_BN) -> bool:
    """True iff the one-pass GAT kernel (fwd + VJP) fits the VMEM budget
    for these row counts — the capacity predicate behind ``GATLayer``'s
    choice between this kernel and its ``jax.ops`` attention."""
    return 4 * gat_fused_vmem_floats(num_src, num_dst, heads, hd, be=be,
                                     bn=bn) <= VMEM_BUDGET
