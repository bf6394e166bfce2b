"""RL004 per-row column idiom — the codified clean shapes.

A 2-D ``(rows, 1)`` column with sublane-aligned rows holds one scalar
per row: the online-softmax running max/denominator scratch
(``kernels/flash_attention.py``, ``kernels/gat_fused.py``) and the
per-edge ids/coefficients blocks of the aggregation kernels
(``kernels/segment_sum.py``).  The rule accepts both without a
suppression comment.
"""
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

VMEM_BUDGET = 8 * 2**20


def scratch(bq=128):
    running_max = pltpu.VMEM((64, 1), jnp.float32)     # 8-aligned rows
    running_den = pltpu.VMEM((bq, 1), jnp.float32)     # via param default
    return running_max, running_den


def edge_ids(be=128):
    return pl.BlockSpec((be, 1), lambda e: (e, 0))     # per-edge column
