"""RL004 per-row column idiom — the shapes that do NOT qualify.

The codified exception is narrow: a 2-D ``(rows, 1)`` block or scratch
with sublane-aligned rows.  Everything adjacent to it stays flagged:
misaligned rows, and a 3-D scratch or block with a trailing 1.
"""
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

VMEM_BUDGET = 8 * 2**20


def scratch():
    ragged = pltpu.VMEM((12, 1), jnp.float32)    # BAD: rows not 8-aligned
    deep = pltpu.VMEM((1, 8, 1), jnp.float32)    # BAD: 3-D, not the idiom
    return ragged, deep


def spec():
    # BAD: a 3-D block with a trailing 1 is not a per-row column
    return pl.BlockSpec((2, 8, 1), lambda i: (i, 0, 0))
