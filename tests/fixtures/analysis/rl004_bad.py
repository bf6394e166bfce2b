"""RL004 true positives: misaligned Pallas tile shapes and a VMEM blowout.

Covers: last dim not lane-aligned, a 3-D BlockSpec with last dim 1
(lane-tile padding — the per-row column exemption is 2-D only),
second-to-last not sublane-aligned, and a scratch buffer over the
module's VMEM_BUDGET.  Shapes resolve through literals, module
constants, and parameter defaults.
"""
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

VMEM_BUDGET = 8 * 2**20
BN = 100                                         # not lane-aligned


def build_specs(bq=24):
    bad_lane = pl.BlockSpec((8, BN), lambda i: (i, 0))       # BAD: 100 % 128
    bad_sub = pl.BlockSpec((12, 128), lambda i: (i, 0))      # BAD: 12 % 8
    bad_col = pl.BlockSpec((2, 8, 1), lambda i: (i, 0, 0))   # BAD: last dim 1
    return bad_lane, bad_sub, bad_col, bq


def scratch():
    huge = pltpu.VMEM((4096, 1024), jnp.float32)             # BAD: 16 MiB
    return huge
