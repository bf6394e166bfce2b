"""RL001 true positive: psum reachable inside a differentiated function.

This is the PR 2 bug verbatim in miniature — under shard_map
check_vma=False, the transpose of the psum is a second psum, so the
gradients come back scaled by the axis size.
"""
import jax
import jax.numpy as jnp

AXIS = "dev"


def local_loss(params, x, y):
    pred = x @ params["w"]
    err = jnp.sum((pred - y) ** 2)
    return jax.lax.psum(err, AXIS)          # BAD: collective inside grad


def train_step(params, x, y):
    grads = jax.grad(local_loss)(params, x, y)
    return grads
