"""Plain float32 building blocks shared by the per-architecture references.

Nothing here imports the program.  The references spell out the same
mathematics as the program's model, loss and optimizer in straightforward
``jax.numpy``: gathers and segment sums in float32, every matrix product at
the precision the caller names.

``precision="highest"`` is float32 (on a TPU, XLA's six-pass product).
``precision="high"`` is the control: each float32 operand is split into a
bfloat16 head and a bfloat16 tail, and the product keeps the three terms
head*head + head*tail + tail*head, as the TPU's three-pass algorithm does,
in the forward and the backward products.  It is written out so that it
computes the same on any backend.
``precision="high_native"`` asks XLA for its own three-pass product, which
only a TPU has (elsewhere it is float32).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def _bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


@jax.custom_vjp
def _mm_three_pass(a, b):
    ah, bh = _bf16(a), _bf16(b)
    al, bl = _bf16(a - ah), _bf16(b - bh)
    return (jnp.dot(ah, bh, precision=HIGHEST)
            + jnp.dot(ah, bl, precision=HIGHEST)
            + jnp.dot(al, bh, precision=HIGHEST))


def _mm_three_pass_fwd(a, b):
    return _mm_three_pass(a, b), (a, b)


def _mm_three_pass_bwd(res, g):
    # the backward products run in three passes too, as on the chip
    a, b = res
    return _mm_three_pass(g, b.T), _mm_three_pass(a.T, g)


_mm_three_pass.defvjp(_mm_three_pass_fwd, _mm_three_pass_bwd)


def mm(a, b, precision: str):
    """``a @ b`` at ``highest``, ``high`` or ``high_native``."""
    if precision == "highest":
        return jnp.dot(a, b, precision=HIGHEST)
    if precision == "high_native":
        return jnp.dot(a, b, precision=jax.lax.Precision.HIGH)
    if precision == "high":
        return _mm_three_pass(a, b)
    raise ValueError(f"unknown precision {precision!r}")


def aggregate(h, src, dst, coef, num_dst: int, chunk: int):
    """``out[d] = sum_{e: dst[e] = d} coef[e] * h[src[e]]`` in float32,
    over edge chunks of ``chunk`` so that the (E, F) messages of one chunk
    are all that is ever held at once."""
    e = src.shape[0]
    n_chunks = max(-(-e // chunk), 1)
    pad = n_chunks * chunk - e
    src = jnp.pad(src, (0, pad)).reshape(n_chunks, chunk)
    dst = jnp.pad(dst, (0, pad)).reshape(n_chunks, chunk)
    coef = jnp.pad(coef, (0, pad)).reshape(n_chunks, chunk)

    def body(acc, part):
        s, d, c = part
        msgs = jnp.take(h, s, axis=0) * c[:, None]
        return acc + jax.ops.segment_sum(msgs, d, num_dst), None

    out, _ = jax.lax.scan(body, jnp.zeros((num_dst, h.shape[1]), h.dtype),
                          (src, dst, coef))
    return out


def masked_nll(logits, labels, mask):
    """Mean negative log-likelihood over the rows where ``mask`` is 1."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def adamw(params, grads, state, opt: dict):
    """One AdamW step with global-norm clipping, as the configuration's
    ``optimizer`` block states it.  Returns ``(params, state)``; the state
    holds ``m``, ``v`` and ``step``."""
    step = state["step"] + 1
    leaves = jax.tree.leaves(grads)
    if opt["clip_norm"]:
        norm = jnp.sqrt(sum(jnp.sum(g * g) for g in leaves))
        scale = jnp.minimum(1.0, opt["clip_norm"] / (norm + 1e-9))
        grads = jax.tree.map(lambda g: g * scale, grads)
    b1, b2 = opt["b1"], opt["b2"]
    m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g, state["m"], grads)
    v = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2) * g * g, state["v"],
                     grads)
    t = step.astype(jnp.float32)
    mc, vc = 1 - b1 ** t, 1 - b2 ** t

    def upd(p, m_, v_):
        u = (m_ / mc) / (jnp.sqrt(v_ / vc) + opt["eps"])
        if opt["weight_decay"] and p.ndim >= 2:
            u = u + opt["weight_decay"] * p
        return p - opt["lr"] * u

    return (jax.tree.map(upd, params, m, v),
            {"m": m, "v": v, "step": step})


def adamw_init(params):
    zeros = lambda p: jnp.zeros(p.shape, jnp.float32)
    return {"m": jax.tree.map(zeros, params),
            "v": jax.tree.map(zeros, params),
            "step": jnp.zeros((), jnp.int32)}


def train(loss_fn, params, batches, opt: dict, precision: str):
    """Run the reference for ``len(batches)`` steps from ``params``.

    Returns ``(losses, first_grad, params_after)``: each step's loss, the
    clipped gradient the optimizer applied in step 1 (read back from its
    first moment, as for the program), and the parameters after the last
    step."""
    @jax.jit
    def step(p, s, batch):
        loss, g = jax.value_and_grad(loss_fn)(p, batch, precision)
        p, s = adamw(p, g, s, opt)
        return p, s, loss

    state = adamw_init(params)
    losses, first_grad = [], None
    for i, batch in enumerate(batches):
        params, state, loss = step(params, state, batch)
        losses.append(float(loss))
        if i == 0:
            first_grad = jax.tree.map(lambda m: np.asarray(m) / (1 - opt["b1"]),
                                      state["m"])
    return losses, first_grad, jax.tree.map(np.asarray, params)


def normal_init(key, shape, fan_in: int):
    """Weights drawn N(0, 1/fan_in), as the program's layers draw them."""
    return jax.random.normal(key, shape, jnp.float32) / np.sqrt(fan_in)
