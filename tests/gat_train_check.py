"""Training-equivalence check for the one-pass fused GAT kernel — run
in a subprocess so ``--xla_force_host_platform_device_count=N`` can be
set before JAX imports.

argv: n_dev

Trains 10 full-graph steps of the published GAT layer (self-loops,
concatenated hidden heads, head-mean output, bias, skip path, ELU) with
``use_kernel=True`` (the fused online-softmax Pallas kernel, interpret
mode on CPU) and with the XLA path from the same init, then demands every
parameter agree to <= 1e-5 — ``jax.grad`` through the composed custom VJP (alpha recompute
+ swapped fused kernels + closed-form softmax backward) matches XLA
autodiff step for step.

* ``n_dev == 1`` uses the single-device full-graph trainer.
* ``n_dev > 1`` replicates the same step under ``jax.pmap`` with
  ``pmean``'d gradients — identical data per replica, so the result must
  still match the single-device reference while the kernel executes on
  every forced host device.
"""
import os
import sys

N_DEV = int(sys.argv[1]) if len(sys.argv) > 1 else 1
STEPS = 10
TOL = 1e-5

if N_DEV > 1:
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={N_DEV} "
        + os.environ.get("XLA_FLAGS", ""))

import jax                                              # noqa: E402
import jax.numpy as jnp                                 # noqa: E402

from repro.core.abstraction import DeviceGraph          # noqa: E402
from repro.graph import generators as G                 # noqa: E402
from repro.models.gnn import model as GM                # noqa: E402
from repro.models.gnn.model import GNNConfig            # noqa: E402
from repro.optim import AdamW                           # noqa: E402

assert jax.device_count() >= N_DEV, jax.device_count()

g = G.sbm(144, 4, p_in=0.9, p_out=0.02, seed=0)
g = G.featurize(g, 16, seed=0, class_sep=1.5)

opt = AdamW(lr=1e-2, weight_decay=0.0)
dg = DeviceGraph.from_graph(g)
x = jnp.asarray(g.features)
y = jnp.asarray(g.labels)
mask = jnp.ones_like(y, jnp.float32)


def run(use_kernel: bool):
    cfg = GNNConfig(arch="gat", feat_dim=16, hidden=32, num_classes=4,
                    use_kernel=use_kernel)
    params = GM.init_gnn(cfg, jax.random.PRNGKey(0))
    ostate = opt.init(params)
    if N_DEV == 1:
        step = jax.jit(GM.make_fullgraph_train_step(cfg, opt))
        for _ in range(STEPS):
            params, ostate, loss = step(params, ostate, dg, x, y, mask)
        return params, float(loss)

    def dp_step(params, ostate):
        def loss_fn(p):
            logits = GM.forward_full(cfg, p, dg, x)
            return GM.nll_loss(logits, y, mask)
        loss, grads = jax.value_and_grad(loss_fn)(params)
        grads = jax.lax.pmean(grads, "dp")      # identical replicas:
        loss = jax.lax.pmean(loss, "dp")        # pmean is the identity
        params, ostate = opt.apply(params, grads, ostate)
        return params, ostate, loss

    step = jax.pmap(dp_step, axis_name="dp")
    rep = jax.tree.map(lambda a: jnp.stack([a] * N_DEV), params)
    ostate = jax.tree.map(lambda a: jnp.stack([a] * N_DEV), ostate)
    for _ in range(STEPS):
        rep, ostate, loss = step(rep, ostate)
    return jax.tree.map(lambda a: a[0], rep), float(loss[0])


p_ref, loss_ref = run(use_kernel=False)
p_ker, loss_ker = run(use_kernel=True)

assert abs(loss_ref - loss_ker) < TOL, (loss_ref, loss_ker)
diffs = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))),
                     p_ker, p_ref)
maxdiff = max(jax.tree_util.tree_leaves(diffs))
assert maxdiff <= TOL, (maxdiff, diffs)

print(f"PASS gat-fused-equivalence n_dev={N_DEV} steps={STEPS} "
      f"maxdiff={maxdiff:.2e} loss={loss_ker:.4f}")
