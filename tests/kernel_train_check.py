"""Training equivalence check for the differentiable Pallas aggregation
kernels — run in a subprocess with
``--xla_force_host_platform_device_count=N``.

argv: n_dev [partitioner]

Trains 10 full-graph GCN steps with ``use_kernel=True`` (the fused
gather-scale-segment-sum Pallas kernel, interpret mode on CPU) and with
the ``jax.ops`` reference from the same init, then demands every
parameter agree to <= 1e-5 — i.e. ``jax.grad`` through the kernels'
custom VJPs matches the XLA autodiff path step for step.

* ``n_dev == 1`` uses the single-device full-graph trainer
  (:func:`repro.models.gnn.model.make_fullgraph_train_step` driven by
  ``GNNConfig.use_kernel``), which exercises the fused GCN layer path.
* ``n_dev > 1`` uses the distributed pull step
  (:func:`repro.core.propagation.make_distributed_gcn_step`), which
  exercises the fused kernel *inside shard_map* — custom VJP under
  ``check_vma=False`` with psum'd gradients.
"""
import os
import sys

N_DEV = int(sys.argv[1]) if len(sys.argv) > 1 else 2
METHOD = sys.argv[2] if len(sys.argv) > 2 else "hash"
STEPS = 10
TOL = 1e-5

if N_DEV > 1:
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={N_DEV} "
        + os.environ.get("XLA_FLAGS", ""))

import jax                                              # noqa: E402
import jax.numpy as jnp                                 # noqa: E402

from repro.core import propagation as PR                # noqa: E402
from repro.graph import generators as G                 # noqa: E402
from repro.models.gnn import model as GM                # noqa: E402
from repro.models.gnn.model import GNNConfig            # noqa: E402
from repro.optim import AdamW                           # noqa: E402

assert jax.device_count() >= N_DEV, jax.device_count()

g = G.sbm(144, 4, p_in=0.9, p_out=0.02, seed=0)
g = G.featurize(g, 16, seed=0, class_sep=1.5)

opt = AdamW(lr=1e-2, weight_decay=0.0)


def run(use_kernel: bool):
    cfg = GNNConfig(arch="gcn", feat_dim=16, hidden=32, num_classes=4,
                    use_kernel=use_kernel)
    params = GM.init_gnn(cfg, jax.random.PRNGKey(0))
    ostate = opt.init(params)
    if N_DEV == 1:
        from repro.core.abstraction import DeviceGraph
        dg = DeviceGraph.from_graph(g)
        x = jnp.asarray(g.features)
        y = jnp.asarray(g.labels)
        mask = jnp.ones_like(y, jnp.float32)
        step = jax.jit(GM.make_fullgraph_train_step(cfg, opt))
        for _ in range(STEPS):
            params, ostate, loss = step(params, ostate, dg, x, y, mask)
        return params, float(loss)
    sg = PR.shard_graph(g, N_DEV, method=METHOD)
    _, step = PR.make_distributed_gcn_step(opt, N_DEV, mode="pull",
                                           use_kernel=use_kernel)
    for _ in range(STEPS):
        params, ostate, loss = step(params, ostate, sg)
    return params, float(loss)


p_ref, loss_ref = run(use_kernel=False)
p_ker, loss_ker = run(use_kernel=True)

assert abs(loss_ref - loss_ker) < TOL, (loss_ref, loss_ker)
diffs = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))),
                     p_ker, p_ref)
maxdiff = max(jax.tree_util.tree_leaves(diffs))
assert maxdiff <= TOL, (maxdiff, diffs)

print(f"PASS kernel-equivalence n_dev={N_DEV} part={METHOD} "
      f"steps={STEPS} maxdiff={maxdiff:.2e} loss={loss_ker:.4f}")
