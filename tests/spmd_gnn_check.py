"""SPMD GNN check — run in a subprocess with 8 forced host devices.

Validates:
  1. distributed pull-mode full-graph GCN == single-device reference
     (numerical equivalence of loss trajectories);
  2. stale mode (DistGNN) trains with bounded loss divergence;
  3. P3 hybrid step runs and learns;
  4. PS coordination == all-reduce coordination (same params).
Prints PASS lines; the pytest wrapper asserts on them.
"""
import os

os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                           + os.environ.get("XLA_FLAGS", ""))

import jax                      # noqa: E402
import jax.numpy as jnp         # noqa: E402
import numpy as np              # noqa: E402

from repro.core import propagation as PR            # noqa: E402
from repro.core import parallel as PL               # noqa: E402
from repro.core.abstraction import DeviceGraph      # noqa: E402
from repro.graph import generators as G             # noqa: E402
from repro.models.gnn import model as GM            # noqa: E402
from repro.models.gnn.model import GNNConfig        # noqa: E402
from repro.optim import AdamW, Sgd                  # noqa: E402

assert jax.device_count() == 8, jax.device_count()

g = G.sbm(192, 4, p_in=0.9, p_out=0.02, seed=0)
g = G.featurize(g, 16, seed=0, class_sep=1.5)
N_DEV = 8

cfg = GNNConfig(arch="gcn", feat_dim=16, hidden=32, num_classes=4)
key = jax.random.PRNGKey(0)
params0 = GM.init_gnn(cfg, key)
opt = AdamW(lr=1e-2, weight_decay=0.0)

# --- single-device reference on the SAME permuted/padded layout ----------
sg = PR.shard_graph(g, N_DEV, method="hash")
dg_edges_src = np.asarray(sg.edge_src_g)
dg_edges_dst_local = np.asarray(sg.edge_dst_l)
n_local = sg.n_local
# reconstruct global edge list from the sharded layout
dev_of = np.repeat(np.arange(N_DEV), sg.e_local)
dst_g = dg_edges_dst_local + dev_of * n_local
mask = np.asarray(sg.edge_mask)

x_full = np.asarray(sg.x)
labels_full = np.asarray(sg.labels)
lmask_full = np.asarray(sg.label_mask)
indeg = np.asarray(sg.in_deg)
outdeg = np.asarray(sg.out_deg)


def ref_loss(params, x):
    h = jnp.asarray(x)
    for i, p in enumerate(params):
        hw = h @ p["w"]
        coef = (1 / np.sqrt(outdeg[dg_edges_src])
                * 1 / np.sqrt(indeg[dst_g]) * mask)
        feat = hw[dg_edges_src] * jnp.asarray(coef)[:, None]
        agg = jax.ops.segment_sum(feat, jnp.asarray(dst_g), len(x))
        h = agg + p["b"]
        if i + 1 < len(params):
            h = jax.nn.relu(h)
    logz = jax.nn.logsumexp(h, axis=-1)
    gold = jnp.take_along_axis(h, jnp.asarray(labels_full)[:, None],
                               axis=-1)[:, 0]
    return jnp.sum((logz - gold) * lmask_full) / lmask_full.sum()


def ref_train(n_steps):
    params = jax.tree.map(lambda a: a, params0)
    ostate = opt.init(params)
    losses = []

    @jax.jit
    def step(params, ostate):
        loss, grads = jax.value_and_grad(
            lambda p: ref_loss(p, x_full))(params)
        params, ostate = opt.apply(params, grads, ostate)
        return params, ostate, loss

    for _ in range(n_steps):
        params, ostate, loss = step(params, ostate)
        losses.append(float(loss))
    return params, losses


mesh, dstep = PR.make_distributed_gcn_step(opt, N_DEV, mode="pull")
params = jax.tree.map(lambda a: a, params0)
ostate = opt.init(params)
dlosses = []
for _ in range(10):
    params, ostate, loss = dstep(params, ostate, sg)
    dlosses.append(float(loss))

rparams, rlosses = ref_train(10)
# fp32 reduction-order differences compound through AdamW: demand tight
# agreement early, relative agreement late.
early = max(abs(a - b) for a, b in zip(dlosses[:4], rlosses[:4]))
late = abs(dlosses[-1] - rlosses[-1]) / rlosses[-1]
assert early < 1e-4, (dlosses, rlosses)
assert late < 0.01, (dlosses, rlosses)
# parameter-level equivalence after 10 steps: the guard for gradient
# scaling bugs (e.g. psum inside loss_fn under check_vma=False multiplies
# grads by n_dev) that Adam's scale-invariance + clipping hide from the
# EARLY loss trajectory entirely and leave late_rel at only ~0.04
pdiff = max(
    float(jnp.max(jnp.abs(a - b)))
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(rparams)))
assert pdiff < 1e-4, pdiff
print(f"PASS pull-equivalence early={early:.2e} late_rel={late:.3f} "
      f"pdiff={pdiff:.2e}")

# --- stale mode: refresh halo every 3 steps -------------------------------
mesh, sstep = PR.make_distributed_gcn_step(opt, N_DEV, mode="stale")
params = jax.tree.map(lambda a: a, params0)
ostate = opt.init(params)
halo = sg.x
slosses = []
for it in range(12):
    if it % 3 == 0:
        halo = sg.x * 0 + np.asarray(sg.x)  # emulate refresh from store
    params, ostate, loss = sstep(params, ostate, sg, halo_cache=halo)
    slosses.append(float(loss))
assert slosses[-1] < slosses[0], slosses
print(f"PASS stale-mode loss {slosses[0]:.3f}->{slosses[-1]:.3f}")

# --- push mode: reduce-scatter partial aggregates --------------------------
push_arrays = PR.push_layout(sg, g)
mesh, pushstep = PR.make_distributed_gcn_step(opt, N_DEV, mode="push")
params = jax.tree.map(lambda a: a, params0)
ostate = opt.init(params)
plosses = []
for _ in range(10):
    params, ostate, loss = pushstep(params, ostate, sg,
                                    push_arrays=push_arrays)
    plosses.append(float(loss))
err_push = max(abs(a - b) for a, b in zip(plosses[:4], rlosses[:4]))
assert err_push < 1e-3, (plosses, rlosses)
print(f"PASS push-equivalence early={err_push:.2e}")

# --- P3 hybrid -------------------------------------------------------------
e = g.edges()
perm = sg.perm
es_g = perm[e[:, 0]].astype(np.int32)
ed_g = perm[e[:, 1]].astype(np.int32)
coef = (1 / np.sqrt(outdeg[es_g]) / np.sqrt(indeg[ed_g])).astype(np.float32)
emask = np.ones(len(e), np.float32)

p3_params = [dict(params0[0]), dict(params0[1])]
p3_opt = AdamW(lr=1e-2, weight_decay=0.0)
p3_state = p3_opt.init(p3_params)
mesh3, p3step = PL.make_p3_train_step(p3_opt, N_DEV)
jp3 = jax.jit(p3step)
p3_losses = []
for _ in range(10):
    p3_params, p3_state, loss = jp3(
        p3_params, p3_state, jnp.asarray(x_full), jnp.asarray(es_g),
        jnp.asarray(ed_g), jnp.asarray(emask), jnp.asarray(coef),
        jnp.asarray(labels_full), jnp.asarray(lmask_full))
    p3_losses.append(float(loss))
err3 = max(abs(a - b) for a, b in zip(p3_losses, rlosses))
assert err3 < 1e-2, (p3_losses, rlosses[:10])
print(f"PASS p3-hybrid maxerr={err3:.2e}")

# --- coordination: PS == all-reduce ---------------------------------------
from jax.sharding import PartitionSpec as P           # noqa: E402
from repro.core import coordination as C              # noqa: E402

sgd = Sgd(lr=0.1)
w0 = {"w": jnp.ones((4, 4))}
s0 = sgd.init(w0)


def grad_for(i):
    return {"w": jnp.full((4, 4), float(i))}


def run(coord):
    def body(w, s, gseed):
        grads = {"w": gseed * jnp.ones((4, 4))}
        return C.COORDINATORS[coord](sgd, w, grads, s)

    f = jax.shard_map(body, mesh=mesh,
                  in_specs=(P(), P(), P(PR.AXIS)),
                  out_specs=(P(), P()), check_vma=False)
    gseed = jnp.arange(8, dtype=jnp.float32).reshape(8)
    return jax.jit(f)(w0, s0, gseed)


wa, _ = run("decentralized")
wb, _ = run("parameter_server")
np.testing.assert_allclose(np.asarray(wa["w"]), np.asarray(wb["w"]),
                           atol=1e-5)
print("PASS coordination ps==allreduce")
print("ALL SPMD CHECKS PASS")
