"""Multi-device (8 forced host devices) benchmark payload — executed in a
subprocess by bench_distributed.py.  Prints CSV rows directly.

Covers:
  Table 2/7 (§3.2.5): data-parallel pull vs P3 hybrid — step time +
    per-step collective bytes from the compiled HLO;
  §3.2.6: push vs pull aggregation collective bytes;
  Table 2 / §3.2.7: BSP vs stale (DistGNN) — per-epoch time + comm saved;
  §3.2.9: decentralized all-reduce vs parameter-server bytes.
"""
import os

os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8")

import time                                            # noqa: E402

import jax                                             # noqa: E402
import jax.numpy as jnp                                # noqa: E402
import numpy as np                                     # noqa: E402
from jax.sharding import PartitionSpec as P            # noqa: E402

from repro.core import coordination as C               # noqa: E402
from repro.core import parallel as PL                  # noqa: E402
from repro.core import propagation as PR               # noqa: E402
from repro.graph import generators as G                # noqa: E402
from repro.launch.hlo_analysis import collective_bytes  # noqa: E402
from repro.models.gnn import model as GM               # noqa: E402
from repro.models.gnn.model import GNNConfig           # noqa: E402
from repro.optim import AdamW, Sgd                     # noqa: E402

N_DEV = 8


def emit(name, us, derived):
    print(f"{name},{us:.1f},{derived}", flush=True)


def coll_of(jitted, *args):
    return collective_bytes(jitted.lower(*args).compile().as_text())


def timeit(fn, iters=5):
    fn()
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2] * 1e6


g = G.sbm(1024, 4, p_in=0.9, p_out=0.02, seed=0)
g = G.featurize(g, 64, seed=0, class_sep=1.5)
cfg = GNNConfig(arch="gcn", feat_dim=64, hidden=128, num_classes=4)
params = GM.init_gnn(cfg, jax.random.PRNGKey(0))
opt = AdamW(lr=1e-2, weight_decay=0.0)
sg = PR.shard_graph(g, N_DEV, method="hash")

# ---- pull (data-parallel full graph, BSP) ---------------------------------
mesh, pstep = PR.make_distributed_gcn_step(opt, N_DEV, mode="pull")
ostate = opt.init(params)


def run_pull():
    p2, o2, loss = pstep(params, ostate, sg)
    jax.block_until_ready(loss)


us_pull = timeit(run_pull)
emit("parallelism/data_parallel_pull_step", us_pull,
     f"nodes={g.num_nodes};edges={g.num_edges}")

# ---- P3 hybrid -------------------------------------------------------------
e = g.edges()
perm = sg.perm
es_g = perm[e[:, 0]].astype(np.int32)
ed_g = perm[e[:, 1]].astype(np.int32)
indeg, outdeg = np.asarray(sg.in_deg), np.asarray(sg.out_deg)
coef = (1 / np.sqrt(outdeg[es_g]) / np.sqrt(indeg[ed_g])).astype(np.float32)
p3_params = [dict(params[0]), dict(params[1])]
p3_opt = AdamW(lr=1e-2, weight_decay=0.0)
p3_state = p3_opt.init(p3_params)
mesh3, p3step = PL.make_p3_train_step(p3_opt, N_DEV)
jp3 = jax.jit(p3step)
args3 = (p3_params, p3_state, sg.x, jnp.asarray(es_g), jnp.asarray(ed_g),
         jnp.ones(len(e), jnp.float32), jnp.asarray(coef), sg.labels,
         sg.label_mask)


def run_p3():
    p2, o2, loss = jp3(*args3)
    jax.block_until_ready(loss)


us_p3 = timeit(run_p3)
c3 = coll_of(jp3, *args3)
emit("parallelism/p3_hybrid_step", us_p3,
     f"coll_bytes={c3.get('total', 0)};"
     f"rs={c3.get('reduce-scatter', 0)};ag={c3.get('all-gather', 0)}")

# ---- push vs pull aggregation collective bytes -----------------------------
F = 64
h_loc_spec = P(PR.AXIS, None)
push_layout = PR.push_layout(sg, g)


def pull_once(h, es, ed, em):
    return PR.pull_aggregate(h, es, ed, em, sg.n_local)


def push_once(h, es, ed, em):
    return PR.push_aggregate(h, es, ed, em, sg.n_local * N_DEV)


x = jnp.asarray(np.random.default_rng(0).normal(
    size=(sg.n_local * N_DEV, F)), jnp.float32)
pull_j = jax.jit(jax.shard_map(
    pull_once, mesh=mesh,
    in_specs=(h_loc_spec, P(PR.AXIS), P(PR.AXIS), P(PR.AXIS)),
    out_specs=h_loc_spec, check_vma=False))
push_j = jax.jit(jax.shard_map(
    push_once, mesh=mesh,
    in_specs=(h_loc_spec, P(PR.AXIS), P(PR.AXIS), P(PR.AXIS)),
    out_specs=h_loc_spec, check_vma=False))
cb_pull = coll_of(pull_j, x, sg.edge_src_g, sg.edge_dst_l, sg.edge_mask)
cb_push = coll_of(push_j, x, push_layout["edge_src_l"],
                  push_layout["edge_dst_g"], push_layout["edge_mask"])
us_pl = timeit(lambda: jax.block_until_ready(
    pull_j(x, sg.edge_src_g, sg.edge_dst_l, sg.edge_mask)))
us_ps = timeit(lambda: jax.block_until_ready(
    push_j(x, push_layout["edge_src_l"], push_layout["edge_dst_g"],
           push_layout["edge_mask"])))
emit("propagation/pull_all_gather", us_pl,
     f"coll_bytes={cb_pull.get('total', 0)}")
emit("propagation/push_reduce_scatter", us_ps,
     f"coll_bytes={cb_push.get('total', 0)}")

# correctness cross-check: push == pull aggregation
a = pull_j(x, sg.edge_src_g, sg.edge_dst_l, sg.edge_mask)
b = push_j(x, push_layout["edge_src_l"], push_layout["edge_dst_g"],
           push_layout["edge_mask"])
err = float(jnp.max(jnp.abs(a - b)))
emit("propagation/push_eq_pull", 0.0, f"maxerr={err:.2e}")

# ---- sync: BSP vs stale ----------------------------------------------------
mesh, sstep = PR.make_distributed_gcn_step(opt, N_DEV, mode="stale")
for staleness in (1, 4, 8):
    p2 = [dict(l) for l in params]
    o2 = opt.init(p2)
    t0 = time.perf_counter()
    losses = []
    for it in range(12):
        # refresh costs one extra device round-trip of the full features
        halo = sg.x if it % staleness == 0 else halo  # noqa: F821
        p2, o2, loss = sstep(p2, o2, sg, halo_cache=halo)
        losses.append(float(loss))
    dt = (time.perf_counter() - t0) * 1e6 / 12
    emit(f"sync/stale_s{staleness}", dt,
         f"loss0={losses[0]:.3f};loss11={losses[-1]:.4f};"
         f"halo_exchanges_saved={(1 - 1 / staleness):.0%}")

# ---- coordination: all-reduce vs parameter server --------------------------
sgd = Sgd(lr=0.1)
w0 = {"w": jnp.ones((256, 256))}
s0 = sgd.init(w0)


def make(coord):
    def body(w, s, gseed):
        grads = {"w": gseed * jnp.ones((256, 256))}
        return C.COORDINATORS[coord](sgd, w, grads, s)

    return jax.jit(jax.shard_map(body, mesh=mesh,
                             in_specs=(P(), P(), P(PR.AXIS)),
                             out_specs=(P(), P()), check_vma=False))


gseed = jnp.arange(N_DEV, dtype=jnp.float32)
for coord in ("decentralized", "parameter_server"):
    f = make(coord)
    cb = coll_of(f, w0, s0, gseed)
    us = timeit(lambda: jax.block_until_ready(f(w0, s0, gseed)))
    emit(f"coordination/{coord}", us, f"coll_bytes={cb.get('total', 0)}")

print("SPMD_BENCH_DONE")
