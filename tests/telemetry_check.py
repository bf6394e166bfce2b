"""Acceptance check for the unified telemetry plane — run in a subprocess
with 2 forced host devices.

Phase 1 (serving): a short cached serve; the registry snapshot's cache
hit/miss counters and per-path comm bytes must equal the
``EmbeddingCache`` / ``Transport`` instance counters exactly.

Phase 2 (training): a 2-device ``--minibatch --wire-codec int8
--use-kernel``-equivalent run; the snapshot must expose per-path comm
bytes (matching the partition stores' ``Transport.total_bytes``), a
step-time histogram with one sample per executed step, and a lowered step
whose HLO names its aggregation's scope, forward and backward.

Phase 3 (dynamic graphs): the update-log / invalidation counters
(``graph_updates_total{kind}``, ``cache_invalidated_rows_total``,
``delta_refresh_rows_total``) must equal their instance counters exactly,
and the PR-6 warmup-reset rule must hold — ``reset_stats`` zeroes the
instance counter AND its registry series in lockstep, so no stale count
leaks across a warmup reset.

Then: the Prometheus exposition round-trips through
``parse_prometheus`` and the JSONL trace validates.  Prints
``PASS telemetry-plane`` on success.
"""
import os
import sys
import tempfile

N_DEV = 2
os.environ["XLA_FLAGS"] = (
    f"--xla_force_host_platform_device_count={N_DEV} "
    + os.environ.get("XLA_FLAGS", ""))

import jax                                              # noqa: E402
import numpy as np                                      # noqa: E402

from repro.core import telemetry                        # noqa: E402
from repro.graph import generators as G                 # noqa: E402
from repro.models.gnn import model as GM                # noqa: E402
from repro.models.gnn.model import GNNConfig            # noqa: E402
from repro.optim import AdamW                           # noqa: E402

assert jax.device_count() == N_DEV, jax.device_count()

telemetry.set_enabled(True)
reg = telemetry.get_registry()

g = G.sbm(144, 4, p_in=0.9, p_out=0.02, seed=0)
g = G.featurize(g, 16, seed=0, class_sep=1.5)

# ---------------------------------------------------------------------------
# phase 1: serving — snapshot vs EmbeddingCache / Transport counters
# ---------------------------------------------------------------------------
from repro.serving import GNNInferenceServer, poisson_workload  # noqa: E402

cfg_s = GNNConfig(arch="sage", feat_dim=16, hidden=32, num_classes=4)
srv = GNNInferenceServer(
    g, cfg_s, GM.init_gnn(cfg_s, jax.random.PRNGKey(0)),
    fanouts=[3, 3], buckets=[1, 4, 8], cache_policy="degree",
    cache_capacity=g.num_nodes // 2, seed=0)
srv.warmup()     # resets cache stats AND the matching telemetry series
srv.run(poisson_workload(48, np.arange(g.num_nodes), 2000.0, seed=1))

hits = reg.value("cache_lookups_total",
                 cache="serving.embedding", result="hit")
misses = reg.value("cache_lookups_total",
                   cache="serving.embedding", result="miss")
assert int(hits) == srv.cache.hits, (hits, srv.cache.hits)
assert int(misses) == srv.cache.misses, (misses, srv.cache.misses)
assert hits + misses > 0

feat_bytes = reg.total("comm_bytes_total", path="serving.features")
assert int(feat_bytes) == srv.cache.features.transport.total_bytes
fill_bytes = reg.total("comm_bytes_total", path="serving.fill")
assert int(fill_bytes) == sum(t.total_bytes for t in srv.cache.fill.values())
assert fill_bytes > 0    # the cached policy really wrote fills

lat = reg.get_histogram("serving_request_latency_seconds")
assert lat is not None and lat.count == srv.stats.served == 48
assert reg.value("serving_requests_total") == 48
assert len(reg.tracer.events) > 0       # serve spans recorded

# ---------------------------------------------------------------------------
# phase 2: 2-device minibatch training, int8 wire codec, Pallas kernels
# ---------------------------------------------------------------------------
from repro.distributed import (DistributedMinibatchSampler,   # noqa: E402
                               collate,
                               make_distributed_minibatch_step)

cfg_t = GNNConfig(arch="gcn", feat_dim=16, hidden=32, num_classes=4,
                  use_kernel=True, wire_codec="int8")
params = GM.init_gnn(cfg_t, jax.random.PRNGKey(0))
opt = AdamW(lr=1e-2, weight_decay=0.0)
ostate = opt.init(params)

dist = DistributedMinibatchSampler(
    g, N_DEV, [3, 3], 24, partitioner="hash", cache_policy="degree",
    cache_capacity=g.num_nodes // 10, wire_codec="int8", seed=0)
mesh, dstep = make_distributed_minibatch_step(cfg_t, opt, N_DEV,
                                              dist.block_shapes())

import time                                             # noqa: E402
m_step = telemetry.histogram("train_step_seconds", mode="minibatch_dist")
rng = np.random.default_rng(1)
STEPS = 3
for _ in range(STEPS):
    seeds = rng.choice(g.num_nodes, 24, replace=False)
    arrays = collate(dist.sample_global(seeds), dist.out_deg)
    t0 = time.perf_counter()
    params, ostate, loss = dstep(params, ostate, arrays)
    m_step.observe(time.perf_counter() - t0)

snap = reg.snapshot()

# per-path comm bytes match the sum over the partition stores' transports
mb_bytes = reg.total("comm_bytes_total", path="minibatch.features")
want = sum(s.transport.total_bytes for s in dist.stores)
assert int(mb_bytes) == want, (mb_bytes, want)
assert mb_bytes > 0
codecs = {k for k in snap["comm_bytes_total"]["series"]
          if "path=minibatch.features" in k}
assert all("codec=int8" in k for k in codecs), codecs

# cache hit counters match the stores
mb_hits = reg.value("cache_lookups_total",
                    cache="minibatch.features", result="hit")
mb_miss = reg.value("cache_lookups_total",
                    cache="minibatch.features", result="miss")
assert int(mb_hits) == sum(s.hits for s in dist.stores)
assert int(mb_miss) == sum(s.misses for s in dist.stores)

# step-time histogram: one sample per executed step
hs = snap["train_step_seconds"]["series"]["mode=minibatch_dist"]
assert hs["count"] == STEPS, hs

# named scopes: the lowered step names the aggregation's operations,
# forward and (transposed) backward
ir = jax.jit(dstep).lower(params, ostate, arrays).as_text(debug_info=True)
scoped = ir.count("gnn.aggregate)/")
assert "jvp(gnn.aggregate)/" in ir, "no gnn.aggregate scope in the step"
assert "transpose(jvp(gnn.aggregate))/" in ir, "no backward scope"

# ---------------------------------------------------------------------------
# phase 3: dynamic-graph counters — registry == instance, reset in lockstep
# ---------------------------------------------------------------------------
from repro.core import partitioning as PT               # noqa: E402
from repro.core.halo import HaloExchange, build_halo    # noqa: E402
from repro.core.updates import (GraphUpdateLog,         # noqa: E402
                                synthesize_updates)

# update-log event counters, per kind
log = GraphUpdateLog()
log.reset_stats()        # clean slate: the series is process-global
synthesize_updates(g, 20, seed=5, log=log)
assert sum(log.counts.values()) == 20
for kind, n in log.counts.items():
    got = reg.value("graph_updates_total", kind=kind)
    assert int(got) == n, (kind, got, n)

# serving-cache invalidation counter, through a real graph-delta fold;
# warmup-reset rule: reset_stats zeroes instance + series together
srv.cache.reset_stats()
assert reg.value("cache_invalidated_rows_total",
                 cache="serving.embedding") == 0.0
n_inv = srv.apply_graph_update(log)["invalidated_rows"]
got_inv = reg.value("cache_invalidated_rows_total",
                    cache="serving.embedding")
assert int(got_inv) == srv.cache.invalidated_rows == n_inv, (
    got_inv, srv.cache.invalidated_rows, n_inv)
assert n_inv > 0
srv.cache.reset_stats()
assert srv.cache.invalidated_rows == 0
assert reg.value("cache_invalidated_rows_total",
                 cache="serving.embedding") == 0.0

# halo ghost-row invalidation counter (no warmup on the training side:
# the counter has no reset entry point, so registry must track instance)
telemetry.counter("delta_refresh_rows_total").reset()
ex = HaloExchange(build_halo(g, PT.partition(g, 2, "hash")), [8],
                  max_staleness=2)
ghost = np.where(ex.ghost_rows)[0][:6]
n_ghost_inv = ex.invalidate_rows(ghost)
assert int(reg.value("delta_refresh_rows_total")) == ex.delta_rows \
    == n_ghost_inv > 0

# log reset zeroes counts and series in lockstep
log.reset_stats()
assert all(v == 0 for v in log.counts.values())
for kind in log.counts:
    assert reg.value("graph_updates_total", kind=kind) == 0.0

# ---------------------------------------------------------------------------
# exposition round trip + trace validation
# ---------------------------------------------------------------------------
with tempfile.TemporaryDirectory() as td:
    prom = os.path.join(td, "metrics.prom")
    trace = os.path.join(td, "trace.jsonl")
    reg.write_prometheus(prom)
    parsed = telemetry.parse_prometheus(open(prom).read())
    key = (("codec", "int8"), ("kind", "payload"),
           ("path", "minibatch.features"))
    assert key in parsed["comm_bytes_total"], sorted(parsed)
    n_ev = reg.tracer.export_jsonl(trace)
    assert telemetry.validate_trace_jsonl(trace) == n_ev > 0

print(f"PASS telemetry-plane n_dev={N_DEV} "
      f"serve_hits={int(hits)} mb_kib={mb_bytes / 1024:.1f} "
      f"steps={STEPS} scoped_ops={scoped} events={n_ev} "
      f"dyn_invalidated={n_inv} dyn_ghost_rows={n_ghost_inv}")
