"""Dynamic-graph bench: incremental (delta-frontier) invalidation vs a
full-flush rebuild-on-schedule baseline, swept over update rates.

Per update rate r ∈ {0%, 1%, 5%} (stream events as a fraction of graph
nodes), two identical servers serve the SAME workload over the sparse
``er`` benchmark graph, folding the SAME synthetic update stream in 4
chunks on the same cadence:

* **incremental** — :meth:`GNNInferenceServer.apply_graph_update`
  invalidates only the (L-1)-hop frontier the delta reaches (memoized
  sampler picks keep untouched neighborhoods bit-identical);
* **flush** — the delta-blind baseline (``flush=True``): every fold
  wholesale-invalidates every admitted row — including zero-event folds,
  since a system without delta tracking cannot know nothing changed.

Recorded per (rate, strategy): embedding hit rate, invalidated
(re-refreshed) rows, cache-fill bytes, p50/p99 latency.  Asserted here,
not just reported:

* incremental hit-rate >= flush hit-rate at EVERY rate;
* incremental refreshes STRICTLY fewer rows than flush at every rate;
* a per-rate 2-device continual-training fold (S=1, hash) finishes with
  ``halo_staleness_violations_total == 0`` and a finite loss.

Results land in ``BENCH_dynamic.json`` at the repo root and as the usual
``name,us,derived`` CSV lines.
"""
import json
import os
import subprocess
import sys

from benchmarks.common import ROOT, SRC, emit

RATES = (0.0, 0.01, 0.05)
REQUESTS = 128
CHUNKS = 4
DEVICES = 2
EPOCHS = 2          # per side of the continual-training fold
STALENESS = 1
TIMEOUT_S = 2400


def _payload() -> None:
    """Runs inside the forced-device subprocess; prints one JSON blob."""
    import copy

    import jax
    import numpy as np

    from benchmarks.common import build_graph
    from repro.core import telemetry
    from repro.core.updates import GraphUpdateLog, synthesize_updates
    from repro.distributed import AsyncFullGraphTrainer
    from repro.models.gnn import model as GM
    from repro.models.gnn.model import GNNConfig
    from repro.optim import AdamW
    from repro.serving import GNNInferenceServer, poisson_workload

    telemetry.set_enabled(True)
    reg = telemetry.get_registry()
    telemetry.counter("halo_staleness_violations_total").reset()

    g0 = build_graph("er")
    cfg = GNNConfig(arch="sage", feat_dim=16, hidden=32,
                    num_classes=g0.num_classes)
    params = GM.init_gnn(cfg, jax.random.PRNGKey(0))
    # all sweep cells share one jitted forward: bucket shapes are static,
    # so each (bucket, block-shape) compiles once for the whole sweep
    # instead of once per server instance
    fwd = jax.jit(lambda p, inner, outer, x, ch, fm:
                  GM.forward_blocks_cached(cfg, p, inner, outer, x, ch, fm))
    cfg_t = GNNConfig(arch="gcn", feat_dim=16, hidden=32,
                      num_classes=g0.num_classes)
    params_t = GM.init_gnn(cfg_t, jax.random.PRNGKey(1))
    opt = AdamW(lr=1e-2, weight_decay=0.0)

    out = {}
    for rate in RATES:
        n_ev = int(round(rate * g0.num_nodes))
        rows = {}
        for mode in ("incremental", "flush"):
            g = copy.deepcopy(g0)
            log = (synthesize_updates(g, n_ev, seed=7) if n_ev
                   else GraphUpdateLog())
            srv = GNNInferenceServer(g, cfg, params, fanouts=[3, 3],
                                     buckets=(1, 4, 16), max_staleness=8,
                                     cache_policy="degree", seed=0,
                                     forward_fn=fwd)
            srv.warmup()
            wl = poisson_workload(REQUESTS, np.arange(g.num_nodes),
                                  4000.0, seed=1)
            per = -(-len(wl) // CHUNKS)
            per_ev = -(-log.last_seq // CHUNKS) if log.last_seq else 0
            for c in range(CHUNKS):
                chunk = wl[c * per:(c + 1) * per]
                if chunk:
                    srv.run(list(chunk))
                upto = min((c + 1) * per_ev, log.last_seq)
                srv.apply_graph_update(log, upto, flush=(mode == "flush"))
            s = srv.summary()
            assert s["served"] == REQUESTS, s["served"]
            assert srv._update_seq == log.last_seq
            print(f"payload: rate={rate} mode={mode} done", file=sys.stderr)
            rows[mode] = {
                "hit_ratio": s["embedding_hit_ratio"],
                "invalidated_rows": s["invalidated_rows"],
                "fill_bytes": s["fill_bytes"],
                "wire_bytes": s["wire_bytes"],
                "p50_ms": s["p50_ms"],
                "p99_ms": s["p99_ms"],
                "events": log.last_seq,
            }
        inc, fl = rows["incremental"], rows["flush"]
        assert inc["hit_ratio"] >= fl["hit_ratio"], (rate, rows)
        assert inc["invalidated_rows"] < fl["invalidated_rows"], (rate, rows)

        # continual training through the same rate: fold mid-run at S=1,
        # the staleness guarantee must survive the delta invalidation
        g = copy.deepcopy(g0)
        log = (synthesize_updates(g, n_ev, seed=7) if n_ev
               else GraphUpdateLog())
        tr = AsyncFullGraphTrainer(g, cfg_t, opt, DEVICES,
                                   partitioner="hash", staleness=STALENESS)
        p, o, _ = tr.run(params_t, opt.init(params_t), EPOCHS)
        fold = tr.fold_updates(log)
        p, o, loss = tr.run(p, o, EPOCHS)
        viol = reg.value("halo_staleness_violations_total")
        assert viol == 0.0, viol
        assert np.isfinite(loss), loss
        rows["train"] = {
            "loss": float(loss),
            "events": fold["events"],
            "ghost_rows_invalidated": fold["invalidated_rows"],
            "staleness_violations": int(viol),
        }
        out[f"{rate:.2f}"] = rows
        print(f"payload: rate={rate} train done", file=sys.stderr)
    print("DYNAMIC_JSON " + json.dumps(out))


def main() -> None:
    env = dict(os.environ)
    # the payload re-imports this module, so it needs ROOT (for
    # ``benchmarks.common``) as well as SRC on the path
    env["PYTHONPATH"] = SRC + os.pathsep + ROOT
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={DEVICES}")
    r = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--payload"],
        capture_output=True, text=True, timeout=TIMEOUT_S, env=env)
    blob = next((ln for ln in r.stdout.splitlines()
                 if ln.startswith("DYNAMIC_JSON ")), None)
    if r.returncode != 0 or blob is None:
        print(f"dynamic/SUBPROCESS_FAILED,0.0,"
              f"err={r.stderr[-200:].replace(chr(10), ' ')}")
        raise RuntimeError(f"dynamic payload failed (exit {r.returncode})")
    results = json.loads(blob[len("DYNAMIC_JSON "):])
    path = os.path.join(ROOT, "BENCH_dynamic.json")
    with open(path, "w") as f:
        json.dump({"devices": DEVICES, "requests": REQUESTS,
                   "chunks": CHUNKS, "rates": list(RATES),
                   "staleness": STALENESS, "results": results},
                  f, indent=2, sort_keys=True)
    for rate, rows in sorted(results.items()):
        for mode in ("incremental", "flush"):
            row = rows[mode]
            emit(f"dynamic/{mode}_rate{rate}", row["p50_ms"] * 1e3,
                 f"hit={row['hit_ratio']:.2%}"
                 f";invalidated={row['invalidated_rows']}"
                 f";fill_kib={row['fill_bytes'] / 1024:.1f}"
                 f";events={row['events']}")
        t = rows["train"]
        emit(f"dynamic/train_rate{rate}", 0.0,
             f"loss={t['loss']:.3f};events={t['events']}"
             f";ghost_inv={t['ghost_rows_invalidated']}"
             f";violations={t['staleness_violations']}")
    print(f"dynamic/BENCH_dynamic_json,0.0,"
          f"path={os.path.relpath(path, ROOT)}")


if __name__ == "__main__":
    if "--payload" in sys.argv:
        _payload()
    else:
        main()
