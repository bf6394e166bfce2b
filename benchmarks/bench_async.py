"""Staleness-bounded asynchronous full-graph training bench (survey
§3.2.7: "the zero-/delayed-communication strategies are fastest with
slight accuracy fluctuation") with a wire-codec axis (the survey's
communication-reduction chapter: quantized ghost transfers à la
Dorylus/SANCUS).

Sweeps the staleness bound S ∈ {0, 1, 2} × wire codec ∈ {fp32, bf16,
int8} on er / sbm / reddit-like graphs (2 forced host devices,
subprocess so the device count can be set before jax initializes) and
records, per (graph, codec, S):

* ``step_ms``        — mean wall time per training step (post-warmup);
* ``bytes_per_step`` — cross-partition ghost-refresh traffic (payload at
  the codec's per-row wire size + per-RPC headers, consumed-plan
  accounting);
* ``accuracy`` / ``accuracy_gap`` — final full-graph accuracy and its
  gap vs the same codec's S=0 run from the same init;
* ``accuracy_gap_vs_fp32`` / ``bytes_vs_fp32`` — gap and byte ratio vs
  the fp32 codec at the *same* S (the compression claims);
* ``comm_savings``   — fraction of the same-codec synchronous volume
  saved by staleness.

Results land in ``BENCH_async.json`` at the repo root (see
docs/benchmarks.md for the field glossary) and are also emitted as the
usual ``name,us,derived`` CSV lines.  The acceptance invariants are
asserted here, not just reported:

* bytes/step strictly decreasing in S on the reddit-like graph, for
  EVERY codec (RefreshPlan estimates are codec-aware);
* int8 bytes/step ≤ 30% of fp32 at the same (graph, S);
* |accuracy(int8) − accuracy(fp32)| ≤ 0.02 at the same (graph, S).
"""
import json
import os
import subprocess
import sys

from benchmarks.common import ROOT, SRC, emit

GRAPHS = ("er", "sbm", "reddit-like")
STALENESS = (0, 1, 2)
CODECS = ("fp32", "bf16", "int8")
DEVICES = 2
EPOCHS = 12
HIDDEN = 64
REFRESH_FRAC = 0.05
INT8_BYTES_FRAC = 0.30
INT8_ACC_GAP = 0.02


def _payload() -> None:
    """Runs inside the forced-device subprocess; prints one JSON blob."""
    import numpy as np

    from benchmarks.common import build_graph
    from repro.distributed import AsyncFullGraphTrainer
    from repro.models.gnn import model as GM
    from repro.models.gnn.model import GNNConfig
    from repro.optim import AdamW

    import jax

    out = {}
    for name in GRAPHS:
        g = build_graph(name)
        opt = AdamW(lr=1e-2, weight_decay=0.0)
        by_codec = {}
        for codec in CODECS:
            cfg = GNNConfig(arch="gcn", feat_dim=g.features.shape[1],
                            hidden=HIDDEN, num_classes=g.num_classes,
                            wire_codec=codec)
            # same init for every (codec, S) cell of this graph
            params0 = GM.init_gnn(cfg, jax.random.PRNGKey(0))
            rows = {}
            for s in STALENESS:
                tr = AsyncFullGraphTrainer(g, cfg, opt, DEVICES,
                                           partitioner="hash", staleness=s,
                                           refresh_frac=REFRESH_FRAC)
                p, _, loss = tr.run(params0, opt.init(params0), EPOCHS)
                st = tr.stats()
                # drop the compile step from timing
                times = tr.step_times_s[1:] or tr.step_times_s
                rows[str(s)] = {
                    "loss": loss,
                    "accuracy": tr.accuracy(p),
                    "step_ms": 1e3 * sum(times) / len(times),
                    "bytes_per_step": st["bytes_per_step"],
                    "sync_bytes_per_step": st["sync_bytes_per_step"],
                    "comm_savings": st["comm_savings"],
                    "ghost_rows": st["ghost_rows"],
                }
            acc0 = rows["0"]["accuracy"]
            for s in STALENESS:
                rows[str(s)]["accuracy_gap"] = \
                    acc0 - rows[str(s)]["accuracy"]
            by_codec[codec] = rows
            assert np.isfinite([r["loss"] for r in rows.values()]).all()
        # cross-codec claims at the same S
        for codec in CODECS:
            for s in STALENESS:
                row = by_codec[codec][str(s)]
                ref = by_codec["fp32"][str(s)]
                row["bytes_vs_fp32"] = (row["bytes_per_step"]
                                        / max(ref["bytes_per_step"], 1))
                row["accuracy_gap_vs_fp32"] = (ref["accuracy"]
                                               - row["accuracy"])
        out[name] = by_codec
        for s in STALENESS:
            r8 = by_codec["int8"][str(s)]
            assert r8["bytes_vs_fp32"] <= INT8_BYTES_FRAC, \
                (name, s, r8["bytes_vs_fp32"])
            assert abs(r8["accuracy_gap_vs_fp32"]) <= INT8_ACC_GAP, \
                (name, s, r8["accuracy_gap_vs_fp32"])
    for codec in CODECS:
        b = [out["reddit-like"][codec][str(s)]["bytes_per_step"]
             for s in STALENESS]
        assert b[0] > b[1] > b[2], \
            f"{codec}: bytes/step not strictly decreasing: {b}"
    print("ASYNC_JSON " + json.dumps(out))


def main() -> None:
    env = dict(os.environ)
    # the payload re-imports this module, so it needs ROOT (for
    # ``benchmarks.common``) as well as SRC on the path
    env["PYTHONPATH"] = SRC + os.pathsep + ROOT
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={DEVICES}")
    r = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--payload"],
        capture_output=True, text=True, timeout=1200, env=env)
    blob = next((ln for ln in r.stdout.splitlines()
                 if ln.startswith("ASYNC_JSON ")), None)
    if r.returncode != 0 or blob is None:
        print(f"async/SUBPROCESS_FAILED,0.0,"
              f"err={r.stderr[-200:].replace(chr(10), ' ')}")
        raise RuntimeError(f"async payload failed (exit {r.returncode})")
    results = json.loads(blob[len("ASYNC_JSON "):])
    path = os.path.join(ROOT, "BENCH_async.json")
    with open(path, "w") as f:
        json.dump({"devices": DEVICES, "epochs": EPOCHS, "hidden": HIDDEN,
                   "refresh_frac": REFRESH_FRAC, "codecs": list(CODECS),
                   "results": results},
                  f, indent=2, sort_keys=True)
    for name, by_codec in results.items():
        for codec, rows in by_codec.items():
            for s, row in sorted(rows.items()):
                emit(f"async/{name}_{codec}_S{s}", row["step_ms"] * 1e3,
                     f"bytes_step={row['bytes_per_step']:.0f}"
                     f";acc={row['accuracy']:.3f}"
                     f";acc_gap={row['accuracy_gap']:.3f}"
                     f";bytes_vs_fp32={row['bytes_vs_fp32']:.2f}"
                     f";saved={row['comm_savings']:.1%}")
    print(f"async/BENCH_async_json,0.0,path={os.path.relpath(path, ROOT)}")


if __name__ == "__main__":
    if "--payload" in sys.argv:
        _payload()
    else:
        main()
