"""Deliverable (g): roofline table from the dry-run artifacts.

Reads results/dryrun_all.json (written by `python -m repro.launch.dryrun
--all --json ...`) and prints per (arch x shape) the three roofline terms,
the dominant bottleneck, and the MODEL_FLOPS/HLO_FLOPs useful ratio.
If the sweep artifact is missing it emits a pointer instead of failing.

A second, artifact-free section maps the locality-reordering policies
(survey §3.2.4) onto the blocked kernels' tile geometry: per graph x
policy it emits the VMEM-residency / tile-density metrics
(``repro.kernels.segment_sum.edge_tile_density``) next to the static
locality numbers.
"""
import json
import os

from benchmarks.common import ROOT, build_graph, emit

SWEEP = os.path.join(ROOT, "results", "dryrun_all.json")


def reorder_density():
    """Tile-density roofline axis: how each reorder policy changes the
    (dst-tile, edge-tile) grid occupancy and the per-tile source
    working set the blocked kernels sweep."""
    from repro.core.reordering import locality_report
    from repro.kernels.segment_sum import edge_tile_density
    for name in ("er", "sbm", "reddit-like"):
        g = build_graph(name)
        for policy in ("none", "degree", "bfs", "rcm"):
            gp, perm, inv = g.reordered(policy)
            e = gp.edges()
            td = edge_tile_density(e[:, 0], e[:, 1], gp.num_nodes)
            rep = locality_report(gp)
            emit(f"roofline/tile_density/{name}/{policy}", 0.0,
                 f"active_tile_frac={td['active_tile_frac']:.3f};"
                 f"src_rows_per_edge_tile="
                 f"{td['src_rows_per_edge_tile']:.1f};"
                 f"gather_stride={rep['avg_gather_stride']:.1f};"
                 f"reuse_hit={rep['reuse_hit_rate']:.3f}")


def main():
    reorder_density()
    if not os.path.exists(SWEEP):
        emit("roofline/missing", 0.0,
             "run: python -m repro.launch.dryrun --all --json "
             "results/dryrun_all.json")
        return
    rows = json.load(open(SWEEP))
    for r in sorted(rows, key=lambda x: (x["arch"], x["shape"], x["mesh"])):
        if r["mesh"] != "16x16":
            continue           # roofline table is single-pod (per brief)
        name = f"roofline/{r['arch']}/{r['shape']}"
        if r["status"] == "SKIP":
            emit(name, 0.0, f"SKIP:{r['reason'][:60]}")
            continue
        if r["status"] != "OK":
            emit(name, 0.0, "FAIL")
            continue
        rf = r["roofline"]
        step_s = max(rf["compute_s"], rf["memory_s"], rf["collective_s"])
        emit(name, step_s * 1e6,
             f"C={rf['compute_s']:.4f}s;M={rf['memory_s']:.4f}s;"
             f"X={rf['collective_s']:.4f}s;dom={rf['dominant']};"
             f"useful={r['useful_ratio']};"
             f"mem_gib={r['bytes_per_device'] / 2**30:.2f}")
    n_multi = sum(1 for r in rows if r["mesh"] == "2x16x16"
                  and r["status"] == "OK")
    emit("roofline/multi_pod_lowered", 0.0, f"combos_ok={n_multi}")


if __name__ == "__main__":
    main()
