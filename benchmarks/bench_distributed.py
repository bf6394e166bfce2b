"""Survey Tables 2 & 7, §3.2.5–§3.2.9: distributed GNN benchmarks (push vs
pull, data-parallel vs P3 hybrid, BSP vs stale sync, all-reduce vs PS) —
runs the payload in a subprocess with 8 forced host devices — plus the
partition-aware mini-batch pipeline's cross-partition traffic with and
without the halo cache (PaGraph claim, host-side accounting)."""
import os
import subprocess
import sys
import time

import numpy as np

from benchmarks.common import ROOT, SRC, emit


def _halo_traffic():
    """Cross-partition fetched bytes on the reddit-like graph along two
    axes: halo cache (degree policy, capacity = 10% of nodes) vs no
    cache, and wire codec (fp32 vs int8 — the communication-plane
    compression claim: int8 must cut remote feature bytes ~4x on the
    SAME sampled batches)."""
    from repro.distributed import DistributedMinibatchSampler
    from repro.graph.datasets import load

    g = load("reddit-like").graph
    n = g.num_nodes
    bytes_by = {}
    for policy in ("none", "degree"):
        for codec in ("fp32", "int8"):
            s = DistributedMinibatchSampler(
                g, 4, [5, 5], 64, partitioner="hash", cache_policy=policy,
                cache_capacity=n // 10, wire_codec=codec, seed=0)
            rng = np.random.default_rng(0)
            t0 = time.perf_counter()     # time sampling only, not setup
            for _ in range(8):
                s.sample_global(rng.choice(n, 64, replace=False))
            st = s.stats()
            bytes_by[policy, codec] = st["cross_partition_bytes"]
            emit(f"distributed/minibatch_xpart_{policy}_{codec}",
                 (time.perf_counter() - t0) * 1e6 / 8,
                 f"bytes={st['cross_partition_bytes']}"
                 f";hit={st['halo_hit_ratio']:.3f}")
    saving = 1.0 - bytes_by["degree", "fp32"] / max(bytes_by["none", "fp32"],
                                                    1)
    emit("distributed/halo_cache_saving", 0.0, f"saving={saving:.1%}")
    # compression claim (sampling is deterministic per seed, so both
    # codecs fetched exactly the same remote rows)
    ratio = bytes_by["none", "int8"] / max(bytes_by["none", "fp32"], 1)
    assert ratio <= 0.30, f"int8/fp32 cross-partition ratio {ratio:.3f}"
    emit("distributed/wire_codec_int8_ratio", 0.0, f"ratio={ratio:.1%}")


def main():
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "spmd_bench.py")],
        capture_output=True, text=True, timeout=900, env=env)
    if "SPMD_BENCH_DONE" not in r.stdout:
        print(f"distributed/SUBPROCESS_FAILED,0.0,"
              f"err={r.stderr[-200:].replace(chr(10), ' ')}")
        raise RuntimeError(f"distributed payload failed (exit {r.returncode})")
    for line in r.stdout.splitlines():
        if "," in line and not line.startswith("SPMD"):
            print(line, flush=True)
    _halo_traffic()


if __name__ == "__main__":
    main()
