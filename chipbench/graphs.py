"""The benchmark's own graph generator and its on-disk cache.

A configuration's ``graph`` block names a public graph's shape: node count,
directed edge count, class count and feature width, plus the generator's
assumed parameters (degree tail, largest expected degree, homophily, class
separation).  :func:`generate` builds a degree-corrected block model with
those counts, in vectorised numpy:

* each node draws a class uniformly and an expected degree from a Pareto
  tail, scaled so the mean matches ``2 * edges / nodes`` and capped at
  ``max_expected_degree``;
* one endpoint of each undirected pair is drawn in proportion to the
  expected degree, the other from the same class with probability
  ``homophily`` (else from any class), again in proportion to degree;
* pairs are deduplicated as 1-D int64 keys ``min * N + max``, topped up
  until exactly ``edges / 2`` distinct pairs remain, written in both
  directions (plus one self-loop per node where ``self_loops``) and sorted
  straight into CSR;
* features are class centres plus unit noise, as ``featurize`` in the
  program does.

The arrays are cached under ``chipbench/.cache/graphs/`` keyed by the
configuration name, a hash of the ``graph`` block and
:data:`GEN_VERSION`, so only a cell's first run in a checkout generates.
"""
from __future__ import annotations

import hashlib
import json
import os
import shutil

import numpy as np

GEN_VERSION = 1
ARRAYS = ("row_ptr", "col_idx", "features", "labels", "train_mask")


def _expected_degrees(rng, n: int, mean: float, tail: float,
                      cap: float) -> np.ndarray:
    """Pareto(``tail``) expected degrees, capped at ``cap``, whose mean is
    ``mean`` (the scale is found by bisection)."""
    base = (1.0 - rng.random(n)) ** (-1.0 / tail)
    lo, hi = 1e-6, mean
    for _ in range(60):
        mid = 0.5 * (lo + hi)
        if np.minimum(mid * base, cap).mean() < mean:
            lo = mid
        else:
            hi = mid
    return np.minimum(hi * base, cap)


def generate(spec: dict) -> dict:
    """Arrays of the graph ``spec`` describes (see the module docstring)."""
    n, k, f = int(spec["nodes"]), int(spec["classes"]), int(spec["features"])
    target = int(spec["edges"]) // 2
    if int(spec["edges"]) % 2:
        raise ValueError("a symmetric graph has an even directed edge count")
    rng = np.random.default_rng(int(spec["graph_seed"]))
    labels = rng.integers(0, k, n).astype(np.int32)
    theta = _expected_degrees(rng, n, 2.0 * target / n,
                              float(spec["degree_tail"]),
                              float(spec["max_expected_degree"]))
    order = np.argsort(labels, kind="stable")
    cum = np.cumsum(theta[order])
    starts = np.searchsorted(labels[order], np.arange(k + 1))
    lo_c = np.concatenate([[0.0], cum])[starts[:-1]]
    hi_c = np.concatenate([[0.0], cum])[starts[1:]]
    homophily = float(spec["homophily"])

    def draw(r):
        return order[np.minimum(np.searchsorted(cum, r, side="right"), n - 1)]

    keys = np.zeros(0, np.int64)
    while len(keys) < target:
        m = int((target - len(keys)) * 1.15) + 1024
        a = draw(rng.random(m) * cum[-1])
        same = rng.random(m) < homophily
        c = labels[a]
        lo = np.where(same, lo_c[c], 0.0)
        hi = np.where(same, hi_c[c], cum[-1])
        b = draw(lo + rng.random(m) * (hi - lo))
        ok = a != b
        a, b = a[ok], b[ok]
        new = np.minimum(a, b).astype(np.int64) * n + np.maximum(a, b)
        keys = np.union1d(keys, new)
    if len(keys) > target:
        keys = np.delete(keys, rng.choice(len(keys), len(keys) - target,
                                          replace=False))
    u, v = np.divmod(keys, n)
    del keys
    parts = [u * n + v, v * n + u]
    if spec.get("self_loops"):
        parts.append(np.arange(n, dtype=np.int64) * (n + 1))
    directed = np.concatenate(parts)
    del parts, u, v
    directed.sort()
    src, col = np.divmod(directed, n)
    del directed
    row_ptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(src, minlength=n), out=row_ptr[1:])
    del src
    centres = rng.normal(0.0, float(spec["class_sep"]), (k, f)).astype(
        np.float32)
    features = rng.standard_normal((n, f), dtype=np.float32)
    features += centres[labels]
    train_mask = np.zeros(n, bool)
    n_train = int(spec.get("train_nodes") or n)
    train_mask[rng.permutation(n)[:n_train]] = True
    return {"row_ptr": row_ptr, "col_idx": col.astype(np.int32),
            "features": features, "labels": labels,
            "train_mask": train_mask}


def cache_key(name: str, spec: dict) -> str:
    blob = json.dumps(spec, sort_keys=True).encode()
    return (f"{name}-gs{spec['graph_seed']}-v{GEN_VERSION}-"
            f"{hashlib.sha256(blob).hexdigest()[:12]}")


def load_arrays(name: str, spec: dict, cache_root: str) -> tuple:
    """``(arrays, generated)``: the cached arrays of configuration ``name``,
    generated and written first where the cache lacks them."""
    d = os.path.join(cache_root, "graphs", cache_key(name, spec))
    if all(os.path.exists(os.path.join(d, a + ".npy")) for a in ARRAYS):
        return {a: np.load(os.path.join(d, a + ".npy")) for a in ARRAYS}, False
    arrays = generate(spec)
    tmp = d + ".partial"
    shutil.rmtree(tmp, ignore_errors=True)
    os.makedirs(tmp)
    for a in ARRAYS:
        np.save(os.path.join(tmp, a + ".npy"), arrays[a])
    shutil.rmtree(d, ignore_errors=True)
    os.replace(tmp, d)
    return arrays, True


def to_program_graph(arrays: dict, num_classes: int):
    """The program's ``Graph`` over the benchmark's arrays."""
    from repro.graph.structure import Graph
    return Graph(row_ptr=arrays["row_ptr"], col_idx=arrays["col_idx"],
                 features=arrays["features"], labels=arrays["labels"],
                 num_classes=num_classes)


def directed_keys(arrays: dict) -> np.ndarray:
    """Sorted int64 keys ``src * N + dst`` of every CSR edge, for
    membership checks against the graph."""
    row_ptr, col = arrays["row_ptr"], arrays["col_idx"]
    n = len(row_ptr) - 1
    src = np.repeat(np.arange(n, dtype=np.int64), np.diff(row_ptr))
    src *= n
    src += col
    return src
