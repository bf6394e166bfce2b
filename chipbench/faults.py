"""Faults planted in the program, to show that ``correct`` catches them.

Each fault replaces one piece of the program for as long as its context
manager is open; the harness then runs as usual and must report
``correct`` false.  The faults a training cell can have:

* ``state_unchanged``: the training step returns the state it was given;
* ``half_batch``: the step leaves out every second seed of the batch (or
  training node of the graph) and takes the mean over the rest;
* ``sampled_edge``: the sampler moves one sampled edge to another
  destination;
* ``fetched_row``: the feature store alters one element of one fetched row.

A one-chip cell has no exchange between chips, and a training cell no
served answer, so those faults do not apply here.
"""
from __future__ import annotations

import contextlib

TRAINING = ("state_unchanged", "half_batch")
MINIBATCH = ("sampled_edge", "fetched_row")


def faulty_step_maker(make_step, fault: str):
    """Wrap a ``make_*_train_step`` so that its steps carry ``fault``."""
    import jax.numpy as jnp

    def make(cfg, optimizer):
        step = make_step(cfg, optimizer)

        def faulty(params, opt_state, *args):
            *rest, mask = args
            if fault == "half_batch":
                keep = (jnp.arange(mask.shape[0]) % 2 == 0).astype(mask.dtype)
                return step(params, opt_state, *rest, mask * keep)
            _, _, loss = step(params, opt_state, *rest, mask)
            return params, opt_state, loss

        return faulty

    return make


@contextlib.contextmanager
def planted(fault: str):
    """Plant ``fault`` in the program while the block runs."""
    import numpy as np
    from repro.core import caching, sampling
    from repro.models.gnn import model as GM

    saved = []

    def patch(obj, name, value):
        saved.append((obj, name, getattr(obj, name)))
        setattr(obj, name, value)

    if fault in TRAINING:
        for name in ("make_minibatch_train_step", "make_fullgraph_train_step"):
            patch(GM, name, faulty_step_maker(getattr(GM, name), fault))
    elif fault == "sampled_edge":
        sample = sampling.NeighborSampler.sample

        def rewired(self, seeds):
            mb = sample(self, seeds)
            b = mb.blocks[0]
            e = int(np.flatnonzero(b.edge_mask)[0])
            b.edge_dst[e] = (b.edge_dst[e] + 1) % int(np.sum(b.dst_nodes >= 0))
            return mb

        patch(sampling.NeighborSampler, "sample", rewired)
    elif fault == "fetched_row":
        fetch = caching.FeatureStore.fetch_masked

        def altered(self, ids, needed):
            out = fetch(self, ids, needed)
            out[int(np.flatnonzero(needed)[0]), 0] += 1.0
            return out

        patch(caching.FeatureStore, "fetch_masked", altered)
    else:
        raise ValueError(f"unknown fault {fault!r}")
    try:
        yield
    finally:
        for obj, name, value in reversed(saved):
            setattr(obj, name, value)
