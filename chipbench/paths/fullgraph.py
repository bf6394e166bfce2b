"""Full-graph training path: the program's single-device full-batch step
(``make_fullgraph_train_step`` over ``DeviceGraph.from_graph``), as
``launch/train_gnn.py`` runs it without ``--minibatch``.

Set-up uploads the graph, the features, the labels and the training mask
once.  Each step of the window waits for the step ``max_inflight_steps``
back (``device_wait``) and dispatches the next (``dispatch``); there is no
sampler and no host fetch.  The loss is taken over the configuration's
training nodes.
"""
from __future__ import annotations

import collections
import time

import numpy as np

from chipbench import compare
from chipbench.references import common


STEP_MAKER = "make_fullgraph_train_step"


def step_args(cfg: dict, mix: dict, spec) -> tuple:
    """The step's arguments after the weights and optimizer state, as
    ``spec(shape, dtype)`` makes them, at the cell's sizes."""
    import jax.numpy as jnp
    from repro.core.abstraction import DeviceGraph
    g = cfg["graph"]
    n = g["nodes"]
    e = g["edges"] + (n if g.get("self_loops") else 0)
    dg = DeviceGraph(spec((e,), jnp.int32), spec((e,), jnp.int32),
                     spec((e,), jnp.bool_), n, n, spec((n,), jnp.float32),
                     spec((n,), jnp.float32))
    return (dg, spec((n, cfg["model"]["in_features"]), jnp.float32),
            spec((n,), jnp.int32), spec((n,), jnp.float32))


class Session:
    def __init__(self, ctx):
        import jax
        import jax.numpy as jnp
        from repro.core.abstraction import DeviceGraph
        from repro.models.gnn import model as GM
        from repro.models.gnn.model import GNNConfig
        from repro.optim import AdamW

        self.ctx = ctx
        cfg = ctx.config
        g = ctx.program_graph()
        with ctx.spans("setup.upload"):
            self.dg = DeviceGraph.from_graph(g)
            self.x = jnp.asarray(g.features)
            self.y = jnp.asarray(g.labels)
            self.mask = jnp.asarray(ctx.graph_arrays()["train_mask"],
                                    jnp.float32)
        m = cfg["model"]
        self.model = GNNConfig(arch=m["arch"], feat_dim=m["in_features"],
                               hidden=m["hidden"], num_classes=m["classes"],
                               num_layers=m["layers"],
                               use_kernel=cfg["use_kernel"])
        self.opt = AdamW(**cfg["optimizer"])
        self.step = jax.jit(GM.make_fullgraph_train_step(self.model,
                                                         self.opt))
        self.n_nodes, self.n_edges = g.num_nodes, g.num_edges
        self.inflight = collections.deque()

    def _one_step(self):
        sp = self.ctx.spans
        if len(self.inflight) >= self.ctx.mix["max_inflight_steps"]:
            with sp("device_wait"):
                self.inflight.popleft().block_until_ready()
        with sp("dispatch"):
            self.params, self.ostate, loss = self.step(
                self.params, self.ostate, self.dg, self.x, self.y, self.mask)
        self.inflight.append(loss)
        return loss

    def start(self, seed: int) -> dict:
        import jax
        self.params = self.ctx.init_params(seed)
        self.ostate = jax.jit(self.opt.init)(self.params)
        params0 = jax.tree.map(np.asarray, self.params)
        losses, first_m = [], None
        for k in range(self.ctx.mix["first_steps"]):
            losses.append(self._one_step())
            if k == 0:
                first_m = jax.tree.map(np.asarray, self.ostate["m"])
        jax.block_until_ready((self.params, self.ostate))
        b1 = self.ctx.config["optimizer"]["b1"]
        return {"losses": [float(l) for l in losses],
                "first_grad": jax.tree.map(lambda m: m / (1 - b1), first_m),
                "params0": params0,
                "params_after": jax.tree.map(np.asarray, self.params)}

    def warm(self):
        """``warm_steps`` untimed steps through the window's own call."""
        import jax
        for _ in range(self.ctx.mix["warm_steps"]):
            self._one_step()
        jax.block_until_ready((self.params, self.ostate))

    def window(self, seconds: float) -> dict:
        import jax
        sp = self.ctx.spans
        losses = []
        t0 = time.perf_counter()
        with sp("window"):
            while True:
                losses.append(self._one_step())
                if time.perf_counter() - t0 >= seconds:
                    break
            with sp("block"):
                jax.block_until_ready((self.params, self.ostate))
        t1 = time.perf_counter()
        self.inflight.clear()
        steps = len(losses)
        failed = sum(1 for l in losses if not np.isfinite(float(l)))
        return {"t0": t0, "t1": t1, "steps": steps, "failed": failed,
                "end_to_end": {
                    "fullgraph_epoch_ms": 1e3 * (t1 - t0) / steps},
                "counts": [(self.n_nodes, self.n_edges)] * steps,
                "edge_lengths": [self.n_edges],
                "counters": {}}

    def stop(self):
        self.inflight.clear()

    def close(self):
        self.stop()
        self.params = self.ostate = self.step = None
        self.dg = self.x = self.y = self.mask = None


def reference_batch(arrays: dict) -> dict:
    row_ptr = arrays["row_ptr"]
    n = len(row_ptr) - 1
    return {"src": np.repeat(np.arange(n, dtype=np.int32), np.diff(row_ptr)),
            "dst": arrays["col_idx"].astype(np.int32),
            "x": arrays["features"], "labels": arrays["labels"],
            "label_mask": arrays["train_mask"].astype(np.float32)}


def reference_run(ctx, first: dict, precision: str) -> dict:
    import jax
    batch = jax.device_put(reference_batch(ctx.graph_arrays()))
    losses, grad, after = common.train(
        ctx.reference.loss, first["params0"],
        [batch] * ctx.mix["first_steps"], ctx.config["optimizer"], precision)
    return {"losses": losses, "first_grad": grad,
            "params0": first["params0"], "params_after": after}


def check(ctx, first: dict) -> dict:
    import sys
    ref = reference_run(ctx, first, "highest")
    numbers, notes = compare.training_numbers(first, ref)
    for line in notes:
        print(line, file=sys.stderr)
    return numbers
