"""Each cell's training step compiled at the cell's real shapes for a
described TPU v5e chip (no chip attached).

The topology is described inside a module fixture, never while a module is
imported: only one process may load the TPU library, and test workers
import every test file.  The program picks interpret mode for its Pallas
kernels from the backend, which is the CPU here, so the test steers it to
compile them for the chip.
"""
import functools
import json
import os

import jax
import pytest
from jax.sharding import SingleDeviceSharding

from chipbench.registry import Registry

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELLS = [w["name"] for w in json.load(
    open(os.path.join(ROOT, "BENCHMARK.json")))["workloads"]]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:           # noqa: BLE001 — any failure means skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """A sharding on one described chip, with the persistent compile cache
    off meanwhile: an entry written for a described chip cannot be read
    back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("cell", CELLS)
def test_cell_step_compiles_for_v5e(one_chip, monkeypatch, cell):
    from repro.kernels import ops as kops
    from repro.models.gnn import model as GM
    from repro.models.gnn.model import GNNConfig
    from repro.optim import AdamW

    monkeypatch.setattr(kops, "_interpret", lambda: False)
    parts = Registry(ROOT).resolve(cell)
    cfg, mix, path = parts["config"], parts["mix"], parts["path"]
    m = cfg["model"]
    model = GNNConfig(arch=m["arch"], feat_dim=m["in_features"],
                      hidden=m["hidden"], num_classes=m["classes"],
                      num_layers=m["layers"], use_kernel=cfg["use_kernel"])
    opt = AdamW(**cfg["optimizer"])
    step = getattr(GM, path.STEP_MAKER)(model, opt)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def on_chip(tree):
        return jax.tree.map(lambda a: spec(a.shape, a.dtype), tree)

    params = on_chip(jax.eval_shape(functools.partial(
        parts["reference"].init, cfg), jax.random.PRNGKey(0)))
    ostate = on_chip(jax.eval_shape(opt.init, params))
    args = path.step_args(cfg, mix, spec)
    with jax.default_matmul_precision(cfg["matmul_precision"]):
        compiled = jax.jit(step).lower(params, ostate, *args).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < 16e9, f"{cell}: {total} bytes do not fit one v5e chip"
    if cfg["use_kernel"]:
        assert "tpu_custom_call" in compiled.as_text()
