"""Launcher contracts: device counts are never clamped, and the entry
points keep JAX's compile cache at one fixed place."""
import math
import os

import jax
import pytest

from repro.launch import train_gnn
from repro.launch.compile_cache import enable_compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("mode", [["--minibatch"], ["--fullgraph"], []])
def test_more_devices_than_visible_fails(monkeypatch, mode):
    # the launcher forces virtual CPU devices only when XLA_FLAGS does not
    # already; pin it so the worker's environment is left as it was
    monkeypatch.setenv("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=1")
    n = jax.device_count() + 1
    with pytest.raises(SystemExit, match=f"--devices {n}: only"):
        train_gnn.main(mode + ["--devices", str(n), "--nodes", "64"])


def test_compile_cache_env_wins_else_checkout_dir(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "cache-from-env")
    assert enable_compile_cache() == "cache-from-env"
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        path = enable_compile_cache()
        assert path == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_minibatch_int8_wire_with_kernels_trains(monkeypatch):
    """``--wire-codec int8 --use-kernel`` on the single-device mini-batch
    path: miss rows arrive decoded, the Pallas kernels aggregate them."""
    monkeypatch.setenv("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=1")
    loss = train_gnn.main(["--minibatch", "--arch", "sage",
                           "--wire-codec", "int8", "--use-kernel",
                           "--nodes", "64", "--epochs", "1",
                           "--batch", "16"])
    assert math.isfinite(loss)
