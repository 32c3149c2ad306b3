"""Device detection, the compile-cache location, and topology resolution:
none of them may turn a missing or failing accelerator into the CPU path."""
import os
import types

import jax
import pytest

from repro.configs import platform
from repro.parallel.topology import TopologySpec


def test_detect_platform_lets_device_errors_through(monkeypatch):
    def broken():
        raise RuntimeError("backend init failed")

    monkeypatch.setattr(jax, "devices", broken)
    with pytest.raises(RuntimeError, match="backend init failed"):
        platform.detect_platform()
    with pytest.raises(RuntimeError, match="backend init failed"):
        platform.detect_device_kind()


@pytest.mark.parametrize("env", [None, "custom"])
def test_enable_compile_cache_location(monkeypatch, tmp_path, env):
    was = jax.config.jax_compilation_cache_dir
    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), ".jax_cache")
    else:
        want = str(tmp_path / env)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
    try:
        assert platform.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def _fake_devices(monkeypatch, kind, n):
    devs = [types.SimpleNamespace(platform=kind, id=i) for i in range(n)]
    monkeypatch.setattr(jax, "devices", lambda *a: devs)


def test_short_2d_topology_loops_on_cpu(monkeypatch):
    _fake_devices(monkeypatch, "cpu", 1)
    assert not TopologySpec(data=2, lanes=2).resolve().on_devices


@pytest.mark.parametrize("spec", [TopologySpec(data=2, lanes=2),
                                  TopologySpec(lanes=4)])
def test_short_topology_fails_on_an_accelerator(monkeypatch, spec):
    _fake_devices(monkeypatch, "tpu", 1)
    with pytest.raises(ValueError, match="needs 4 devices, found 1 tpu"):
        spec.resolve()
