"""utils/device.py: where the compile cache goes, and the measurement
scripts' no-chip guard."""

from pathlib import Path

import jax
import pytest

from differential_transformer_replication_tpu.utils import device

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_config():
    """Hand the test the config entry and put it back afterwards —
    JAX's memo of "is the cache in use" with it, or every later compile
    of this process would take the cache path (and serialize XLA:CPU
    executables for a cache that is not there)."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_compilation_cache_dir
    yield lambda: jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", was)
    compilation_cache.reset_cache()


def test_cache_dir_from_outside_is_left_alone(monkeypatch, cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    before = cache_config()
    assert device.setup_compile_cache() == "/somewhere/else"
    assert cache_config() == before  # nothing set in code


def test_cache_dir_is_a_fixed_path_in_the_checkout(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert device.setup_compile_cache() == str(REPO / ".jax_cache")
    assert cache_config() == str(REPO / ".jax_cache")
    # the path is part of the cache key: a second call gives the same
    assert device.setup_compile_cache() == str(REPO / ".jax_cache")
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()


def test_cpu_backend_gets_no_cache(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = cache_config()
    assert device.setup_compile_cache() is None
    assert cache_config() == before


def test_require_tpu_exits_on_the_cpu_and_names_what_it_found():
    with pytest.raises(SystemExit, match="bench: JAX found no TPU.*'cpu'"):
        device.require_tpu("bench")
    with pytest.raises(SystemExit):
        device.start_measurement("sweep")
    # a script's own --smoke gate runs anywhere, and says where
    summary = device.start_measurement("sweep", smoke=True)
    assert summary == device.device_summary()
    assert summary == {"platform": "cpu", "kind": "cpu",
                       "count": jax.device_count()}
    assert device.peak_memory_bytes() is None  # the CPU keeps no stats
