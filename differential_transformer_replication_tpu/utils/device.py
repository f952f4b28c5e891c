"""What the program asks of the device it runs on, in one place.

- :func:`setup_compile_cache` places JAX's persistent compilation
  cache. Every entry point that jits (``train()``, the server's
  ``main``, ``bench.py``, ``chip_smoke.py``, the measurement tools)
  calls it before its first compile.
- :func:`require_tpu` is the measurement scripts' guard: a timing taken
  on the CPU backend measures XLA:CPU or the Pallas interpreter, which
  nobody deploys, so they exit instead of printing one
  (:func:`start_measurement` is the two together).
- :func:`device_summary` is the device line every result carries, and
  :func:`peak_memory_bytes` the memory high-water mark beside it.
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

import jax

# <checkout>/.jax_cache (git-ignored). The path is part of the cache
# key, so it is fixed: never tmp, pid or time.
_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def setup_compile_cache() -> Optional[str]:
    """Place the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it
    and nothing is set in code (a machine that comes with it set keeps
    its cache across runs). Otherwise the cache lives at the fixed path
    ``<checkout>/.jax_cache``, so a second process of the same checkout
    finds what the first compiled. The CPU backend gets none (returns
    None): the cache is there to save chip time, and XLA:CPU loads its
    cached executables with machine-feature errors on every hit."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if jax.default_backend() == "cpu":
        return None
    jax.config.update("jax_compilation_cache_dir", str(_CACHE_DIR))
    return str(_CACHE_DIR)


def device_summary() -> dict:
    """``{"platform", "kind", "count"}`` as JAX reports the devices."""
    dev = jax.devices()
    return {
        "platform": dev[0].platform,
        "kind": dev[0].device_kind,
        "count": len(dev),
    }


def peak_memory_bytes() -> Optional[int]:
    """``peak_bytes_in_use`` of the first local device, or None where the
    backend keeps no memory stats (the CPU)."""
    stats = jax.local_devices()[0].memory_stats()
    return stats.get("peak_bytes_in_use") if stats else None


def require_tpu(what: str) -> dict:
    """Exit unless JAX's default backend is a TPU; returns
    :func:`device_summary` otherwise. ``what`` names the caller in the
    message."""
    if jax.default_backend() != "tpu":
        raise SystemExit(
            f"{what}: JAX found no TPU (default backend "
            f"{jax.default_backend()!r}, devices {jax.devices()}). A "
            "timing from the CPU backend or the Pallas interpreter is "
            "not a device measurement; run this on the chip."
        )
    return device_summary()


def start_measurement(what: str, smoke: bool = False) -> dict:
    """The first call of a measurement script: :func:`require_tpu`, then
    :func:`setup_compile_cache`; returns the device line. ``smoke`` is
    the script's own tiny CI gate (interpret-mode parity and plumbing,
    whose timings are not measurements), which runs anywhere."""
    device = device_summary() if smoke else require_tpu(what)
    setup_compile_cache()
    return device
