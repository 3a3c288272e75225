"""What the entry points settle with the device before they compile.

* :func:`enable_compile_cache` — JAX's persistent compilation cache, placed
  where ``JAX_COMPILATION_CACHE_DIR`` says or at a fixed directory inside
  the checkout. Called by each entry point's ``main``, never at import.
* :func:`describe_devices` / :func:`device_target` — the platform, kind and
  count JAX reports, and the registered hardware target of a TPU.
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Dict, Optional

import jax

from repro.core.target import HardwareTarget, target_for_device_kind

#: The checkout's root (``src/repro/launch/device.py`` -> three levels up).
REPO_ROOT = Path(__file__).resolve().parents[3]
#: Default cache directory: fixed, because the path is part of a cache
#: entry's key — a directory named per process or per run never hits.
CACHE_DIR = REPO_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX has already read it and
    nothing is changed here; otherwise the cache goes to :data:`CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)


def describe_devices() -> Dict[str, object]:
    """``platform``, ``kind`` and ``count`` of the devices JAX holds."""
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def device_target() -> Optional[HardwareTarget]:
    """The registered target of the TPU this process holds; ``None`` on any
    other platform. An unregistered TPU kind raises."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return None
    return target_for_device_kind(dev.device_kind)
