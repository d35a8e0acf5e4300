"""Process setup shared by the entry points (``chip_smoke.py``,
``launch/serve.py``, the benchmarks' ``main``): the persistent compile
cache, host-platform devices for CPU runs, and the device label printed
next to every figure.  Library code and tests never call these."""
from __future__ import annotations

import os
import pathlib

import jax

#: the checkout's own cache directory (listed in .gitignore); fixed, since
#: the path is part of what a cache hit has to match
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compile cache and return its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is set; otherwise the cache lives in ``<repo>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)


def ensure_host_devices(n: int) -> None:
    """Give a CPU run ``n`` host-platform devices for ``--tp n``.

    Applies only when ``JAX_PLATFORMS`` names ``cpu``: on an accelerator a
    failed backend must not fall back to fake CPU devices that make a
    ``--tp`` run "work".  Must run before the first jax operation; if the
    backend is already up with too few devices, ``make_tp_mesh`` raises."""
    if n <= 1 or "cpu" not in os.environ.get("JAX_PLATFORMS", "").split(","):
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}").strip()


def device_label() -> str:
    """``<platform> <device_kind> x<count>`` as JAX reports the devices."""
    devs = jax.devices()
    return f"{devs[0].platform} {devs[0].device_kind} x{len(devs)}"
