"""Process start-up: which JAX platform this process runs on, and where its
compiled programs are kept.

One accelerator chip belongs to one process at a time, so every launcher
decides the platform BEFORE it starts a process that will touch JAX
(``visible_chips`` counts chips without importing JAX), and every entry point
that compiles calls ``enable_compile_cache`` once before its first jit.
Tests and dry runs pin the CPU backend through ``force_cpu_platform``.
"""

from __future__ import annotations

import glob
import os

_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
# Fixed in-checkout location: the directory is part of every cache key, so
# a temp name, pid or timestamp here would never hit.
DEFAULT_COMPILE_CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_cache")


def cpu_pinned() -> bool:
    """True when the operator asked for JAX's CPU backend explicitly."""
    return os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"


def force_cpu_platform(n_virtual_devices: int | None = None) -> None:
    """Pin jax to the CPU backend; optionally request N virtual devices.

    The virtual-device flag only takes effect if the CPU backend has not
    initialized yet (XLA reads XLA_FLAGS at backend-init time).
    """
    if n_virtual_devices is not None:
        flag = f"--xla_force_host_platform_device_count={n_virtual_devices}"
        if "--xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " " + flag).strip()
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")


def enable_compile_cache() -> str | None:
    """Turn on JAX's persistent compilation cache; returns its directory:
    ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX reads
    it itself and no directory is set in code), else
    ``<checkout>/.jax_cache``. The engine compiles one small program per
    (plan, row bucket), most of them under JAX's default 1 s write
    threshold, so the thresholds go to "cache everything".

    A CPU-pinned process (tests, dry runs) keeps the cache off and gets
    None: XLA's CPU loader logs a multi-kilobyte machine-feature mismatch
    error for every cached program it loads, even on the machine that
    compiled it, and CPU compiles of these programs take milliseconds.
    """
    if cpu_pinned():
        return None
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_COMPILE_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def device_info() -> dict:
    """``{"platform", "device_kind", "count"}`` as JAX reports the default
    backend. Initializes that backend: on an accelerator the calling
    process holds the chip from here on."""
    import jax

    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "count": len(devs),
    }


def visible_chips() -> int:
    """Accelerator chips this host exposes, counted WITHOUT touching JAX
    (a launcher that initialized a backend would hold the chip its
    children need). 0 when the operator pinned the CPU backend. TPU chips
    surface as one vfio group (``/dev/vfio/<n>``) or one ``/dev/accel<n>``
    node each."""
    if cpu_pinned():
        return 0
    nodes = [
        p for p in glob.glob("/dev/vfio/*") if os.path.basename(p).isdigit()
    ]
    return len(nodes) or len(glob.glob("/dev/accel*"))
