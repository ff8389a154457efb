"""CPU-simulated device meshes — the single place that knows the dance.

The TPU-native analog of the reference's ``mp.spawn``-on-localhost pattern
(`model_parallel_ResNet50.py:260`, SURVEY.md §4): N fake CPU devices let
mesh/sharding/elastic code run anywhere.  ``JAX_PLATFORMS`` is read when jax
is imported and ``XLA_FLAGS`` when the backend initializes; importing this
module imports jax (through ``tpudist``), so the platform is set in the
environment (for child processes) AND in ``jax.config`` (for this one).
Must be called before anything initializes a JAX backend.
"""

from __future__ import annotations

import os


def force_cpu_devices(n: int = 8, check: bool = True) -> None:
    """Force the CPU platform with ``n`` simulated devices.

    ``check=False`` skips the device-count probe — required in processes
    that will call ``jax.distributed.initialize`` afterwards (the probe
    itself initializes the XLA backend, which must not happen first).
    """
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}"
        ).strip()
    import jax

    jax.config.update("jax_platforms", "cpu")
    if check and jax.device_count() < n:
        raise RuntimeError(
            f"requested {n} simulated devices but the backend was already "
            f"initialized with {jax.device_count()}; call force_cpu_devices "
            "before any jax device query (XLA_FLAGS is read only once)"
        )
