"""Multi-host bootstrap and world introspection.

Replaces the reference's process-group / MPI / RPC initialization
(`mnist_ddp_elastic.py:22-27`, `mnist_horovod.py:28`,
`model_parallel_ResNet50.py:233-249` — SURVEY.md §2.2): on TPU, multi-host
training is one Python process per host, coordinated by
``jax.distributed.initialize`` over DCN; all tensor traffic then rides ICI via
XLA collectives, so there is no NCCL/gloo/MPI anywhere.

Single-host (including the CPU-simulated test meshes) needs no bootstrap at
all — ``initialize`` is a no-op there, mirroring how the reference's
``mp.spawn`` examples self-host a world on localhost
(`model_parallel_ResNet50.py:257-260`).
"""

from __future__ import annotations

import dataclasses
import os

import jax


@dataclasses.dataclass(frozen=True)
class DistributedContext:
    """What the reference reads from RANK/WORLD_SIZE env vars
    (`mnist_ddp_elastic.py:44-45`), derived here from the JAX runtime."""

    process_index: int
    process_count: int
    local_device_count: int
    global_device_count: int

    @property
    def is_coordinator(self) -> bool:
        return self.process_index == 0


_initialized = False

# Env vars whose presence indicates a managed multi-host launch where
# ``jax.distributed.initialize()`` can auto-detect everything.
_CLUSTER_ENV_HINTS = (
    "JAX_COORDINATOR_ADDRESS",
    "COORDINATOR_ADDRESS",
    "MEGASCALE_COORDINATOR_ADDRESS",
)


def _launcher_env() -> tuple[str, int, int] | None:
    """World description exported by :mod:`tpudist.runtime.launch` (the
    RANK/WORLD_SIZE env contract, `mnist_ddp_elastic.py:44-45`)."""
    addr = os.environ.get("TPUDIST_COORDINATOR")
    if not addr:
        return None
    return (addr, int(os.environ["TPUDIST_NUM_PROCESSES"]),
            int(os.environ["TPUDIST_PROCESS_ID"]))


def _detected_multihost() -> bool:
    """True only for an actual multi-host topology: a coordinator address,
    or a TPU worker list naming more than one host (a single-entry
    ``TPU_WORKER_HOSTNAMES`` — one host with its chips attached — needs
    no bootstrap and ``initialize`` would fail on it)."""
    if any(os.environ.get(k) for k in _CLUSTER_ENV_HINTS):
        return True
    hostnames = os.environ.get("TPU_WORKER_HOSTNAMES", "")
    return len([h for h in hostnames.split(",") if h.strip()]) > 1


def initialize(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> DistributedContext:
    """Bootstrap the (possibly multi-host) runtime. Idempotent.

    ``jax.distributed.initialize`` is invoked when (a) explicit arguments are
    given, or (b) a cluster environment is detectable (coordinator env vars /
    TPU pod metadata hints).  On a plain single host neither holds and no
    bootstrap is needed.  If a detected bootstrap *fails*, this raises rather
    than silently training N independent single-host models — the equivalent
    failure mode of forgetting ``init_process_group``
    (`mnist_ddp_elastic.py:26`).
    """
    global _initialized
    # Elastic recovery = process restart + re-jit (SURVEY.md §5), so a
    # restarted worker's compiles should be warm.  JAX itself reads an
    # ambient JAX_COMPILATION_CACHE_DIR (the launcher/test env exports
    # it); the call adds the compile telemetry and sets no directory.
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        from tpudist.runtime.cache import enable_compilation_cache

        enable_compilation_cache()
    launcher = _launcher_env()
    if launcher is not None and coordinator_address is None:
        coordinator_address, num_processes, process_id = launcher
    explicit = coordinator_address is not None or num_processes is not None
    detected = _detected_multihost()
    if not _initialized and (explicit or detected):
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
        _initialized = True
    return world_info()


def world_info() -> DistributedContext:
    return DistributedContext(
        process_index=jax.process_index(),
        process_count=jax.process_count(),
        local_device_count=jax.local_device_count(),
        global_device_count=jax.device_count(),
    )


def process_index() -> int:
    return jax.process_index()


def process_count() -> int:
    return jax.process_count()


def local_rank() -> int:
    """Index of this process among processes on the same host (LOCAL_RANK
    equivalent, `mnist_ddp_elastic.py:45`). TPU runs one process per host, so
    this is 0 except under explicit multi-process-per-host launches."""
    return int(os.environ.get("TPUDIST_LOCAL_RANK", "0"))
