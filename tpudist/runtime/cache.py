"""Persistent XLA compilation cache.

First-compile latency on a TPU backend can reach minutes for scanned
train loops (conv nets under ``lax.scan``); a persistent on-disk cache makes
every subsequent process start warm.  The reference has no analog (eager
PyTorch compiles nothing); for tpudist the cache is what keeps the
compile-once-run-everywhere contract cheap across process restarts — which
elastic training does constantly (SURVEY.md §5 "failure detection":
recovery = process restart + re-jit).

Where the cache lives is decided outside the program: JAX itself reads
``JAX_COMPILATION_CACHE_DIR``, and when that is set this module sets no
directory in code.  Otherwise the cache sits at ONE fixed path inside the
checkout — the directory is part of the cache key, so a path that moves
(a temporary name, a pid, a time) never hits.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_CACHE_DIR = str(Path(__file__).resolve().parents[2] / ".jax_cache")


def enable_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache (idempotent).  Returns
    the directory in use: ``JAX_COMPILATION_CACHE_DIR`` where the
    environment sets it (JAX already uses it; nothing is set here),
    else :data:`DEFAULT_CACHE_DIR`."""
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = DEFAULT_CACHE_DIR
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # a process that cares about compile cost wants the xla/compiles
    # counter + duration histogram too (the recompile-storm detector);
    # idempotent, no-op if jax lacks the hooks
    from tpudist.obs.xla import install_compile_telemetry

    install_compile_telemetry()
    return cache_dir
