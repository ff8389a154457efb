"""Shared launcher plumbing for the example twins.

The reference's L5 layer (SURVEY.md §1) is torchrun / horovodrun /
``mp.spawn``; on TPU one Python process per host drives every local device,
so "launching a world" is just importing jax — plus, for laptops and CI, an
optional CPU-simulated mesh (the ``mp.spawn``-on-localhost equivalent,
SURVEY.md §4).

``--sim-devices N`` must take effect before jax initializes, so examples call
:func:`setup_platform` with raw ``sys.argv`` before importing jax.
"""

from __future__ import annotations

import os
import sys
from typing import Sequence

# Examples are runnable from anywhere: `python examples/foo_tpu.py` puts only
# examples/ on sys.path, so add the repo root for the tpudist package.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def setup_platform(argv: Sequence[str] | None = None) -> list[str]:
    """Consume ``--sim-devices N`` from ``argv`` (before jax import).

    Returns the remaining argv.  With N > 0, forces the CPU backend with N
    simulated devices; otherwise the ambient platform (real TPU when
    present) is used.
    """
    argv = list(sys.argv[1:] if argv is None else argv)
    # Spawned by tpudist.runtime.launch with the CPU platform (a chip
    # belongs to one process, so N launcher workers on one host get the
    # CPU): JAX_PLATFORMS=cpu alone sticks, nothing to force.
    sim = (os.environ.get("JAX_PLATFORMS") == "cpu"
           and "TPUDIST_NUM_PROCESSES" in os.environ)
    if "--sim-devices" in argv:
        i = argv.index("--sim-devices")
        n = int(argv[i + 1])
        del argv[i : i + 2]
        if n > 0:
            sim = True
            from tpudist.runtime.simulate import force_cpu_devices

            force_cpu_devices(n)
    if not sim:
        # The ambient platform, as it is: with a TPU expected and none
        # found jax fails at start-up, it does not degrade to the CPU.
        # Accelerator backends pay long first compiles; cache persistently.
        from tpudist.runtime.cache import enable_compilation_cache

        enable_compilation_cache()
    return argv
