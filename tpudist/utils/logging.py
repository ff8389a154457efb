"""Rank-stamped logging.

The reference's observability is bare ``print`` with manual rank prefixes
(`mnist_ddp_elastic.py:88`, `horovod_mnist_elastic.py:73` — SURVEY.md §5).
Here: standard :mod:`logging` with a ``[pN]`` process stamp.

The process index is resolved *lazily at emission time, and only if a JAX
backend already exists* — calling ``jax.process_index()`` eagerly would
initialize the backend as an import side effect (and on TPU that means
touching the runtime before the trainer decides how), so loggers must never
be the first thing that talks to the hardware.
"""

from __future__ import annotations

import logging
import sys

_configured = False


def _process_index_if_initialized() -> int:
    """Process index without forcing backend initialization."""
    try:
        from jax._src import xla_bridge as xb

        if getattr(xb, "_backends", None):
            import jax

            return jax.process_index()
    except Exception:
        pass
    return 0


class _RankFormatter(logging.Formatter):
    def format(self, record: logging.LogRecord) -> str:
        record.rank = _process_index_if_initialized()
        return super().format(record)


def get_logger(name: str = "tpudist") -> logging.Logger:
    global _configured
    logger = logging.getLogger(name)
    if not _configured:
        handler = logging.StreamHandler(sys.stderr)
        handler.setFormatter(
            _RankFormatter(
                fmt="%(asctime)s [p%(rank)s] %(name)s %(levelname)s: %(message)s",
                datefmt="%H:%M:%S",
            )
        )
        root = logging.getLogger("tpudist")
        root.addHandler(handler)
        root.setLevel(logging.INFO)
        root.propagate = False
        _configured = True
    return logger

